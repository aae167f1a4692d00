"""Mean host milliseconds of one ``engine.logits_fetch`` span: one slot's
full-vocabulary logits row copied from the device for the KNN-LM
interpolation. None where the span never ran."""


def read(run):
    spans = getattr(run.result, "spans", None) or {}
    if "engine.logits_fetch" not in spans:
        return None
    n, total, _ = spans["engine.logits_fetch"]
    return 1e3 * total / n
