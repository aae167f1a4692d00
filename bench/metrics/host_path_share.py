"""Share of the window in which the serving thread was not blocked on the
device: 100 x (1 - the total of the program's ``device.wait`` spans over
the window's ``serve()`` call / the window's wall seconds). The spans sum
over threads; with async rounds (a variant with ``a``) the verification
worker waits on the device while the serving thread runs, so the share of
the serving thread alone cannot be read and this returns None, as it does
where the span never ran."""


def read(run):
    spans = getattr(run.result, "spans", None) or {}
    if "device.wait" not in spans or not run.window_s:
        return None
    if "a" in run.cfg.get("serving", {}).get("variant", ""):
        return None
    return 100.0 * (1.0 - spans["device.wait"][1] / run.window_s)
