"""The KB call's share of its roofline: the least time the chip could take
for the work an exact scan must do (read the KB once, the queries, write the
top-k; or its FLOPs, whichever bounds), over the device time of the whole
jitted ``dense_topk`` call. The work is counted from shapes (``work.py``)
with the query counts of the calls the trace saw."""
from bench import work


def read(run):
    t = run.trace
    n = t.program_n("dense_topk") if t is not None else 0
    if not n or not run.kb_batches:
        return None
    kb = run.cfg["kb"]
    k = run.kb_k
    pk = work.peaks(run.device_kind)
    need = sum(work.roofline_s(work.kb_call_flops(kb["rows"], kb["dim"], B),
                               work.kb_call_bytes(kb["rows"], kb["dim"], B, k),
                               pk) for B in run.kb_batches) / len(run.kb_batches)
    return 100.0 * need / (t.program_s("dense_topk") / n)
