"""Device time of one execution of the jitted KB call (``dense_topk``: the
per-call pad of the KB to the kernel's block and the Pallas scan), from the
trace."""


def read(run):
    t = run.trace
    if t is None or not t.program_n("dense_topk"):
        return None
    return 1e3 * t.program_s("dense_topk") / t.program_n("dense_topk")
