"""Device time in the engine's ``prefill`` programs over the traced
window."""


def read(run):
    t = run.trace
    if t is None or not t.window_s or not t.program_n("prefill"):
        return None
    return 100.0 * t.program_s("prefill") / t.window_s
