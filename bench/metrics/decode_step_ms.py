"""Device time of one execution of the engine's ``decode`` program, from
the trace."""


def read(run):
    t = run.trace
    if t is None or not t.program_n("decode"):
        return None
    return 1e3 * t.program_s("decode") / t.program_n("decode")
