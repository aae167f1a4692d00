"""Output tokens of every request retired in the window over the window's
wall seconds (host clock)."""


def read(run):
    return run.tokens / run.window_s
