"""Speculation rounds per request in the window (a metric added by a file)."""


def read(run):
    res = run.result.results
    return sum(r.rounds for r in res) / len(res) if res else None
