"""KNN-LM interpolations the window ran per output token: speculation's
(``knn_interp.spec``) plus verification's recomputations
(``knn_interp.verify``), the program's counters over the window's
``serve()`` call. None where the program keeps no such counters."""


def read(run):
    counters = getattr(run.result, "counters", None) or {}
    n = counters.get("knn_interp.spec", 0) + counters.get("knn_interp.verify", 0)
    return n / run.tokens if n and run.tokens else None
