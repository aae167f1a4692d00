"""Host milliseconds of KNN-LM interpolation per output token: the total of
the program's ``knn.interpolate`` spans over the window's ``serve()`` call
(the body of ``knn_interpolate``, at speculation and at verification).

The call-site spans ``spec.interpolate`` / ``verify.interpolate`` are not
read: in a traced run the harness wraps ``knn_interpolate``, and that
wrapper starts and stops the profiler, which then lands in whichever
call-site span is open. None where the span never ran."""


def read(run):
    spans = getattr(run.result, "spans", None) or {}
    if "knn.interpolate" not in spans or not run.tokens:
        return None
    return 1e3 * spans["knn.interpolate"][1] / run.tokens
