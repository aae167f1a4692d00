"""The per-layer metrics that read the program's own span ledger
(``ContinuousResult.spans`` / ``counters``): present on the KNN-LM cell,
absent where their spans never run, and silent on a program that keeps no
ledger. ``spans/benchmark.json`` reports them in every tiny cell."""
from types import SimpleNamespace

from bench import spec
from bench.tests.helpers import HERE, run_tiny

KNN_ONLY = ("interp_calls_per_token", "interp_ms_per_token", "logits_fetch_ms")
ALL = KNN_ONLY + ("host_path_share",)
BM = HERE / "spans" / "benchmark.json"
DIRS = (HERE / "spans", HERE)


def test_knnlm_cell_reports_the_span_metrics():
    m = run_tiny("tiny-knnlm.lm", trace=True, bench_path=BM,
                 data_dirs=DIRS)["metrics"]
    assert 1 <= m["interp_calls_per_token"]["value"] <= 3
    assert 0 < m["host_path_share"]["value"] <= 100
    assert m["interp_ms_per_token"]["value"] > 0
    assert m["logits_fetch_ms"]["value"] > 0


def test_ralm_cell_has_no_interpolation():
    m = run_tiny("tiny-ralm.qa", trace=True, bench_path=BM,
                 data_dirs=DIRS)["metrics"]
    assert not set(KNN_ONLY) & set(m)
    assert 0 < m["host_path_share"]["value"] <= 100


def test_silent_without_the_ledger():
    """A program whose results carry no spans or counters reads None."""
    run = SimpleNamespace(result=SimpleNamespace(results=[]), tokens=100,
                          window_s=1.0, cfg={"serving": {"variant": "ps"}})
    for name in ALL:
        assert spec.load_metric(name).read(run) is None
