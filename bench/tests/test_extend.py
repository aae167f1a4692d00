"""A configuration, a traffic mix and a per-layer metric are added by files
and BENCHMARK.json entries alone: ``extra/`` holds one of each, and the
harness runs them without any change to the files it already has."""
from bench.tests.helpers import HERE, run_tiny


def test_added_config_mix_and_metric_run():
    bm = HERE / "extra" / "benchmark.json"
    dirs = (HERE / "extra", HERE)
    out = run_tiny("tiny-ralm-deep.qa3", trace=True, bench_path=bm,
                   data_dirs=dirs)
    assert out["correct"] is True
    assert out["metrics"]["rounds_per_request"]["value"] > 0
    other = run_tiny("tiny-ralm.qa", trace=True, bench_path=bm, data_dirs=dirs)
    assert "rounds_per_request" not in other["metrics"]
