"""The whole run, end to end, on the CPU at a tiny size."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.helpers import HERE, run_tiny

ROOT = HERE.parents[1]


@pytest.mark.parametrize("cell", ["tiny-ralm.qa", "tiny-knnlm.lm"])
def test_tiny_cell_runs_and_is_correct(cell):
    out = run_tiny(cell, seed=2 ** 31 + 12345)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 4
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert set(m) == {"tokens_per_s", "req_latency_p50_s",
                      "req_latency_p90_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert m["req_latency_p50_s"]["value"] <= m["req_latency_p90_s"]["value"]
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_per_layer_counters_in_traced_cpu_run():
    out = run_tiny("tiny-ralm.qa", trace=True)
    m = out["metrics"]
    assert 0 < m["kb_calls_per_token"]["value"] < 1
    assert 0 <= m["spec_accept_share"]["value"] <= 100
    assert "busy_s" in out["device"] and "breakdown" in out


def test_same_seed_same_inputs():
    from bench import traffic
    mix = json.loads((HERE / "traffic" / "tiny-qa.json").read_text())

    class C:
        import numpy as np
        passages = np.arange(3000 * 32).reshape(3000, 32) % 500
    n = 2 * len(traffic.output_pool(mix))          # two whole cycles
    a = traffic.make_requests(mix, C, 2 ** 31 + 99, n)
    b = traffic.make_requests(mix, C, 2 ** 31 + 99, n)
    c = traffic.make_requests(mix, C, 100, n)
    assert a == b and a != c
    # another seed: the same sizes, in another order
    assert sorted(m for _, m in a) == sorted(m for _, m in c)
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in c)


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "ralm-edr-qa-c8", "--seed", "1", "--seconds", "1",
                           "--trace", "0"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cli_refuses_cpu_without_result_line():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_cli_fails_without_the_program(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
