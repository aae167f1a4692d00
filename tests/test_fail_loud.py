"""Failures on the serving path surface; nothing quietly routes around them.

  * a KB exception outside the fault taxonomy (`repro.retrieval.faults`) —
    a kernel that fails to compile, a device out of memory — propagates out
    of ``serve()`` on the first attempt, in sync and async fleets alike,
    instead of being retried and degraded to speculation-only rounds,
  * the sharded backend refuses more KB shards than there are devices,
  * the serve CLI exits non-zero when speculation changed an output, and
    when a request degraded although no fault was injected,
  * ``chip_smoke.py`` exits non-zero, with no result line, off the TPU and
    outside the repository.
"""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import RaLMConfig, get_config, reduced
from repro.models.model import build_model
from repro.retrieval.backends import FlatBackend, ShardedBackend
from repro.retrieval.encoder import ContextEncoder
from repro.retrieval.faults import TransientRetrievalError
from repro.retrieval.kb import DenseKB
from repro.retrieval.retrievers import ExactDenseRetriever
from repro.serving.batched import BatchedServeEngine
from repro.serving.fleet import FleetServer
from repro.training.data import make_queries, synthetic_corpus

ROOT = os.path.join(os.path.dirname(__file__), "..")

RCFG = RaLMConfig(max_new_tokens=12, speculation_stride=3, retry_max=2,
                  async_gate_ratio=0.0, async_min_overlap=0)


@pytest.fixture(scope="module")
def fleet_stack():
    cfg = reduced(get_config("ralm-gpt2-medium"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    docs = synthetic_corpus(600, cfg.vocab_size)
    enc = ContextEncoder(cfg.vocab_size, d=32)
    kb = DenseKB.build(docs, enc)
    prompts = [(q * 10)[:32] for q in make_queries(docs, 2)]
    beng = BatchedServeEngine(model, params, 2, cache_window=256)
    return kb, enc, prompts, beng


class _DeviceFault(RuntimeError):
    """Stands in for what a device raises: a compile error, an OOM."""


@pytest.mark.parametrize("async_rounds", [False, True])
@pytest.mark.parametrize("fail_at", [1, 2], ids=["seed", "verify"])
def test_non_taxonomy_kb_error_propagates(fleet_stack, async_rounds,
                                          fail_at):
    """The first KB call seeds the slots; the second is a verification
    round (on the worker thread when async). Either way the error leaves
    serve() after ONE attempt: no retry, no degraded round."""
    kb, enc, prompts, beng = fleet_stack
    retr = ExactDenseRetriever(kb)
    calls = [0]
    search = retr.backend.search

    def failing_search(queries, k):
        calls[0] += 1
        if calls[0] >= fail_at:
            raise _DeviceFault("Mosaic failed to compile the KB kernel")
        return search(queries, k)

    retr.backend.search = failing_search
    with FleetServer(beng, retr, RCFG, enc,
                     async_rounds=async_rounds) as fleet:
        with pytest.raises(_DeviceFault, match="Mosaic"):
            fleet.serve(prompts)
    assert calls[0] == fail_at, "a non-taxonomy error must not be retried"
    assert retr.stats.errors == 0 and retr.stats.failed_calls == 0


def test_sharded_backend_refuses_more_shards_than_devices():
    emb = np.random.default_rng(0).standard_normal((64, 8)).astype(np.float32)
    n = len(jax.devices())
    assert ShardedBackend(emb, n_shards=n).n_shards == n
    with pytest.raises(ValueError, match=f"{n + 1} KB shards"):
        ShardedBackend(emb, n_shards=n + 1)


def _serve_cli(monkeypatch, argv):
    from repro.launch import serve as serve_mod
    # the test process keeps JAX's default (in-memory) compile caching
    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve", "--n-docs", "400",
                                      "--requests", "2", "--max-new", "6"]
                        + argv)
    with pytest.raises(SystemExit) as ei:
        serve_mod.main()
    return ei.value.code


def test_serve_cli_fails_on_output_mismatch(monkeypatch, capsys):
    from repro.core.ralmspec import RaLMSeq
    from repro.launch import serve as serve_mod

    class WrongSeq(RaLMSeq):
        def serve(self, prompt):
            res = super().serve(prompt)
            res.tokens = res.tokens[:-1] + [res.tokens[-1] + 1]
            return res

    monkeypatch.setattr(serve_mod, "RaLMSeq", WrongSeq)
    code = _serve_cli(monkeypatch, ["--mode", "both"])
    assert code not in (0, None)
    assert "outputs identical: False" in str(code)
    assert "outputs identical: False" in capsys.readouterr().out


def test_serve_cli_fails_on_degradation_without_injected_faults(monkeypatch,
                                                                capsys):
    """A KB that keeps failing with a taxonomy error degrades its rounds —
    the documented answer to injected faults, a failure otherwise."""
    def outage(self, queries, k):
        raise TransientRetrievalError("KB replica unreachable")

    monkeypatch.setattr(FlatBackend, "search", outage)
    code = _serve_cli(monkeypatch, ["--mode", "spec", "--concurrency", "2",
                                    "--retry-max", "0"])
    assert code not in (0, None)
    assert "degraded with no injected faults" in str(code)
    assert "degraded rounds" in capsys.readouterr().out


def _run_smoke(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run_smoke(ROOT, env)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = _run_smoke(tmp_path, env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
