"""The span ledger (``repro.obs``) and what the serving path records in it:
self-time arithmetic, threads, interval deltas, the names a KNN-LM serve
leaves, the interpolation counters, the profiler's copy of the spans, and
the wall-clock request timeline on ``ServeResult``."""
import dataclasses
import gc
import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import RaLMConfig, get_config, reduced
from repro.models.model import build_model
from repro.retrieval.encoder import ContextEncoder
from repro.retrieval.kb import build_knn_datastore
from repro.retrieval.retrievers import ExactDenseRetriever
from repro.serving.batched import BatchedServeEngine
from repro.serving.continuous import ContinuousFleetServer, as_requests
from repro.serving.fleet import FleetServer
from repro.serving.workload import KNNLMWorkload
from repro.training.data import synthetic_corpus

SERVE_SPANS = {"serve.admit", "round", "round.speculate", "spec.query",
               "engine.logits_fetch", "spec.interpolate", "engine.decode",
               "engine.prefill", "round.verify", "kb.call", "round.commit",
               "verify.interpolate", "knn.interpolate", "commit.cache_insert",
               "commit.rollback", "serve.retire", "device.wait"}


@pytest.fixture
def fake_clock(monkeypatch):
    """The span clock reads the next value of a list; collections are off
    so that no ``host.gc`` span reads it in between."""
    ticks = []
    monkeypatch.setattr(obs, "_clock", lambda: ticks.pop(0))
    was = gc.isenabled()
    gc.disable()
    try:
        yield ticks
    finally:
        if was:
            gc.enable()


def test_nested_self_time(fake_clock):
    fake_clock.extend([0, 10, 40, 50, 60, 100])
    snap = obs.snapshot()
    with obs.span("t.outer") as outer:
        with obs.span("t.inner"):
            pass
        with obs.span("t.inner"):
            pass
    spans, _ = obs.since(snap)
    assert spans["t.outer"] == (1, pytest.approx(100e-9), pytest.approx(60e-9))
    assert spans["t.inner"] == (2, pytest.approx(40e-9), pytest.approx(40e-9))
    assert outer.seconds == pytest.approx(100e-9)
    assert (outer.start, outer.end) == (0.0, pytest.approx(100e-9))


def test_since_reports_only_the_interval():
    with obs.span("t.before"):
        pass
    obs.count("t.counter", 5)
    snap = obs.snapshot()
    with obs.span("t.after"):
        pass
    obs.count("t.counter", 2)
    obs.count("t.fresh")
    spans, counters = obs.since(snap)
    assert "t.before" not in spans
    assert spans["t.after"][0] == 1
    assert counters["t.counter"] == 2 and counters["t.fresh"] == 1
    assert obs.since(obs.snapshot()) == ({}, {})


def test_worker_thread_spans_and_counts():
    """Spans on other threads land in the ledger but are nobody's children
    on the main thread; concurrent counts and spans lose no update."""
    n_threads, n_iter = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    snap = obs.snapshot()
    started = threading.Barrier(n_threads + 1)

    def work():
        started.wait(timeout=30)
        for _ in range(n_iter):
            with obs.span("t.worker"):
                with obs.span("t.worker.child"):
                    obs.count("t.hits")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    try:
        with obs.span("t.main") as main:
            for t in threads:
                t.start()
            started.wait(timeout=30)
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    spans, counters = obs.since(snap)
    n = n_threads * n_iter
    assert counters["t.hits"] == n
    assert spans["t.worker"][0] == n and spans["t.worker.child"][0] == n
    count, total, own = spans["t.main"]
    gc_inside = spans.get("host.gc", (0, 0.0, 0.0))[1]
    assert count == 1 and total == pytest.approx(main.seconds)
    assert own >= total - gc_inside - 1e-9     # worker time is not its child
    assert spans["t.worker"][2] <= spans["t.worker"][1]


def test_spans_reach_the_profiler(tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("t.traced.outer"):
            with obs.span("t.traced.inner"):
                jnp.ones(8).block_until_ready()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    names = {ev.name for plane in pd.planes if not plane.name.startswith(
        "/device:") for line in plane.lines for ev in line.events}
    assert {"t.traced.outer", "t.traced.inner"} <= names


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lm_programs_name_their_phases(program):
    """The jitted ``decode`` and ``prefill`` keep their names and carry
    the ``attention``, ``mlp`` and ``unembed`` scopes a trace shows."""
    from repro.serving.engine import lm_programs
    model = build_model(reduced(get_config("knnlm-247m")))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    decode, prefill = lm_programs(model, 64, None)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    if program == "decode":
        state = jax.eval_shape(lambda: model.init_decode_state(2, 64))
        lowered = decode.lower(params, state, i32(2), i32(2))
    else:
        lowered = prefill.lower(params, i32(1, 16))
    text = lowered.as_text(debug_info=True)
    for scope in ("attention", "mlp", "unembed"):
        assert f"jit({program})/{scope}/" in text, scope


# ---- the serving path ------------------------------------------------------
@pytest.fixture(scope="module")
def knn():
    cfg = reduced(get_config("ralm-gpt2-medium"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    docs = synthetic_corpus(400, cfg.vocab_size)
    stream = np.concatenate([np.asarray(d, np.int32) for d in docs[:200]])
    enc = ContextEncoder(cfg.vocab_size, d=32, window=16)
    ds = build_knn_datastore(stream, enc, context=16, limit=4000)
    prompts = [stream[i * 97:i * 97 + 40].tolist() for i in range(3)]
    eng = BatchedServeEngine(model, params, 2, cache_window=128)
    # a small speculation cache mis-speculates, so rollbacks happen too
    rcfg = RaLMConfig(knnlm=True, knn_k=8, max_new_tokens=12,
                      speculation_stride=3, cache_capacity=16)
    return eng, ExactDenseRetriever(ds), enc, rcfg, prompts


class _CountingKNNLM(KNNLMWorkload):
    """Counts the steps ``check_and_commit`` evaluates: up to the first
    mismatch, or the whole stride."""

    evaluated = 0

    def check_and_commit(self, srv, st, gt_ids, gt_scores):
        m, corr = super().check_and_commit(srv, st, gt_ids, gt_scores)
        self.evaluated += m + 1 if m < len(st.specs) else m
        return m, corr


def _latency_watch(eng, latencies):
    """Admission-to-retirement wall time per request, measured from outside
    the program as a benchmark does: wrappers around ``engine.admit`` /
    ``engine.retire`` on the instance."""
    admit, retire, t_in = eng.admit, eng.retire, {}

    def on_admit(slot, *a, **kw):
        t_in[slot] = time.perf_counter()
        return admit(slot, *a, **kw)

    def on_retire(slot):
        latencies.append(time.perf_counter() - t_in.pop(slot))
        return retire(slot)

    eng.admit, eng.retire = on_admit, on_retire


def test_knnlm_serve_records_every_span(knn):
    eng, retr, enc, rcfg, prompts = knn
    wl = _CountingKNNLM()
    latencies = []
    _latency_watch(eng, latencies)
    try:
        with ContinuousFleetServer(eng, retr, rcfg, enc, workload=wl) as srv:
            res = srv.serve(as_requests(prompts))
    finally:
        del eng.admit, eng.retire
    assert SERVE_SPANS <= set(res.spans)
    for name, (count, total, own) in res.spans.items():
        assert count > 0 and 0 <= own <= total + 1e-9, name
    assert sum(r.mismatches for r in res.results) > 0
    steps = sum(r.spec_steps for r in res.results)
    assert res.counters["knn_interp.spec"] == steps
    assert res.counters["knn_interp.verify"] == wl.evaluated
    assert res.spans["spec.interpolate"][0] == steps
    assert res.spans["verify.interpolate"][0] == wl.evaluated
    assert res.spans["knn.interpolate"][0] == steps + wl.evaluated
    # the engine's timers are readings of the same spans
    assert eng.stats.decode_time == pytest.approx(
        res.spans["engine.decode"][1], rel=1e-6)
    assert eng.stats.prefill_time == pytest.approx(
        res.spans["engine.prefill"][1], rel=1e-6)
    # wall-clock timeline against admission-to-retirement seen from outside
    done = sorted(res.results, key=lambda r: r.t_retire)
    assert len(done) == len(latencies)
    for r, lat in zip(done, latencies):
        assert abs((r.t_retire - r.t_admit) - lat) < 5e-3
        assert r.t_admit < r.t_first_token <= r.commit_times[-1] <= r.t_retire
        assert len(r.commit_times) == r.rounds
        assert r.commit_times == sorted(r.commit_times)


def test_fixed_fleet_carries_its_own_interval(knn):
    eng, retr, enc, rcfg, prompts = knn
    rcfg = dataclasses.replace(rcfg, cache_capacity=4096)
    with FleetServer(eng, retr, rcfg, enc) as srv:
        first = srv.serve(prompts[:2])
        second = srv.serve(prompts[:2])
    assert first.spans["round"][0] == first.rounds
    assert second.spans["round"][0] == second.rounds
    assert second.counters["knn_interp.spec"] == sum(
        r.spec_steps for r in second.results)
    assert second.verify_wall_s == pytest.approx(
        second.spans["verify.call"][1], rel=1e-6)
    for r in second.results:
        assert r.t_admit < r.t_first_token <= r.t_retire


def test_collection_inside_the_ledger_lock_does_not_deadlock():
    """A collection that starts while this thread holds the ledger's lock
    (a snapshot, a span's update) records its ``host.gc`` span without
    waiting on that lock."""
    def work():
        junk = []
        for i in range(3000):
            with obs.span("t.stress"):
                junk.append([i])
            obs.snapshot()
            if len(junk) > 100:
                junk.clear()

    old = gc.get_threshold()
    gc.set_threshold(1)
    try:
        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(timeout=120)
    finally:
        gc.set_threshold(*old)
    assert not t.is_alive(), "deadlocked on the ledger's lock"
