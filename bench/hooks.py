"""What the harness watches during the window, from its own wrappers.

Instance attributes shadow the engine's and retriever's methods for the
window's ``serve()`` call only:

  * ``engine.admit`` / ``engine.retire``: wall-clock admission and
    retirement of each request (its latency);
  * ``retriever.retrieve``: the merged KB calls, a seeded reservoir sample
    of whose inputs and answers the output check re-computes;
  * with a trace, ``jax.profiler.TraceAnnotation`` host spans around the
    engine's decode (``gen``/``advance``), its prefills (``admit``,
    ``set_doc``), the KB call and the KNN-LM interpolation, so the trace
    can say what the host was doing in each device idle gap. The trace
    itself starts and stops from these wrappers, at fixed times into the
    window."""
from __future__ import annotations

import functools
import time
from typing import List, Optional

import jax
import numpy as np

SPANS = {"gen": "engine.decode", "advance": "engine.decode",
         "admit": "engine.prefill", "set_doc": "engine.prefill"}


class Tracer:
    """Profiler session over [start_after, start_after + duration) seconds
    of the window, opened and closed from the next wrapped call."""

    def __init__(self, log_dir: str, start_after: float, duration: float):
        self.log_dir, self.start_after, self.duration = \
            log_dir, start_after, duration
        self.t0 = None
        self.started = self.stopped = None
        self.kb_batches: List[int] = []

    @property
    def active(self) -> bool:
        return self.started is not None and self.stopped is None

    def tick(self) -> None:
        now = time.perf_counter()
        if self.started is None and now - self.t0 >= self.start_after:
            jax.profiler.start_trace(self.log_dir)
            self.started = time.perf_counter()
        elif self.active and now - self.started >= self.duration:
            self.close()

    def close(self) -> None:
        if self.active:
            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return (self.stopped - self.started) if self.stopped else 0.0


class Watch:
    def __init__(self, server, seed_rng: np.random.Generator,
                 kb_sample: int = 48, tracer: Optional[Tracer] = None):
        self.server, self.rng, self.kb_sample = server, seed_rng, kb_sample
        self.tracer = tracer
        self.latencies: List[float] = []
        self.kb_records: list = []
        self.kb_seen = 0
        self._admitted = {}
        self._saved = []

    # ---- install / remove -------------------------------------------------
    def _wrap(self, obj, name, fn):
        self._saved.append((obj, name, obj.__dict__.get(name)))
        setattr(obj, name, fn)

    def __enter__(self):
        eng, r = self.server.engine, self.server.retriever
        admit, retire, retrieve = eng.admit, eng.retire, r.retrieve

        def on_admit(slot, *a, **kw):
            self._admitted[slot] = time.perf_counter()
            return admit(slot, *a, **kw)

        def on_retire(slot):
            t_admit = self._admitted.pop(slot, None)
            if t_admit is not None:
                self.latencies.append(time.perf_counter() - t_admit)
            return retire(slot)

        def on_retrieve(queries, k):
            ids, scores = retrieve(queries, k)
            self._record_kb(queries, ids, scores, k)
            return ids, scores

        self._wrap(eng, "admit", on_admit)
        self._wrap(eng, "retire", on_retire)
        self._wrap(r, "retrieve", on_retrieve)
        if self.tracer is not None:
            self._install_spans()
            self.tracer.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.tracer is not None:
            self.tracer.close()
        for obj, name, old in reversed(self._saved):
            if old is None:
                delattr(obj, name)
            else:
                setattr(obj, name, old)
        self._saved = []

    def _install_spans(self):
        tr = self.tracer
        eng, r = self.server.engine, self.server.retriever

        def spanned(fn, label):
            @functools.wraps(fn)
            def inner(*a, **kw):
                tr.tick()
                with jax.profiler.TraceAnnotation(label):
                    return fn(*a, **kw)
            return inner

        for meth, label in SPANS.items():
            if hasattr(eng, meth):
                self._wrap(eng, meth, spanned(getattr(eng, meth), label))
        inner_retrieve = r.retrieve

        def kb(queries, k):
            tr.tick()
            B = len(queries)
            active = tr.active
            with jax.profiler.TraceAnnotation("retriever.kb_call"):
                out = inner_retrieve(queries, k)
            if active and tr.active:
                tr.kb_batches.append(B)
            return out
        self._wrap(r, "retrieve", kb)
        if self.server.workload.name == "knnlm":
            import repro.serving.workload as wl
            self._wrap(wl, "knn_interpolate",
                       spanned(wl.knn_interpolate, "knnlm.interpolate"))

    # ---- KB reservoir -----------------------------------------------------
    def _record_kb(self, queries, ids, scores, k):
        """Reservoir sample (size kb_sample, seeded) of merged KB calls."""
        self.kb_seen += 1
        rec = None
        if len(self.kb_records) < self.kb_sample:
            rec, slot = True, len(self.kb_records)
            self.kb_records.append(None)
        else:
            j = int(self.rng.integers(0, self.kb_seen))
            if j < self.kb_sample:
                rec, slot = True, j
        if rec:
            self.kb_records[slot] = (np.array(queries, np.float32, copy=True),
                                     np.array(ids), np.array(scores), k)
