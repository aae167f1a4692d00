"""Spans and counters: where the serving loop's host time goes.

``span(name)`` times a block of host code on two records at once. It opens a
``jax.profiler.TraceAnnotation``, which puts the block on the profiler's host
plane, on the clock of the device trace (and does nothing when no profile
runs). It also adds the block to an in-process ledger under ``name``: calls,
total seconds and self seconds, self being the total less the spans opened
inside it on the same thread. ``count(name, n)`` adds to a counter.
``snapshot()`` and ``since(snap)`` give the ledger's change over an
interval: ``serve()`` results carry the spans and counters of that call.
The ledger is process-wide and locked, so spans opened on the verification
worker thread land in it too.

The module imports JAX only when the first span opens, so modules that must
stay free of JAX at import time (``retrieval/backends.py``) can import it.

Every Python garbage collection after the first span is recorded as a
``host.gc`` span and counted under ``gc.collections``.

Names used by the serving path:

  serve.admit           admitting queued requests: prefills, pre-seed inserts,
                        the batched seed call
  serve.retire          retiring finished requests
  round                 one speculation round (``FleetServer._run_round``)
  round.speculate       its lockstep speculation sub-steps (and, with async
                        rounds, the stride overlapped with the KB call)
  spec.query            per slot and sub-step: snapshot, query encoding,
                        speculation-cache lookup
  spec.interpolate      KNN-LM interpolation at speculation
  round.verify          query build, dedup and the merged KB call, to its join
  verify.call           the merged KB call behind its retry shell (on the
                        worker thread with async rounds)
  kb.call               the retriever's scan and the fetch of its results
  round.commit          per-slot check, cache update, rollback, correction
  verify.interpolate    KNN-LM interpolation recomputed at verification
  knn.interpolate       the interpolation's body, inside either of the two
                        above; the call-site spans also hold whatever wraps
                        the function, this one does not
  commit.cache_insert   speculation-cache inserts
  commit.rollback       restore, correction payload, correction stride
  engine.prefill        one slot's prefill
  engine.decode         one decode call (``gen`` / ``advance``), dispatch to
                        result
  engine.logits_fetch   one slot's logits row copied to the host
  device.wait           the host blocked on device results
  host.gc               a Python garbage collection

Counters: ``knn_interp.spec`` and ``knn_interp.verify`` (interpolations
done), ``gc.collections``.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, Tuple

# Re-entrant: a collection can start inside a locked block, and its
# ``host.gc`` span then updates the ledger from the same thread. Its entries
# exist from the start, so such an update never resizes a dict being copied.
_lock = threading.RLock()
_spans: Dict[str, list] = {"host.gc": [0, 0, 0]}   # name -> [n, total, self] ns
_counters: Dict[str, int] = {"gc.collections": 0}
_local = threading.local()
_annotation = None                    # jax.profiler.TraceAnnotation, bound lazily
_clock = time.perf_counter_ns


class span:
    """Context manager; after the block, ``seconds`` is its duration and
    ``start`` / ``end`` its bounds on ``time.perf_counter``'s clock."""

    __slots__ = ("name", "seconds", "start", "end", "_ann", "_t0", "_child")

    def __init__(self, name: str):
        self.name = name
        self.seconds = self.start = self.end = 0.0

    def __enter__(self) -> "span":
        global _annotation
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._child = 0
        stack.append(self)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _clock()
        stack = _local.stack
        stack.pop()
        self._ann.__exit__(*exc)
        dur = t1 - self._t0
        if stack:
            stack[-1]._child += dur
        e = _spans.get(self.name)
        if e is None:
            e = [0, 0, 0]
        with _lock:
            e = _spans.setdefault(self.name, e)
            e[0] += 1
            e[1] += dur
            e[2] += dur - self._child
        self.seconds = dur * 1e-9
        self.start, self.end = self._t0 * 1e-9, t1 * 1e-9


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def snapshot() -> Tuple[dict, dict]:
    with _lock:
        return {k: tuple(v) for k, v in _spans.items()}, dict(_counters)


def since(snap: Tuple[dict, dict]) -> Tuple[Dict[str, tuple], Dict[str, int]]:
    """-> (spans: name -> (count, total_s, self_s), counters: name -> n),
    each the change since ``snap``; names that did not move are left out."""
    spans0, counters0 = snap
    now_spans, now_counters = snapshot()
    spans = {}
    for name, (n, tot, own) in now_spans.items():
        n0, tot0, own0 = spans0.get(name, (0, 0, 0))
        if n > n0:
            spans[name] = (n - n0, (tot - tot0) * 1e-9, (own - own0) * 1e-9)
    counters = {k: v - counters0.get(k, 0) for k, v in now_counters.items()
                if v != counters0.get(k, 0)}
    return spans, counters


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _annotation is not None:       # not while JAX is being imported
            _local.gc = span("host.gc").__enter__()
    else:
        s = getattr(_local, "gc", None)
        if s is not None:
            _local.gc = None
            s.__exit__(None, None, None)
            count("gc.collections")


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
