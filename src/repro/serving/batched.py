"""Batched multi-request serving engine: one batch-dim decode over N slots.

``BatchedServeEngine`` generalizes :class:`repro.serving.engine.ServeEngine` from
one request to ``n_slots`` concurrent requests while keeping its exactness
contract: every slot's token stream is *identical* to what a single-request
engine would produce for the same prompt/doc schedule
(tests/test_output_preservation.py asserts this token-for-token).

Design (ROADMAP north star: fleet-level amortization):
  * one batched decode state (leading batch dim over slots). A lockstep decode
    step advances every *live* slot with a single jitted ``Model.decode_step``
    call at per-slot absolute positions — the G-cost of a speculation stride is
    paid once per fleet, not once per request.
  * per-slot prefill: slot contexts differ in length, so prefill stays per-slot
    (re-prefill on doc swap is the Ram-et-al. baseline semantics) and the
    resulting row is scattered into the batched state. Prefill shapes live on
    the same fixed grid as the single engine, so the jit cache is shared.
  * per-slot snapshot/restore: JAX arrays are immutable, so a snapshot is an
    O(1) reference to the whole batched pytree plus the slot's scalars; restore
    writes back only that slot's row. Mis-speculation rollback in one slot
    therefore cannot perturb sibling slots (regression-tested in
    tests/test_output_preservation.py). This row-granular semantics is what
    makes async fleet rounds' overlapped strides revocable: a snapshot taken
    before an overlapped step can be restored a ROUND later — after siblings
    advanced, rolled back, or (continuous batching) retired and readmitted —
    and still rewinds exactly one slot to exactly that step
    (tests/test_async_fleet.py).
  * slots leave a lockstep ``gen`` when they hit EOS or their own budget; a
    masked merge commits each slot's state as of its *own* last step, so late
    leavers keep decoding batched while early leavers stay frozen.
  * slot lifecycle (continuous batching): ``admit(slot, prompt)`` prefills a
    request into a free slot of the LIVE batch — the scatter touches only that
    slot's row, so sibling slots' caches/positions are undisturbed — and
    ``retire(slot)`` frees it again. ``gen``/``snapshot``/``restore`` operate
    only on active slots (active-slot masking); a retired slot's device row
    stays stale until the next admit prefills over it.
    ContinuousFleetServer (repro.serving.continuous) drives this API to admit
    queued requests mid-flight the moment slots free up.

The engine is cache-agnostic: the fleet servers attach Algorithm-1 state
(including each slot's speculation cache — a plain per-request cache, or a
``SharedCacheView`` over the fleet-wide ``SharedRetrievalCache`` tier when the
shared tier is enabled) per slot via ``RequestState``; nothing here reads it.
The exactness contract above is exactly why the shared tier preserves outputs:
speculation picks the docs, but this engine replays whatever verification
confirms, token-for-token.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.serving.engine import EngineStats, lm_programs


def _row_mask(mask: jax.Array, leaf: jax.Array) -> jax.Array:
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


class BatchedServeEngine:
    """N-slot greedy engine over a Model: batched decode, per-slot lifecycle."""

    def __init__(self, model: Model, params, n_slots: int, *,
                 cache_window: int = 2048, eos_id: int = -1,
                 extra: Optional[dict] = None):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.W = cache_window
        self.eos_id = eos_id
        self.extra = extra
        self.stats = EngineStats()
        self._decode_jit, self._prefill_jit = lm_programs(model, self.W,
                                                          extra)
        # scatter one prefilled row into the batched bundle / restore one row
        # from a snapshot bundle / commit rows by mask — all jitted once, with a
        # traced slot index so no per-slot recompiles
        self._scatter_jit = jax.jit(lambda cur, row, b: jax.tree.map(
            lambda c, r: c.at[b].set(r[0]), cur, row))
        self._restore_jit = jax.jit(lambda cur, old, b: jax.tree.map(
            lambda c, o: c.at[b].set(o[b]), cur, old))
        self._commit_jit = jax.jit(lambda new, com, mask: jax.tree.map(
            lambda n, c: jnp.where(_row_mask(mask, n), n, c), new, com))
        # per-slot bookkeeping (host side)
        self.tokens: List[List[int]] = [[] for _ in range(n_slots)]
        self.n_prompt = [0] * n_slots
        self.doc: List[Tuple[int, ...]] = [()] * n_slots
        self.active = [False] * n_slots
        # batched device state: (decode state, per-slot positions, last logits)
        self._state = model.init_decode_state(n_slots, self.W)
        self._pos = jnp.zeros((n_slots,), jnp.int32)
        self._last_logits = jnp.zeros((n_slots, model.cfg.vocab_size), jnp.float32)

    # ---- bundle helpers ---------------------------------------------------------------
    def _bundle(self):
        return (self._state, self._pos, self._last_logits)

    def _set_bundle(self, bundle) -> None:
        self._state, self._pos, self._last_logits = bundle

    def warm(self, lengths: Sequence[int]) -> None:
        """Precompile the prefill shape grid plus one batched decode step."""
        for L in sorted(set(int(x) for x in lengths)):
            toks = jnp.zeros((1, L), jnp.int32)
            last, state, pos = self._prefill_jit(self.params, toks)
            jax.block_until_ready(last)
        logits, _ = self._decode_jit(self.params, self._state,
                                     jnp.zeros((self.n_slots,), jnp.int32),
                                     self._pos)
        jax.block_until_ready(logits)

    # ---- slot lifecycle ---------------------------------------------------------------
    def admit(self, slot: int, prompt: Sequence[int],
              doc: Sequence[int] = ()) -> None:
        """Admit a request into a FREE slot of the live batch. The per-slot
        prefill scatters only row ``slot`` of the batched state, so sibling
        slots keep decoding from exactly where they were — this is what lets
        continuous batching admit mid-flight (even between a sibling's
        snapshot and its rollback restore; tests/test_continuous.py)."""
        assert not self.active[slot], f"admit into busy slot {slot}"
        self.active[slot] = True
        self.tokens[slot] = list(prompt)
        self.n_prompt[slot] = len(prompt)
        self.doc[slot] = tuple(doc)
        self._prefill_slot(slot)

    def retire(self, slot: int) -> None:
        """Free a finished slot. Host bookkeeping is cleared immediately; the
        slot's device row is left stale on purpose (the next admit's prefill
        overwrites it), so retirement costs nothing on device."""
        assert self.active[slot], f"retire of idle slot {slot}"
        self.active[slot] = False
        self.tokens[slot] = []
        self.n_prompt[slot] = 0
        self.doc[slot] = ()

    def free_slots(self) -> List[int]:
        return [b for b in range(self.n_slots) if not self.active[b]]

    def start(self, slot: int, prompt: Sequence[int],
              doc: Sequence[int] = ()) -> None:
        """Fixed-group entry point: (re)start a slot — retire-if-busy + admit."""
        if self.active[slot]:
            self.retire(slot)
        self.admit(slot, prompt, doc)

    def _prefill_slot(self, slot: int) -> None:
        t0 = time.perf_counter()
        seq = list(self.doc[slot]) + self.tokens[slot]
        toks = jnp.asarray(np.asarray(seq, np.int32))[None]
        last, state, pos = self._prefill_jit(self.params, toks)
        b = jnp.int32(slot)
        self._state = self._scatter_jit(self._state, state, b)
        self._pos = self._pos.at[slot].set(pos)
        self._last_logits = self._last_logits.at[slot].set(last[0])
        jax.block_until_ready(self._last_logits)
        self.stats.prefill_time += time.perf_counter() - t0
        self.stats.prefills += 1

    def set_doc(self, slot: int, doc: Sequence[int]) -> None:
        """Prepend-replace the slot's retrieved chunk (re-prefill if changed)."""
        doc = tuple(doc)
        if doc == self.doc[slot]:
            return
        self.doc[slot] = doc
        self._prefill_slot(slot)

    # ---- generation -------------------------------------------------------------------
    def gen(self, slots: Sequence[int], ks: Sequence[int]) -> List[List[int]]:
        """Lockstep greedy decode: up to ``ks[i]`` tokens for ``slots[i]`` (each
        slot stops at EOS or its own budget). One batched decode per step.
        Returns the new tokens per requested slot."""
        assert all(self.active[int(b)] for b in slots), \
            f"gen over idle slot(s): {[int(b) for b in slots if not self.active[int(b)]]}"
        t0 = time.perf_counter()
        remaining = {int(b): int(k) for b, k in zip(slots, ks)}
        out = {int(b): [] for b in slots}
        live = [b for b, k in remaining.items() if k > 0]
        committed = self._bundle()
        current = committed
        while live:
            state, pos, logits = current
            next_tok = np.asarray(jnp.argmax(logits, axis=-1))
            eos_exits, budget_exits = [], []
            tok_vec = np.zeros((self.n_slots,), np.int32)
            for b in live:
                t = int(next_tok[b])
                out[b].append(t)
                self.tokens[b].append(t)
                if t == self.eos_id:
                    eos_exits.append(b)     # EOS: no decode for this token
                    continue
                tok_vec[b] = t
                remaining[b] -= 1
                if remaining[b] <= 0:
                    budget_exits.append(b)  # budget: commit *after* this decode
            if eos_exits:
                committed = self._commit_bundle(current, committed, eos_exits)
                live = [b for b in live if b not in eos_exits]
                if not live:
                    break
            logits2, state2 = self._decode_jit(
                self.params, state, jnp.asarray(tok_vec), pos)
            live_mask = np.zeros((self.n_slots,), bool)
            live_mask[live] = True
            pos2 = pos + jnp.asarray(live_mask, jnp.int32)
            current = (state2, pos2, logits2)
            if budget_exits:
                committed = self._commit_bundle(current, committed, budget_exits)
                live = [b for b in live if b not in budget_exits]
        self._set_bundle(committed)
        jax.block_until_ready(self._last_logits)
        self.stats.decode_time += time.perf_counter() - t0
        self.stats.decodes += sum(len(v) for v in out.values())
        return [out[int(b)] for b in slots]

    def _commit_bundle(self, current, committed, slot_list):
        mask = np.zeros((self.n_slots,), bool)
        mask[slot_list] = True
        return self._commit_jit(current, committed, jnp.asarray(mask))

    def peek_logits(self, slot: int) -> np.ndarray:
        """Logits for the slot's *next* token given its current context —
        the batched form of ServeEngine.peek_logits (KNN-LM interpolation)."""
        assert self.active[slot], f"peek_logits of idle slot {slot}"
        return np.asarray(self._last_logits[slot])

    def advance(self, slots: Sequence[int], toks: Sequence[int]) -> None:
        """Append one externally-chosen token per given slot (KNN-LM: the
        interpolated argmax) and run ONE batched decode step over exactly
        those slots — the lockstep form of ServeEngine.advance, and the
        KNN-LM fleet's whole G-cost per speculation sub-step. Non-participant
        slots' rows are decoded with a dummy token and discarded by the
        masked commit, exactly as in ``gen``, so their state is untouched."""
        slots = [int(b) for b in slots]
        assert all(self.active[b] for b in slots), \
            f"advance over idle slot(s): {[b for b in slots if not self.active[b]]}"
        t0 = time.perf_counter()
        state, pos, logits = self._bundle()
        tok_vec = np.zeros((self.n_slots,), np.int32)
        for b, t in zip(slots, toks):
            t = int(t)
            self.tokens[b].append(t)
            tok_vec[b] = t
        logits2, state2 = self._decode_jit(self.params, state,
                                           jnp.asarray(tok_vec), pos)
        mask = np.zeros((self.n_slots,), bool)
        mask[slots] = True
        pos2 = pos + jnp.asarray(mask, jnp.int32)
        self._set_bundle(self._commit_bundle((state2, pos2, logits2),
                                             self._bundle(), slots))
        jax.block_until_ready(self._last_logits)
        self.stats.decode_time += time.perf_counter() - t0
        self.stats.decodes += len(slots)

    # ---- per-slot views ---------------------------------------------------------------
    def generated(self, slot: int) -> List[int]:
        return self.tokens[slot][self.n_prompt[slot]:]

    def finished(self, slot: int) -> bool:
        g = self.generated(slot)
        return bool(g) and g[-1] == self.eos_id

    # ---- speculation support ------------------------------------------------------------
    def snapshot(self, slot: int):
        """O(1): references to the immutable batched bundle + the slot's scalars.
        The bundle's row `slot` is the slot's state at snapshot time; sibling
        rows are ignored on restore — which is why a snapshot stays valid
        across round boundaries (async overlapped strides) no matter what
        siblings did in between."""
        assert self.active[slot], f"snapshot of idle slot {slot}"
        return (len(self.tokens[slot]), self.doc[slot], self._bundle())

    def restore(self, slot: int, snap) -> None:
        """Rewind ``slot`` to a snapshot it took earlier in ITS OWN request
        (any number of gen/set_doc/sibling-ops later, including overlapped
        strides from async fleet rounds). The slot's token list must be an
        extension of the snapshotted one — restoring across a retire/admit
        would silently decode from another request's state, so fail loudly."""
        assert self.active[slot], f"restore of idle slot {slot}"
        n, doc, bundle = snap
        assert n <= len(self.tokens[slot]), \
            f"slot {slot}: snapshot is not from this request's lineage"
        self.tokens[slot] = self.tokens[slot][:n]
        self.doc[slot] = doc
        b = jnp.int32(slot)
        self._set_bundle(self._restore_jit(self._bundle(), bundle, b))
