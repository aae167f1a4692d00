"""Serving engine: prefill + greedy decode with snapshot/rollback.

RaLMSpec needs three properties from the LM side (paper §3 + our DESIGN §5):
  * deterministic generation (greedy) — the output-preservation proof needs it,
  * cheap state snapshots at speculation-step boundaries — JAX arrays are immutable,
    so a snapshot is just (context length, position, state pytree *reference*): O(1),
  * doc-conditioned generation à la Ram et al. 2023: the latest retrieved chunk is
    prepended to the prompt, *replacing* the previous one, which invalidates the KV
    cache ⇒ re-prefill. This is the baseline's dominant G-cost, exactly as the paper
    describes it.

Shape discipline for jit reuse: documents are padded/truncated to a fixed chunk
length and generation advances in fixed strides, so prefill shapes recur across
requests and the jit cache stays small.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model


def lm_programs(model: Model, cache_window: int, extra: Optional[dict]):
    """The jitted (decode, prefill) pair every engine serves with.

    The decode step's matmuls run at full f32 precision on every platform.
    On a TPU the default reads f32 operands as one bf16 pass; the
    sequential baseline decodes at batch 1 and the fleet at batch N, two
    different programs, and at that precision they can round a logit
    differently and pick another greedy token — breaking output
    preservation. Prefill always runs at batch 1, one program for both."""
    def decode(p, st, tok, pos):
        with jax.default_matmul_precision("highest"):
            return model.decode_step(p, st, tok, pos)

    def prefill(p, toks):
        return model.prefill(p, toks, extra=extra, window_cache=cache_window)

    return jax.jit(decode), jax.jit(prefill)


@dataclass
class EngineStats:
    prefill_time: float = 0.0
    decode_time: float = 0.0
    prefills: int = 0
    decodes: int = 0

    @property
    def gen_time(self) -> float:        # the paper's G component
        return self.prefill_time + self.decode_time

    def reset(self):
        self.prefill_time = self.decode_time = 0.0
        self.prefills = self.decodes = 0


class ServeEngine:
    """Single-request greedy engine over a Model."""

    def __init__(self, model: Model, params, *, cache_window: int = 2048,
                 eos_id: int = -1, extra: Optional[dict] = None):
        self.model = model
        self.params = params
        self.W = cache_window
        self.eos_id = eos_id
        self.extra = extra
        self.stats = EngineStats()
        self._decode_jit, self._prefill_jit = lm_programs(model, self.W,
                                                          extra)
        # mutable per-request state
        self.doc: Tuple[int, ...] = ()
        self.tokens: List[int] = []        # prompt + generated (doc NOT included)
        self.n_prompt = 0
        self._state = None
        self._pos = None

    def warm(self, lengths: Sequence[int]) -> None:
        """Precompile prefill for every context length in the serving grid (and one
        decode step) so wall-clock benchmarks measure compute, not XLA compiles.
        Both RaLMSeq and RaLMSpec use the same closed set of shapes (fixed doc chunk
        + prompt + i * generation_stride), so warming is system-neutral."""
        for L in sorted(set(int(x) for x in lengths)):
            toks = jnp.zeros((1, L), jnp.int32)
            last, state, pos = self._prefill_jit(self.params, toks)
            jax.block_until_ready(last)
        logits, _ = self._decode_jit(self.params, state,
                                     jnp.zeros((1,), jnp.int32), pos)
        jax.block_until_ready(logits)

    # ---- request lifecycle -----------------------------------------------------------
    def start(self, prompt: Sequence[int], doc: Sequence[int] = ()) -> None:
        self.tokens = list(prompt)
        self.n_prompt = len(prompt)
        self.doc = tuple(doc)
        self._prefill()

    def _prefill(self) -> None:
        t0 = time.perf_counter()
        seq = list(self.doc) + self.tokens
        toks = jnp.asarray(np.asarray(seq, np.int32))[None]
        last, state, pos = self._prefill_jit(self.params, toks)
        self._last_logits = last
        self._state = state
        self._pos = pos
        jax.block_until_ready(last)
        self.stats.prefill_time += time.perf_counter() - t0
        self.stats.prefills += 1

    def set_doc(self, doc: Sequence[int]) -> None:
        """Prepend-replace the retrieved chunk (re-prefill if it changed)."""
        doc = tuple(doc)
        if doc == self.doc:
            return
        self.doc = doc
        self._prefill()

    # ---- generation -------------------------------------------------------------------
    def gen(self, k: int) -> List[int]:
        """Greedy-decode up to k tokens (stops at EOS). Returns the new tokens."""
        t0 = time.perf_counter()
        out = []
        logits = self._last_logits
        for _ in range(k):
            tok = int(jnp.argmax(logits[0]))
            out.append(tok)
            self.tokens.append(tok)
            if tok == self.eos_id:
                break
            logits, self._state = self._decode_jit(
                self.params, self._state, jnp.asarray([tok], jnp.int32), self._pos)
            self._pos = self._pos + 1
            self._last_logits = logits
        jax.block_until_ready(self._last_logits)
        self.stats.decode_time += time.perf_counter() - t0
        self.stats.decodes += len(out)
        return out

    def peek_logits(self) -> np.ndarray:
        """Logits for the *next* token given the current context (KNN-LM interp)."""
        return np.asarray(self._last_logits[0])

    def advance(self, tok: int) -> None:
        """Append an externally-chosen token (KNN-LM: interpolated argmax)."""
        t0 = time.perf_counter()
        self.tokens.append(int(tok))
        logits, self._state = self._decode_jit(
            self.params, self._state, jnp.asarray([int(tok)], jnp.int32), self._pos)
        self._pos = self._pos + 1
        self._last_logits = logits
        jax.block_until_ready(logits)
        self.stats.decode_time += time.perf_counter() - t0
        self.stats.decodes += 1

    @property
    def generated(self) -> List[int]:
        return self.tokens[self.n_prompt:]

    @property
    def finished(self) -> bool:
        return bool(self.generated) and self.generated[-1] == self.eos_id

    # ---- speculation support ------------------------------------------------------------
    def snapshot(self):
        """O(1): JAX arrays are immutable, so references suffice (DESIGN §5 — this is
        what makes rollback exact for recurrent/SSM archs, not just KV models)."""
        return (len(self.tokens), self.doc, self._state, self._pos, self._last_logits)

    def restore(self, snap) -> None:
        n, doc, state, pos, last = snap
        self.tokens = self.tokens[:n]
        self.doc = doc
        self._state = state
        self._pos = pos
        self._last_logits = last
