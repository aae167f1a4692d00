"""Jit'd public wrappers for the Pallas kernels.

On TPU the calls compile to Mosaic. On CPU, where the tests run, the kernels
execute in interpret mode — the kernel body is semantically validated. Any
other platform is an error, never a silent interpret. ``force_ref=True``
routes to the pure-jnp oracle (used by retrievers when interpret overhead would
dominate a wall-clock benchmark).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.dense_topk import (FUSED_BLOCK_C, dense_topk_pallas,
                                      fused_gathered_topk_pallas,
                                      gathered_topk_pallas,
                                      quant_fused_gathered_topk_pallas,
                                      quant_gathered_topk_pallas,
                                      quant_topk_pallas)


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"no Pallas kernel path on platform {platform!r}: the kernels "
            "compile for 'tpu' and run in interpret mode only on 'cpu'")
    return platform == "cpu"


@partial(jax.jit, static_argnames=("k", "force_ref"))
def dense_topk(queries: jax.Array, kb: jax.Array, k: int,
               force_ref: bool = False):
    """Blocked dense retrieval: (B, d) x (N, d) -> top-k (scores, ids)."""
    if force_ref:
        return ref.dense_topk_ref(queries, kb, k)
    return dense_topk_pallas(queries, kb, k, interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "force_ref"))
def gathered_topk(queries: jax.Array, kb: jax.Array, cand: jax.Array, k: int,
                  force_ref: bool = False):
    """Masked/gathered dense retrieval (the ADR/IVF probe): query b scores
    only the KB rows named by cand[b] ((B, C) int32, -1 = padding). The
    candidate-embedding gather runs on device against the resident KB; pad
    slots come back as (NEG sentinel, -1).

    The gather materializes (B, C, d) in HBM before the kernel streams it
    (unlike the numpy path, which chunks rows to bound host scratch) —
    acceptable while B*C*d stays well under the KB's own footprint. The
    serving path uses :func:`fused_gathered_topk` instead, which tiles the
    gather into the pallas grid; this pre-gathered form stays as the
    small-probe fast path and the fused kernels' parity baseline."""
    emb = jnp.take(kb, jnp.maximum(cand, 0), axis=0)     # (B, C, d)
    if force_ref:
        return ref.gathered_topk_ref(queries, emb, cand, k)
    return gathered_topk_pallas(queries, emb, cand, k, interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "block_c", "force_ref"))
def fused_gathered_topk(queries: jax.Array, kb: jax.Array, cand: jax.Array,
                        k: int, block_c: int = FUSED_BLOCK_C,
                        force_ref: bool = False):
    """The fused-gather ADR/IVF probe: query b scores only the KB rows named
    by cand[b] ((B, C) int32, -1 = padding), and the candidate gather runs
    INSIDE the kernel — each candidate's row DMAs from the resident KB, so
    peak candidate scratch (`dense_topk.fused_scratch_bytes`) does not grow
    with C (no (B, C, d) materialization anywhere, including under
    ``force_ref``, whose oracle streams (B, block_c) id tiles with a running
    top-k). Same candidates, ids and tie break as :func:`gathered_topk`."""
    if force_ref:
        return ref.fused_gathered_topk_ref(queries, kb, cand, k,
                                           block_c=block_c)
    return fused_gathered_topk_pallas(queries, kb, cand, k, block_c=block_c,
                                      interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "force_ref"))
def quant_dense_topk(queries: jax.Array, kb_q: jax.Array, scales: jax.Array,
                     k: int, force_ref: bool = False):
    """Fused dequant + matmul + top-k over an int8 KB: (B, d) x (N, d) int8
    with per-row fp32 scales -> top-k of ``(q @ kb_q.T) * scales``. The KB
    never materializes in fp32 — the cast happens tile-wise in VMEM and the
    scale multiply lands on the score tile."""
    if force_ref:
        return ref.quant_dense_topk_ref(queries, kb_q, scales, k)
    return quant_topk_pallas(queries, kb_q, scales, k, interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "force_ref"))
def quant_gathered_topk(queries: jax.Array, kb_q: jax.Array,
                        scales: jax.Array, cand: jax.Array, k: int,
                        force_ref: bool = False):
    """Masked/gathered fused dequant scan (the ADR/IVF probe over an int8 KB):
    query b scores only the rows named by cand[b] ((B, C) int32, -1 = pad).
    The candidate gather pulls int8 codes + fp32 row scales — 4x less HBM
    traffic than the fp32 gather; pad slots come back as (NEG sentinel, -1)."""
    emb = jnp.take(kb_q, jnp.maximum(cand, 0), axis=0)    # (B, C, d) int8
    scl = jnp.take(scales, jnp.maximum(cand, 0), axis=0)  # (B, C) f32
    if force_ref:
        return ref.quant_gathered_topk_ref(queries, emb, scl, cand, k)
    return quant_gathered_topk_pallas(queries, emb, scl, cand, k,
                                      interpret=_interpret())


@partial(jax.jit, static_argnames=("k", "block_c", "force_ref"))
def quant_fused_gathered_topk(queries: jax.Array, kb_q: jax.Array,
                              scales: jax.Array, cand: jax.Array, k: int,
                              block_c: int = FUSED_BLOCK_C,
                              force_ref: bool = False):
    """Fused-gather form of :func:`quant_gathered_topk`: each candidate row's
    int8 codes AND fp32 scale DMA from the resident arrays inside the kernel
    — neither the (B, C, d) code gather nor the (B, C) scale gather
    materializes. Same candidates, ids and tie break as
    :func:`quant_gathered_topk`."""
    if force_ref:
        return ref.quant_fused_gathered_topk_ref(queries, kb_q, scales, cand,
                                                 k, block_c=block_c)
    return quant_fused_gathered_topk_pallas(queries, kb_q, scales, cand, k,
                                            block_c=block_c,
                                            interpret=_interpret())


@partial(jax.jit, static_argnames=("force_ref",))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, force_ref: bool = False):
    """Flash-decode GQA attention over a ring KV cache."""
    if force_ref:
        return ref.decode_attention_ref(q, k_cache, v_cache, cache_len)
    return decode_attention_pallas(q, k_cache, v_cache, cache_len,
                                   interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "window", "prefix_len", "force_ref"))
def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0, prefix_len: int = 0,
                      force_ref: bool = False):
    """Blockwise (flash) causal attention for prefill — never materializes S x S."""
    from repro.kernels.prefill_attention import prefill_attention_pallas
    if force_ref:
        return ref.prefill_attention_ref(q, k, v, causal=causal, window=window,
                                         prefix_len=prefix_len)
    return prefill_attention_pallas(q, k, v, causal=causal, window=window,
                                    prefix_len=prefix_len, interpret=_interpret())
