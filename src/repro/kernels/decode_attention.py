"""Pallas TPU kernel: GQA single-token decode attention over a (ring) KV cache.

The serving-side compute hot spot: one query vector per request attending over a
long KV cache. Adaptation for TPU: flash-decode style — the cache is streamed
(block_w, KV, hd) HBM->VMEM tile by tile, online-softmax accumulators live in VMEM
scratch, invalid ring slots (>= cache_len) are masked. Grid: (batch, cache tiles).

The q/k contraction for one token is a (G, hd) x (hd, block_w) matmul per KV head —
grouped heads give the MXU a real M dimension instead of a degenerate matvec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.4e38


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, *,
                        block_w: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0]                                   # (KV, G, hd)
    k = k_ref[0]                                   # (block_w, KV, hd)
    v = v_ref[0]
    cache_len = len_ref[0]

    s = jax.lax.dot_general(
        q.astype(jnp.float32),                     # (KV, G, hd)
        jnp.swapaxes(k, 0, 1).astype(jnp.float32),  # (KV, block_w, hd)
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale  # (KV, G, block_w)

    idx = j * block_w + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(idx < cache_len, s, NEG)

    m_prev = m_sc[...]                             # (KV, G)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    p = jnp.exp(s - m_new[..., None])              # (KV, G, block_w)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2)
    pv = jax.lax.dot_general(
        p, jnp.swapaxes(v, 0, 1).astype(jnp.float32),  # (KV, block_w, hd)
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # (KV, G, hd)
    acc[...] = acc[...] * corr[..., None] + pv
    m_sc[...] = m_new

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc[...] / jnp.maximum(l_sc[...][..., None], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                            cache_len: jax.Array, *, block_w: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q (B, H, hd); k/v_cache (B, W, KV, hd); cache_len (B,) int32
    -> out (B, H, hd)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    W, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    block_w = min(block_w, W)
    nb = -(-W // block_w)
    pad = nb * block_w - W
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(B, KV, G, hd)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))

    kernel = functools.partial(_decode_attn_kernel, block_w=block_w, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1,), lambda b, j: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_w, KV, hd), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_w, KV, hd), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), lambda b, j: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
        ],
        interpret=interpret, name="decode_attention",
    )(cache_len, qg, k_cache, v_cache)
    return out.reshape(B, H, hd)
