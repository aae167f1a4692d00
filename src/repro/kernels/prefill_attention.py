"""Pallas TPU kernel: blockwise causal (flash) attention for prefill.

The prefill_32k hot spot: O(S^2) attention computed without ever materializing the
S x S score matrix. Grid (batch*kv_head, q_tiles, kv_tiles); the kv dimension is the
innermost (sequential) grid axis so the online-softmax accumulators for one q tile
live in VMEM scratch across kv steps. Causal tiles above the diagonal are skipped
entirely (masked to no-op via pl.when), halving the MXU work like the pure-JAX
blockwise path — but with explicit VMEM tiling: q tile (bq, G, hd), kv tiles
(bk, hd), accumulators (bq, G, hd) f32.

Supports GQA (G = H / KV query heads per kv head), optional sliding window, and an
optional bidirectional prefix (prefix-LM / PaliGemma).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.4e38


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, *,
                    bq: int, bk: int, seq: int, scale: float, causal: bool,
                    window: int, prefix_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    # tile coordinates (traced: derived from program ids)
    q_lo = qi * bq
    k_lo = kj * bk
    # causal skip: drop kv tiles entirely in the future of every q row of this
    # tile (bidirectional prefix tiles must NOT be skipped); window skip: drop kv
    # tiles entirely behind the sliding window
    needed = (jnp.logical_or(k_lo <= q_lo + bq - 1, k_lo < prefix_len)
              if causal else jnp.bool_(True))
    in_window = (k_lo + bk > q_lo - window) if window > 0 else jnp.bool_(True)

    @pl.when(jnp.logical_and(needed, in_window))
    def _compute():
        q = q_ref[0]                                 # (bq, G, hd)
        k = k_ref[0]                                 # (bk, hd)
        v = v_ref[0]
        G, hd = q.shape[1], q.shape[2]
        s = jax.lax.dot_general(
            q.astype(jnp.float32).reshape(bq * G, hd),
            k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(bq, G, bk) * scale
        q_idx = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, G, bk), 0)
        k_idx = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, G, bk), 2)
        ok = k_idx < seq
        if causal:
            c = k_idx <= q_idx
            if prefix_len > 0:
                c = jnp.logical_or(
                    c, jnp.logical_and(q_idx < prefix_len, k_idx < prefix_len))
            ok = jnp.logical_and(ok, c)
        if window > 0:
            ok = jnp.logical_and(ok, k_idx > q_idx - window)
        s = jnp.where(ok, s, NEG)

        m_prev = m_sc[...]                           # (bq, G)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p.reshape(bq * G, bk), v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(bq, G, -1)
        acc[...] = acc[...] * corr[..., None] + pv
        m_sc[...] = m_new

    @pl.when(kj == nk - 1)
    def _done():
        o_ref[0] = (acc[...] / jnp.maximum(l_sc[...][..., None], 1e-30)
                    ).astype(o_ref.dtype)


def prefill_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True, window: int = 0,
                             prefix_len: int = 0, bq: int = 256, bk: int = 256,
                             interpret: bool = False) -> jax.Array:
    """q (B, S, H, hd); k/v (B, S, KV, hd) -> out (B, S, H, hd)."""
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    bq = min(bq, S)
    bk = min(bk, S)
    nq, nk = -(-S // bq), -(-S // bk)
    pad_q, pad_k = nq * bq - S, nk * bk - S
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    # layout: fold KV into the leading grid dim: (B*KV, S, G, hd) / (B*KV, S, hd)
    qf = q.reshape(B, nq * bq, KV, G, hd).transpose(0, 2, 1, 3, 4) \
          .reshape(B * KV, nq * bq, G, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, nk * bk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, nk * bk, hd)

    kernel = functools.partial(
        _prefill_kernel, bq=bq, bk=bk, seq=S, scale=scale, causal=causal,
        window=window, prefix_len=prefix_len)
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, nq * bq, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, G, hd), jnp.float32),
            pltpu.VMEM((bq, G), jnp.float32),
            pltpu.VMEM((bq, G), jnp.float32),
        ],
        interpret=interpret, name="prefill_attention",
    )(qf, kf, vf)
    out = out.reshape(B, KV, nq * bq, G, hd).transpose(0, 2, 1, 3, 4) \
             .reshape(B, nq * bq, H, hd)
    return out[:, :S]
