"""Shared neural building blocks.

Everything is written as pure functions over param pytrees so that the whole stack
jits/shards cleanly under pjit. Attention over long sequences is *blockwise*
(online-softmax over KV chunks, flash-attention-style) so `S x S` score matrices are
never materialized — required for the prefill_32k / long_500k shapes.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# --------------------------------------------------------------------------------------
# sharding helper: constraint only when a mesh is in scope (no-op without one)
# --------------------------------------------------------------------------------------
def shard(x: jax.Array, spec: P) -> jax.Array:
    """Sharding constraint that adapts to the active mesh: with no mesh it is
    a no-op; axes missing from the mesh are dropped, and any spec entry whose
    mesh-axis product does not divide the array dimension is dropped (e.g.
    KV=8 heads on a 16-way 'model' axis -> replicated). Keeps one set of
    constraints valid across 1-device serving, the 16x16 pod mesh and the
    2x16x16 multi-pod mesh. Any error from the constraint itself propagates."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def _filter(entry, dim):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a in sizes)
        if not kept:
            return None
        prod = 1
        for a in kept:
            prod *= sizes[a]
        if dim % prod != 0:
            return None
        return kept if len(kept) > 1 else kept[0]

    entries = list(spec) + [None] * (x.ndim - len(spec))
    spec = P(*[_filter(e, x.shape[i]) for i, e in enumerate(entries[: x.ndim])])
    return jax.lax.with_sharding_constraint(x, spec)


def batch_axes(mesh_axis_names) -> tuple:
    """The mesh axes batch is sharded over ('pod','data' when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh_axis_names)


# --------------------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (when rope_theta == 0)."""
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(d_model // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------------------
# blockwise (online-softmax) attention — full-sequence (train / prefill)
# --------------------------------------------------------------------------------------
_NEG_INF = -1e30


def _mask_block(qi: jax.Array, kj: jax.Array, *, causal: bool, window: int,
                prefix_len: int, valid_len: Optional[jax.Array]) -> jax.Array:
    """(bq, bk) boolean allowed-mask for global query idx qi (bq,), key idx kj (bk,)."""
    allowed = jnp.ones((qi.shape[0], kj.shape[0]), dtype=bool)
    qi_ = qi[:, None]
    kj_ = kj[None, :]
    if causal:
        c = kj_ <= qi_
        if prefix_len > 0:
            c = c | ((qi_ < prefix_len) & (kj_ < prefix_len))
        allowed &= c
    if window > 0:
        allowed &= kj_ > qi_ - window
    if valid_len is not None:
        allowed &= kj_ < valid_len
    return allowed


def blockwise_attention(
    q: jax.Array,                # (B, S, H, hd)
    k: jax.Array,                # (B, T, KV, hd)
    v: jax.Array,                # (B, T, KV, hd)
    *,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention. O(bq*bk) live memory; causal chunks are *skipped*
    (dynamic inner fori_loop bound), not just masked, so FLOPs ~ S^2/2 not S^2."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    n_q = -(-S // q_chunk)
    n_kv = -(-T // kv_chunk)
    # pad S/T to chunk multiples
    pad_q = n_q * q_chunk - S
    pad_kv = n_kv * kv_chunk - T
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    qg = q.reshape(B, n_q, q_chunk, KV, G, hd)
    kg = k.reshape(B, n_kv, kv_chunk, KV, hd)
    vg = v.reshape(B, n_kv, kv_chunk, KV, hd)

    def q_body(qi: int):
        q_blk = qg[:, qi]                                    # (B, bq, KV, G, hd)
        q_idx = qi * q_chunk + jnp.arange(q_chunk)

        acc0 = jnp.zeros((B, q_chunk, KV, G, hd), jnp.float32)
        m0 = jnp.full((B, q_chunk, KV, G), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, KV, G), jnp.float32)

        def kv_body(kj, carry):
            acc, m, l = carry
            k_blk = kg[:, kj]                                # (B, bk, KV, hd)
            v_blk = vg[:, kj]
            k_idx = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgh,btkh->bkgqt", q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * scale  # (B,KV,G,bq,bk)
            msk = _mask_block(q_idx, k_idx, causal=causal, window=window,
                              prefix_len=prefix_len,
                              valid_len=jnp.asarray(T))
            s = jnp.where(msk[None, None, None], s, _NEG_INF)
            s = jnp.moveaxis(s, 3, 1)                        # (B,bq,KV,G,bk)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bqkgt,btkh->bqkgh", p, v_blk.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return acc_new, m_new, l_new

        # static per-chunk bounds (qi is a Python int — the q-chunk loop is
        # unrolled) => causal chunk SKIPPING (FLOPs ~ S^2/2, not masked S^2) while
        # staying reverse-differentiable for the training path.
        if causal and window > 0:
            lo = max(0, (qi * q_chunk - window) // kv_chunk)
            hi = min(n_kv, ((qi + 1) * q_chunk - 1) // kv_chunk + 1)
        elif causal:
            lo = 0
            hi = min(n_kv, ((qi + 1) * q_chunk - 1) // kv_chunk + 1)
        else:
            lo, hi = 0, n_kv
        acc, m, l = jax.lax.fori_loop(lo, hi, kv_body, (acc0, m0, l0))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)                            # (B,bq,KV,G,hd)

    outs = jnp.stack([q_body(qi) for qi in range(n_q)])       # (n_q,B,bq,KV,G,hd)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_q * q_chunk, H, hd)
    return out[:, :S]


def plain_attention(q, k, v, *, causal=True, window=0, prefix_len=0,
                    scale=None) -> jax.Array:
    """Reference / short-sequence attention (materializes S x T scores)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,btkh->bqkgt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    msk = _mask_block(jnp.arange(S), jnp.arange(T), causal=causal, window=window,
                      prefix_len=prefix_len, valid_len=None)
    s = jnp.where(msk[None, :, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqkgt,btkh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def decode_attention(
    q: jax.Array,                # (B, 1, H, hd) — current-step query (already roped)
    k_cache: jax.Array,          # (B, W, KV, hd) — roped keys (ring or linear buffer)
    v_cache: jax.Array,          # (B, W, KV, hd)
    cache_len: jax.Array,        # scalar/per-batch number of valid entries
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode against a KV cache. Ring-buffer validity is expressed purely
    through ``cache_len`` masking (entries >= cache_len are invalid); for ring buffers
    cache_len == W once wrapped. Softmax order-invariance makes ring rotation a no-op."""
    B, _, H, hd = q.shape
    W, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    # accumulate in f32 via preferred_element_type — never materialize an f32 COPY
    # of the (huge) cache (that copy doubled decode HBM traffic; EXPERIMENTS §Perf)
    s = jnp.einsum("bkgh,btkh->bkgt", qg.astype(k_cache.dtype), k_cache,
                   preferred_element_type=jnp.float32) * scale   # (B,KV,G,W)
    idx = jnp.arange(W)
    valid = idx[None, :] < jnp.reshape(cache_len, (-1, 1))       # (B,W) or (1,W)
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    sc_in = 1.0 / math.sqrt(d_model)
    sc_out = 1.0 / math.sqrt(d_ff)
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * sc_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * sc_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * sc_out).astype(dtype),
    }


def apply_mlp(p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"])
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard(h, P(("pod", "data"), None, "model"))
    return jnp.einsum("...f,fd->...d", h, p["w_down"])


# --------------------------------------------------------------------------------------
# attention module (projections + rope + blockwise/decode core)
# --------------------------------------------------------------------------------------
def init_attention(key, cfg, dtype, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(keys[0], (d, H * hd)) * sc).astype(dtype),
        "wk": (jax.random.normal(keys[1], (d, KV * hd)) * sc).astype(dtype),
        "wv": (jax.random.normal(keys[2], (d, KV * hd)) * sc).astype(dtype),
        "wo": (jax.random.normal(keys[3], (H * hd, d)) / math.sqrt(H * hd)).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p, cfg, xq, xkv):
    B, S = xq.shape[0], xq.shape[1]
    T = xkv.shape[1]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", xq, p["wq"])
    k = jnp.einsum("btd,dh->bth", xkv, p["wk"])
    v = jnp.einsum("btd,dh->bth", xkv, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, T, KV, hd)
    v = v.reshape(B, T, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def apply_self_attention(p, cfg, x, positions, *, causal=True, window=0,
                         prefix_len=0, q_chunk=1024, kv_chunk=1024) -> jax.Array:
    """Full-sequence self-attention (train / prefill path)."""
    q, k, v = _project_qkv(p, cfg, x, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, P(("pod", "data"), None, "model", None))
    k = shard(k, P(("pod", "data"), None, "model", None))
    S = x.shape[1]
    if S <= max(q_chunk, 2048):
        out = plain_attention(q, k, v, causal=causal, window=window,
                              prefix_len=prefix_len)
    else:
        out = blockwise_attention(q, k, v, causal=causal, window=window,
                                  prefix_len=prefix_len, q_chunk=q_chunk,
                                  kv_chunk=kv_chunk)
    out = out.reshape(x.shape[0], S, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def _mesh_active() -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    return not mesh.empty and any(int(s) > 1 for s in mesh.axis_sizes)


def apply_self_attention_decode(p, cfg, x, position, k_cache, v_cache, cache_len,
                                write_idx) -> tuple:
    """One-token decode: project, rope at `position`, write ring slot, attend.

    Ring write: under a >1-device mesh the cache window may be sharded over
    'model'; a dynamic_update_slice at a dynamic index into a sharded dim makes
    GSPMD all-gather the whole cache per layer (measured: 56GB/step on
    kimi x decode_32k — EXPERIMENTS §Perf). The masked elementwise write shards
    cleanly; single-device serving keeps the cheap in-place slice update.

    Returns (out, new_k_cache, new_v_cache).
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x, x)                   # S == 1
    pos = jnp.reshape(position, (-1, 1)) * jnp.ones((B, 1), jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    if _mesh_active() or jnp.ndim(write_idx) > 0:
        # vector write_idx (B,): per-slot ring positions (multi-request serving —
        # each fleet slot sits at its own absolute position)
        slot = (jnp.arange(k_cache.shape[1])[None, :, None, None]
                == jnp.reshape(write_idx, (-1, 1, 1, 1)))
        k_cache = jnp.where(slot, k.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(slot, v.astype(v_cache.dtype), v_cache)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), write_idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), write_idx, axis=1)
    out = decode_attention(q, k_cache, v_cache, cache_len)
    out = out.reshape(B, 1, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), k_cache, v_cache


def apply_cross_attention(p, cfg, x, mem_k, mem_v) -> jax.Array:
    """Decoder cross-attention over precomputed encoder memory K/V."""
    B, S = x.shape[0], x.shape[1]
    H, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, S, H, hd)
    out = plain_attention(q, mem_k, mem_v, causal=False)
    out = out.reshape(B, S, -1)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def project_memory_kv(p, cfg, mem) -> tuple:
    """Project encoder output into the decoder cross-attention K/V once."""
    B, T = mem.shape[0], mem.shape[1]
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("btd,dh->bth", mem, p["wk"])
    v = jnp.einsum("btd,dh->bth", mem, p["wv"])
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    return k.reshape(B, T, KV, hd), v.reshape(B, T, KV, hd)
