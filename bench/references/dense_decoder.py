"""Plain float32 reference of the dense decoder the configurations serve.

The layer equations, written out here with nothing imported from the
program: token embedding; per layer a pre-norm block
``x += Wo·attn(RoPE(Wq·rms(x)+bq), RoPE(Wk·rms(x)+bk), Wv·rms(x)+bv)`` with
causal softmax attention at scale 1/sqrt(head_dim), then
``x += W_down·(silu(W_gate·rms(x)) * W_up·rms(x))``; a final RMSNorm and an
untied unembedding. RMSNorm is ``x / sqrt(mean(x²) + eps) * scale``; RoPE
rotates the two halves of each head, ``[x1 cos − x2 sin, x2 cos + x1 sin]``,
at angle ``pos / theta**(i / (head_dim/2))``.

Weights live in a dict whose layout matches the served model's parameter
tree (``embed``, ``unembed``, ``final_norm``, ``blocks``: one dict of
layer-stacked arrays), so the same seeded arrays serve both; this module
makes them (``make_params``) and reads them by name.

``precision`` is a pair ``(prefilled, decoded)``: the matmul precision of
the rows before ``split`` (positions a serving engine fills by prefill) and
of the rows from ``split`` on (positions it fills one decode step at a
time), each ``"highest"`` (float32), ``"high"`` (three bf16 passes),
``"default"`` (float32 arrays, one bf16 pass per matmul on a TPU) or
``"bfloat16"`` (weights and activations in bf16, the control one step below
the configurations' float32; only as both halves of the pair). A row's
precision is that of its query position, in the projections, the attention
and the unembedding alike."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_PREC = {"highest": jax.lax.Precision.HIGHEST,
         "high": jax.lax.Precision.HIGH,
         "default": jax.lax.Precision.DEFAULT,
         "bfloat16": jax.lax.Precision.DEFAULT}


def _dtype(precision: tuple):
    if "bfloat16" in precision:
        assert precision == ("bfloat16", "bfloat16"), precision
        return jnp.bfloat16
    return jnp.float32


def _rows(precision: tuple, prefilled):
    """``mm(eq, a, b)``: an einsum at the pair's precision per row, where
    ``prefilled`` (broadcastable to the result) marks the rows of the
    first."""
    def at(prec):
        return partial(jnp.einsum, precision=_PREC[prec],
                       preferred_element_type=jnp.float32)
    if precision[0] == precision[1]:
        return at(precision[0])
    first, second = at(precision[0]), at(precision[1])
    return lambda eq, a, b: jnp.where(prefilled, first(eq, a, b),
                                      second(eq, a, b))


def _leaf_affine(path: str, shape, d_model: int, logit_scale: float):
    """(offset, scale) turning N(0, 1) draws into one parameter's values,
    by name: norms near 1, biases small, matrices N(0, 1/fan_in); the
    embedding by 1/sqrt(d_model); the unembedding times ``logit_scale``
    (the logits' standard deviation)."""
    name = path.rsplit("/", 1)[-1]
    if "norm" in name:
        return 1.0, 0.1
    if name.startswith("b"):
        return 0.0, 0.02
    fan_in = d_model if name == "embed" else shape[-2]
    return 0.0, (logit_scale if name == "unembed" else 1.0) / math.sqrt(fan_in)


def make_params(shapes, key, d_model: int, logit_scale: float = 1.0):
    """Fill a parameter-shape tree (``jax.eval_shape`` of the model's init)
    with seeded float32 values: one jitted call on the device that draws
    every parameter from a single normal stream."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in p) for p, _ in leaves]
    shp = [tuple(s.shape) for _, s in leaves]
    sizes = [math.prod(s) for s in shp]
    aff = [_leaf_affine(p, s, d_model, logit_scale) for p, s in zip(paths, shp)]

    @jax.jit
    def init(k):
        flat = jax.random.normal(k, (sum(sizes),), jnp.float32)
        out, off = [], 0
        for s, n, (a, b) in zip(shp, sizes, aff):
            out.append(a + b * flat[off:off + n].reshape(s))
            off += n
        return out
    return jax.tree_util.tree_unflatten(treedef, init(key))


def layers(params):
    """Per-layer weight dicts from the layer-stacked ``blocks``."""
    stacked = params["blocks"][0]
    n = stacked["norm1"].shape[0]
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(n)]


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta: float):
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


FULL = ("highest", "highest")


@partial(jax.jit, static_argnames=("cfg", "precision"))
def hidden(params, tokens, split, cfg: tuple, precision: tuple = FULL):
    """Final normalised hidden states (B, S, d) of a causal forward pass.
    ``split`` (B,) is each sequence's first row at ``precision[1]``;
    ``cfg`` = (n_heads, head_dim, rope_theta, norm_eps)."""
    H, hd, theta, eps = cfg
    dt = _dtype(precision)
    p = jax.tree.map(lambda a: a.astype(dt), params)
    B, S = tokens.shape
    pre = jnp.arange(S)[None, :] < jnp.reshape(split, (B, 1))    # (B, S)
    mm_s = _rows(precision, pre[:, :, None])            # rows on axis 1
    mm_q = _rows(precision, pre[:, None, :, None])      # (B, H, q, k)
    mm_o = _rows(precision, pre[:, :, None, None])      # (B, q, H, hd)
    x = p["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, lp):
        h = _rms(x, lp["norm1"], eps)
        a = lp["mixer"]
        q, k, v = (mm_s("bsd,dh->bsh", h, a["w" + n]) + a.get("b" + n, 0.0)
                   for n in "qkv")
        q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
        q = _rope(q.reshape(B, S, H, hd), theta)
        k = _rope(k.reshape(B, S, -1, hd), theta)
        v = v.reshape(B, S, -1, hd)
        s = mm_q("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        o = mm_o("bhqk,bkhd->bqhd", w, v).astype(dt).reshape(B, S, H * hd)
        x = (x + mm_s("bsh,hd->bsd", o, a["wo"])).astype(dt)
        h = _rms(x, lp["norm2"], eps)
        f = lp["ffn"]
        g = mm_s("bsd,df->bsf", h, f["w_gate"])
        u = mm_s("bsd,df->bsf", h, f["w_up"])
        m = (jax.nn.silu(g) * u).astype(dt)
        return (x + mm_s("bsf,fd->bsd", m, f["w_down"])).astype(dt), None

    x, _ = jax.lax.scan(block, x, p["blocks"][0])
    return _rms(x, p["final_norm"], eps)


@partial(jax.jit, static_argnames=("precision",))
def logits_at(params, h, prefilled, precision: tuple = FULL):
    """Logits (…, V) in float32 of hidden states h (…, d); ``prefilled``
    (…) marks the rows at ``precision[0]``."""
    dt = _dtype(precision)
    mm = _rows(precision, jnp.asarray(prefilled)[..., None])
    return mm("...d,dv->...v", h.astype(dt), params["unembed"].astype(dt))


def model_cfg(cfg: dict) -> tuple:
    assert cfg["num_kv_heads"] == cfg["num_heads"], "reference: MHA only"
    return (cfg["num_heads"], cfg["head_dim"], float(cfg.get("rope_theta", 1e4)),
            float(cfg.get("norm_eps", 1e-5)))


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul per token, unembedding
    included (embedding lookup, norms and biases excluded)."""
    d, H, KV, hd, f = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + 3 * d * f
    return cfg["num_layers"] * per_layer + d * cfg["vocab_size"]


def encode(tokens, table: np.ndarray, window: int, decay: float) -> np.ndarray:
    """The retrieval query of a context: decay-weighted sum of the last
    ``window`` tokens' table rows (newest weight 1), unit norm."""
    toks = np.asarray(tokens, np.int64)[-window:]
    if toks.size == 0:
        return np.zeros((table.shape[1],), np.float32)
    w = decay ** np.arange(len(toks) - 1, -1, -1, dtype=np.float32)
    v = (table[toks] * w[:, None]).sum(0)
    n = np.linalg.norm(v)
    return (v / n).astype(np.float32) if n > 0 else v.astype(np.float32)
