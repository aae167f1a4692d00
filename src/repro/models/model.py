"""Unified model assembly for all assigned architectures.

Layer-stacking discipline (DESIGN §7): per-layer *signatures* (mixer kind, MoE?) are
computed from the config; a maximal periodic suffix is `lax.scan`'d over stacked
params (so an 80-layer dense model lowers as one rolled loop; Jamba scans over its
8-layer period) while any irregular prefix (e.g. kimi-k2's dense first layer) runs as
single blocks.

Step kinds:
  * ``forward``              — full sequence (train / prefill), scan-rolled.
  * ``prefill``              — unrolled walk collecting KV caches + recurrent states.
  * ``decode_step``          — one token, per-layer state list (serving path).
  * ``decode_step_stacked``  — one token, scan-rolled stacked state (dry-run path,
                               keeps the HLO compact for 61–80 layer models).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.layers import shard

Sig = Tuple[str, bool]   # (mixer kind, has_moe)


# --------------------------------------------------------------------------------------
# layer plan
# --------------------------------------------------------------------------------------
def signatures(cfg: ModelConfig) -> list:
    kinds = cfg.layer_kinds()
    return [(kinds[i], cfg.layer_has_moe(i)) for i in range(cfg.num_layers)]


def layer_plan(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_prefix_singles, period, n_repeats); n_prefix + period*n_repeats == L."""
    sigs = signatures(cfg)
    LY = len(sigs)
    for p in range(1, min(8, LY) + 1):
        for k in range(0, min(4, LY)):
            tail = sigs[k:]
            if tail and len(tail) % p == 0 and all(
                    tail[i] == tail[i % p] for i in range(len(tail))):
                return k, p, len(tail) // p
    return LY, 1, 0


# --------------------------------------------------------------------------------------
# block init / apply (full sequence)
# --------------------------------------------------------------------------------------
def _init_block(key, cfg: ModelConfig, sig: Sig, dtype, cross: bool) -> dict:
    kind, has_moe = sig
    keys = jax.random.split(key, 4)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    if kind == "attn":
        p["mixer"] = L.init_attention(keys[0], cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = SSM.init_mamba(keys[0], cfg, dtype)
    elif kind == "mlstm":
        p["mixer"] = SSM.init_mlstm(keys[0], cfg, dtype)
    elif kind == "slstm":
        p["mixer"] = SSM.init_slstm(keys[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if cross:
        p["norm_cross"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = L.init_attention(keys[1], cfg, dtype, cross=True)
    if has_moe:
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        p["moe"] = MOE.init_moe(keys[2], cfg, dtype)
    elif cfg.d_ff > 0:
        p["norm2"] = jnp.ones((cfg.d_model,), dtype)
        p["ffn"] = L.init_mlp(keys[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def _apply_block(bp, cfg, sig: Sig, x, positions, *, mem=None, window=0,
                 prefix_len=0, cross: bool = False, moe_exact: bool = False):
    kind, has_moe = sig
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, bp["norm1"], cfg.norm_eps)
    if kind == "attn":
        with jax.named_scope("attention"):
            h = L.apply_self_attention(bp["mixer"], cfg, h, positions,
                                       causal=True, window=window,
                                       prefix_len=prefix_len)
    elif kind == "mamba":
        h = SSM.apply_mamba(bp["mixer"], cfg, h)
    elif kind == "mlstm":
        h = SSM.apply_mlstm(bp["mixer"], cfg, h)
    elif kind == "slstm":
        h = SSM.apply_slstm(bp["mixer"], cfg, h)
    x = x + h
    if cross and mem is not None:
        h = L.rms_norm(x, bp["norm_cross"], cfg.norm_eps)
        mem_k, mem_v = L.project_memory_kv(bp["cross"], cfg, mem)
        h = L.apply_cross_attention(bp["cross"], cfg, h, mem_k, mem_v)
        x = x + h
    if has_moe:
        h = L.rms_norm(x, bp["norm2"], cfg.norm_eps)
        moe_fn = MOE.apply_moe_exact if moe_exact else MOE.apply_moe
        h, a = moe_fn(bp["moe"], cfg, h)
        aux = aux + a
        x = x + h
    elif "ffn" in bp:
        h = L.rms_norm(x, bp["norm2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            x = x + L.apply_mlp(bp["ffn"], h)
    x = shard(x, P(("pod", "data"), None, None))
    return x, aux


def _collect_kv(bp, cfg, x, positions):
    """Roped K/V of the full sequence for decode handoff (attention layers)."""
    h = L.rms_norm(x, bp["norm1"], cfg.norm_eps)
    _, k, v = L._project_qkv(bp["mixer"], cfg, h, h)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return k, v


# --------------------------------------------------------------------------------------
# block apply (decode step)
# --------------------------------------------------------------------------------------
def _apply_block_decode(bp, cfg, sig: Sig, x, state, pos, write_idx, cache_len,
                        *, cross: bool = False, exact_moe: bool = True):
    kind, has_moe = sig
    h = L.rms_norm(x, bp["norm1"], cfg.norm_eps)
    if kind == "attn":
        with jax.named_scope("attention"):
            h, k_new, v_new = L.apply_self_attention_decode(
                bp["mixer"], cfg, h, pos, state["k"], state["v"], cache_len,
                write_idx)
        state = dict(state, k=k_new, v=v_new)
    elif kind == "mamba":
        h, st = SSM.apply_mamba_step(bp["mixer"], cfg, h, state["ssm"])
        state = dict(state, ssm=st)
    elif kind == "mlstm":
        h, st = SSM.apply_mlstm_step(bp["mixer"], cfg, h, state["ssm"])
        state = dict(state, ssm=st)
    elif kind == "slstm":
        h, st = SSM.apply_slstm_step(bp["mixer"], cfg, h, state["ssm"])
        state = dict(state, ssm=st)
    x = x + h
    if cross and "cross_k" in state:
        h = L.rms_norm(x, bp["norm_cross"], cfg.norm_eps)
        h = L.apply_cross_attention(bp["cross"], cfg, h,
                                    state["cross_k"], state["cross_v"])
        x = x + h
    if has_moe:
        h = L.rms_norm(x, bp["norm2"], cfg.norm_eps)
        # serving decode uses the capacity path only on dry-run-scale meshes; the
        # single-token batch fits capacity exactly there. The exact (dropless) MoE
        # keeps decode consistent with prefill at serving scale.
        moe_fn = MOE.apply_moe_exact if exact_moe else MOE.apply_moe
        h, _ = moe_fn(bp["moe"], cfg, h)
        x = x + h
    elif "ffn" in bp:
        h = L.rms_norm(x, bp["norm2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            x = x + L.apply_mlp(bp["ffn"], h)
    return x, state


def _init_layer_state(cfg, sig: Sig, batch: int, window: int, dtype,
                      cross_frames: int = 0) -> dict:
    kind, _ = sig
    st: dict = {}
    if kind == "attn":
        st["k"] = jnp.zeros((batch, window, cfg.num_kv_heads, cfg.head_dim), dtype)
        st["v"] = jnp.zeros((batch, window, cfg.num_kv_heads, cfg.head_dim), dtype)
    elif kind == "mamba":
        st["ssm"] = SSM.init_mamba_state(cfg, batch, dtype)
    elif kind == "mlstm":
        st["ssm"] = SSM.init_mlstm_state(cfg, batch, dtype)
    elif kind == "slstm":
        st["ssm"] = SSM.init_slstm_state(cfg, batch, dtype)
    if cross_frames:
        st["cross_k"] = jnp.zeros((batch, cross_frames, cfg.num_kv_heads, cfg.head_dim), dtype)
        st["cross_v"] = jnp.zeros((batch, cross_frames, cfg.num_kv_heads, cfg.head_dim), dtype)
    return st


def _sinusoid_at(pos, d_model: int):
    """Sinusoidal embedding at `pos` (scalar or per-slot (B,)) -> (B|1, 1, d)."""
    posf = jnp.atleast_1d(jnp.asarray(pos, jnp.float32)).reshape(-1, 1)
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)
    inv = jnp.exp(-math.log(10000.0) * dim / max(d_model // 2 - 1, 1))
    ang = posf * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, None]


# --------------------------------------------------------------------------------------
# the Model facade
# --------------------------------------------------------------------------------------
@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---- init -------------------------------------------------------------------------
    def init(self, key, dtype=jnp.float32) -> dict:
        cfg = self.cfg
        n_pre, period, n_rep = layer_plan(cfg)
        sigs = signatures(cfg)
        cross = cfg.family == "audio"
        keys = jax.random.split(key, cfg.num_layers + 4)
        params: dict = {
            "embed": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model))
                      * (1.0 / math.sqrt(cfg.d_model))).astype(dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = (jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab_size))
                                 * (1.0 / math.sqrt(cfg.d_model))).astype(dtype)
        params["prefix"] = tuple(
            _init_block(keys[i], cfg, sigs[i], dtype, cross) for i in range(n_pre))
        stages = []
        for j in range(period):
            reps = [
                _init_block(keys[n_pre + r * period + j], cfg, sigs[n_pre + j],
                            dtype, cross)
                for r in range(n_rep)
            ]
            if not reps:
                continue
            stages.append(jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
                          if n_rep > 1 else reps[0])
        params["blocks"] = tuple(stages)
        if cfg.family == "audio":
            ekeys = jax.random.split(keys[-3], cfg.encoder_layers)
            params["encoder"] = {
                "layers": tuple(
                    _init_block(ekeys[i], cfg, ("attn", False), dtype, cross=False)
                    for i in range(cfg.encoder_layers)),
                "final_norm": jnp.ones((cfg.d_model,), dtype),
            }
        return params

    # ---- encoder (audio) ----------------------------------------------------------------
    def encode(self, params, frames: jax.Array) -> jax.Array:
        """frames: (B, F, d) precomputed conv-frontend embeddings (assignment stub)."""
        cfg = self.cfg
        x = frames + L.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(frames.dtype)
        positions = jnp.arange(frames.shape[1])[None]
        for bp in params["encoder"]["layers"]:
            h = L.rms_norm(x, bp["norm1"], cfg.norm_eps)
            h = L.apply_self_attention(bp["mixer"], cfg, h, positions, causal=False)
            x = x + h
            h = L.rms_norm(x, bp["norm2"], cfg.norm_eps)
            x = x + L.apply_mlp(bp["ffn"], h)
        return L.rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)

    # ---- embedding / prefix handling ----------------------------------------------------
    def _embed_inputs(self, params, tokens, extra):
        cfg = self.cfg
        x = params["embed"][tokens]
        prefix_len = 0
        if cfg.family == "vlm" and extra is not None and "patches" in extra:
            x = jnp.concatenate([extra["patches"].astype(x.dtype), x], axis=1)
            prefix_len = extra["patches"].shape[1]
        if cfg.rope_theta <= 0:
            x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
        return x, prefix_len

    # ---- full-sequence forward -----------------------------------------------------------
    def forward(self, params, tokens: jax.Array, *, extra: Optional[dict] = None,
                window: int = 0, last_only: bool = False, remat: bool = False):
        """tokens: (B, S_text). Returns (logits, aux_loss). ``last_only`` unembeds
        only the final position (inference-prefill: logits for the next token).
        ``remat`` checkpoints each BLOCK inside the layer scan — without it the
        scan's reverse pass stores every layer's MoE/attention intermediates
        (measured 832GB/chip on kimi x train_4k; EXPERIMENTS §Perf)."""
        cfg = self.cfg
        n_pre, period, n_rep = layer_plan(cfg)
        sigs = signatures(cfg)
        cross = cfg.family == "audio"
        x, prefix_len = self._embed_inputs(params, tokens, extra)
        mem = self.encode(params, extra["frames"]) if cross else None
        positions = jnp.arange(x.shape[1])[None]
        x = shard(x, P(("pod", "data"), None, None))
        aux = jnp.zeros((), jnp.float32)

        def block_fn(bp, sig, x, positions):
            return _apply_block(bp, cfg, sig, x, positions, mem=mem,
                                window=window, prefix_len=prefix_len, cross=cross)

        if remat:
            block_fn = jax.checkpoint(block_fn, static_argnums=(1,))

        for i, bp in enumerate(params["prefix"]):
            x, a = block_fn(bp, sigs[i], x, positions)
            aux += a

        if n_rep == 1:
            for j, bp in enumerate(params["blocks"]):
                x, a = block_fn(bp, sigs[n_pre + j], x, positions)
                aux += a
        elif n_rep > 1:
            period_sigs = [sigs[n_pre + j] for j in range(period)]

            def body(carry, stage_params):
                xx, acc = carry
                for j in range(period):
                    xx, a = block_fn(stage_params[j], period_sigs[j], xx, positions)
                    acc = acc + a
                return (xx, acc), None

            (x, aux), _ = jax.lax.scan(body, (x, aux), tuple(params["blocks"]))

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if last_only:
            x = x[:, -1:]
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("bsd,dv->bsv", x, unembed)
        logits = shard(logits, P(("pod", "data"), None, "model"))
        return logits, aux

    # ---- per-layer param view ------------------------------------------------------------
    def _layer_params(self, params, layer_idx: int):
        n_pre, period, n_rep = layer_plan(self.cfg)
        if layer_idx < n_pre:
            return params["prefix"][layer_idx]
        off = layer_idx - n_pre
        r, j = divmod(off, period)
        stacked = params["blocks"][j]
        if n_rep <= 1:
            return stacked
        return jax.tree.map(lambda t: t[r], stacked)

    # ---- decode state ---------------------------------------------------------------------
    def init_decode_state(self, batch: int, window: int, dtype=jnp.float32) -> list:
        cfg = self.cfg
        cross_frames = cfg.encoder_frames if cfg.family == "audio" else 0
        return [_init_layer_state(cfg, s, batch, window, dtype, cross_frames)
                for s in signatures(cfg)]

    def init_decode_state_stacked(self, batch: int, window: int, dtype=jnp.float32):
        """Stacked layout mirroring the param layout (dry-run / compiled decode)."""
        cfg = self.cfg
        n_pre, period, n_rep = layer_plan(cfg)
        sigs = signatures(cfg)
        cross_frames = cfg.encoder_frames if cfg.family == "audio" else 0
        prefix = tuple(_init_layer_state(cfg, sigs[i], batch, window, dtype,
                                         cross_frames) for i in range(n_pre))
        stages = []
        for j in range(period):
            if n_rep == 0:
                break
            one = _init_layer_state(cfg, sigs[n_pre + j], batch, window, dtype,
                                    cross_frames)
            if n_rep > 1:
                one = jax.tree.map(
                    lambda t: jnp.broadcast_to(t, (n_rep,) + t.shape), one)
            stages.append(one)
        return {"prefix": prefix, "stages": tuple(stages)}

    # ---- decode (serving path: per-layer list) ----------------------------------------------
    def decode_step(self, params, state: list, token: jax.Array, pos: jax.Array):
        """token: (B,) int32; pos: scalar absolute position shared by the batch, or
        per-slot (B,) positions (fleet serving: every slot decodes at its own
        absolute position). -> (logits (B,V), state)."""
        cfg = self.cfg
        sigs = signatures(cfg)
        x = params["embed"][token][:, None]
        if cfg.rope_theta <= 0:
            x = x + _sinusoid_at(pos, cfg.d_model).astype(x.dtype)
        cross = cfg.family == "audio"
        new_state = []
        for i, sig in enumerate(sigs):
            bp = self._layer_params(params, i)
            st = state[i]
            write_idx, cache_len = self._ring(st, sig, pos)
            x, st = _apply_block_decode(bp, cfg, sig, x, st, pos, write_idx,
                                        cache_len, cross=cross)
            new_state.append(st)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        with jax.named_scope("unembed"):
            logits = jnp.einsum("bsd,dv->bsv", x, unembed)[:, 0]
        return logits, new_state

    @staticmethod
    def _ring(st, sig, pos):
        if sig[0] == "attn":
            W = st["k"].shape[1]
            return (pos % W).astype(jnp.int32), jnp.minimum(pos + 1, W).astype(jnp.int32)
        return jnp.int32(0), jnp.int32(0)

    # ---- decode (dry-run path: stacked state, scan-rolled) -----------------------------------
    def decode_step_stacked(self, params, state: dict, token: jax.Array,
                            pos: jax.Array):
        cfg = self.cfg
        n_pre, period, n_rep = layer_plan(cfg)
        sigs = signatures(cfg)
        cross = cfg.family == "audio"
        x = params["embed"][token][:, None]
        if cfg.rope_theta <= 0:
            x = x + _sinusoid_at(pos, cfg.d_model).astype(x.dtype)

        new_prefix = []
        for i, st in enumerate(state["prefix"]):
            bp = params["prefix"][i]
            write_idx, cache_len = self._ring(st, sigs[i], pos)
            x, st = _apply_block_decode(bp, cfg, sigs[i], x, st, pos, write_idx,
                                        cache_len, cross=cross, exact_moe=False)
            new_prefix.append(st)

        new_stages = state["stages"]
        if n_rep == 1:
            new_stages = []
            for j, bp in enumerate(params["blocks"]):
                sig = sigs[n_pre + j]
                st = state["stages"][j]
                write_idx, cache_len = self._ring(st, sig, pos)
                x, st = _apply_block_decode(bp, cfg, sig, x, st, pos, write_idx,
                                            cache_len, cross=cross,
                                            exact_moe=False)
                new_stages.append(st)
            new_stages = tuple(new_stages)
        elif n_rep > 1:
            period_sigs = [sigs[n_pre + j] for j in range(period)]

            def body(xx, inp):
                stage_params, stage_states = inp
                outs = []
                for j in range(period):
                    st = stage_states[j]
                    write_idx, cache_len = self._ring(st, period_sigs[j], pos)
                    xx, st = _apply_block_decode(stage_params[j], cfg,
                                                 period_sigs[j], xx, st, pos,
                                                 write_idx, cache_len, cross=cross,
                                                 exact_moe=False)
                    outs.append(st)
                return xx, tuple(outs)

            x, new_stages = jax.lax.scan(
                body, x, (tuple(params["blocks"]), state["stages"]))

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("bsd,dv->bsv", x, unembed)[:, 0]
        return logits, {"prefix": tuple(new_prefix), "stages": new_stages}

    # ---- prefill ---------------------------------------------------------------------------
    def prefill(self, params, tokens, *, extra=None, window_cache: int = 0,
                dtype=jnp.float32):
        """Unrolled full-sequence walk that also builds the decode state.

        Returns (last_logits (B, V), state list, next_pos scalar).
        """
        cfg = self.cfg
        sigs = signatures(cfg)
        cross = cfg.family == "audio"
        B = tokens.shape[0]
        x, prefix_len = self._embed_inputs(params, tokens, extra)
        S = x.shape[1]
        # default: full-attention decode with headroom (W=S would evict position 0
        # on the very first decode step — sliding-window semantics, not intended)
        W = window_cache or (S + 512)
        positions = jnp.arange(S)[None]
        mem = self.encode(params, extra["frames"]) if cross else None
        state = self.init_decode_state(B, W, dtype)

        for i, sig in enumerate(sigs):
            bp = self._layer_params(params, i)
            if sig[0] == "attn":
                with jax.named_scope("attention"):
                    k, v = _collect_kv(bp, cfg, x, positions)
                take = min(W, S)
                kk = k[:, -take:].astype(state[i]["k"].dtype)
                vv = v[:, -take:].astype(state[i]["v"].dtype)
                if take < W:
                    kk = jnp.pad(kk, ((0, 0), (0, W - take), (0, 0), (0, 0)))
                    vv = jnp.pad(vv, ((0, 0), (0, W - take), (0, 0), (0, 0)))
                if S > W:
                    # ring alignment: position p lives at index p % W
                    kk = jnp.roll(kk, S % W, axis=1)
                    vv = jnp.roll(vv, S % W, axis=1)
                state[i]["k"], state[i]["v"] = kk, vv
            else:
                h = L.rms_norm(x, bp["norm1"], cfg.norm_eps)
                state[i]["ssm"] = _final_state(bp["mixer"], cfg, sig[0], h)
            if cross:
                mk, mv = L.project_memory_kv(bp["cross"], cfg, mem)
                state[i]["cross_k"] = mk.astype(state[i]["cross_k"].dtype)
                state[i]["cross_v"] = mv.astype(state[i]["cross_v"].dtype)
            x, _ = _apply_block(bp, cfg, sig, x, positions, mem=mem,
                                prefix_len=prefix_len, cross=cross, moe_exact=True)

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        with jax.named_scope("unembed"):
            logits = jnp.einsum("bsd,dv->bsv", x, unembed)
        return logits[:, -1], state, jnp.int32(S)


def _final_state(mp, cfg, kind: str, h: jax.Array) -> dict:
    """Final recurrent state after consuming h (B, S, d) — stepwise scan."""
    B = h.shape[0]
    if kind == "mamba":
        st = SSM.init_mamba_state(cfg, B, h.dtype)
        step = lambda s, xt: (SSM.apply_mamba_step(mp, cfg, xt[:, None], s)[1], None)
    elif kind == "mlstm":
        st = SSM.init_mlstm_state(cfg, B, h.dtype)
        step = lambda s, xt: (SSM.apply_mlstm_step(mp, cfg, xt[:, None], s)[1], None)
    else:
        st = SSM.init_slstm_state(cfg, B, h.dtype)
        step = lambda s, xt: (SSM.apply_slstm_step(mp, cfg, xt[:, None], s)[1], None)
    st, _ = jax.lax.scan(step, st, h.swapaxes(0, 1))
    return st


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
