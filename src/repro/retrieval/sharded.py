"""Distributed dense retrieval: the knowledge base sharded across the mesh, batched
verification as a single collective program.

This is the multi-chip form of the paper's verification step (DESIGN §3): each
device scans its KB shard with the blocked top-k (the Pallas kernel on TPU; its
jnp oracle under shard_map here), then the per-shard candidates — k << shard size —
are all-gathered and reduced to a global top-k. Collective volume is
O(devices * B * k * 8 bytes): negligible next to the HBM scan, which is the point —
batched verification scales out linearly with chips.

Serving reaches this through :class:`repro.retrieval.backends.ShardedBackend`
(``--retriever-backend sharded``): the fleet's merged verification call per
round is exactly one invocation of :func:`sharded_dense_topk`, i.e. one
collective per round however many requests participate.

KB sizes need not divide the shard count: the KB is padded to a shard multiple
(here, or at build time by ShardedBackend) and the padded rows' scores are
masked to -inf BEFORE the per-shard top-k, so they can neither displace real
candidates within a shard nor reach the global top-k. Results are
byte-identical to the single-host scan under the canonical tie order (score
desc, id asc — `jax.lax.top_k` order per shard; across shards, equal scores
resolve to the lower shard index = lower global id because shard candidates
concatenate in shard order).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.dense_topk import NEG  # one pad sentinel, every backend

# f32 scores at full f32 precision on every platform: the sharded scan is an
# exact backend, held to byte parity with the numpy scan
_HIGHEST = jax.lax.Precision.HIGHEST


def sharded_dense_topk(queries: jax.Array, kb: jax.Array, k: int, mesh,
                       axis: str = "data", *, n_total: Optional[int] = None,
                       scales: Optional[jax.Array] = None):
    """queries (B, d) replicated; kb (N, d) sharded over `axis`.
    -> (scores (B, k), global ids (B, k)).

    ``n_total`` is the number of REAL KB rows when ``kb`` arrives pre-padded
    to a shard multiple (ShardedBackend pads at build time); rows at global
    ids >= n_total are padding and score -inf. Unpadded non-divisible KBs are
    padded here instead — either way no shard ever misindexes and no padded
    id can reach the global top-k.

    ``scales`` (N,) f32, when given, marks ``kb`` as int8 codes with per-row
    symmetric scales: each shard scores its resident slice as
    ``(q @ codes.T) * scales`` — the dequant multiply lands on the per-shard
    score matrix before the pad mask and per-shard top-k, so only int8 codes
    ever live in shard HBM and the collective shape is unchanged (still ONE
    per call).
    """
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    N = kb.shape[0]
    if n_total is None:
        n_total = N
    shard_n = -(-N // n_shards)
    pad = shard_n * n_shards - N
    if pad:
        kb = jnp.pad(kb, ((0, pad), (0, 0)))
        if scales is not None:
            scales = jnp.pad(scales, ((0, pad),))
    assert k <= n_total, f"top-{k} of a {n_total}-row KB"
    # a shard holds only shard_n rows, so it can contribute at most that many
    # global candidates; n_shards * k_local >= n_total >= k keeps the global
    # reduce exact when k exceeds the shard size
    k_local = min(k, shard_n)

    def local(q, kb_shard, scl_shard):
        kb2 = kb_shard[0] if kb_shard.ndim == 3 else kb_shard
        shard_idx = jax.lax.axis_index(axis)
        s_full = jnp.einsum("bd,nd->bn", q.astype(jnp.float32),
                            kb2.astype(jnp.float32), precision=_HIGHEST)
        if scl_shard is not None:
            scl2 = scl_shard[0] if scl_shard.ndim == 2 else scl_shard
            s_full = s_full * scl2.astype(jnp.float32)[None, :]
        # mask padded rows BEFORE the per-shard top-k: a zero-padded row
        # scores 0.0, which would displace genuinely negative candidates
        col_gids = shard_idx * shard_n + jnp.arange(shard_n, dtype=jnp.int32)
        s_full = jnp.where(col_gids[None, :] < n_total, s_full, NEG)
        s, ids = jax.lax.top_k(s_full, k_local)
        gids = ids.astype(jnp.int32) + shard_idx * shard_n
        # gather candidates from every shard: (n_shards, B, k_local)
        all_s = jax.lax.all_gather(s, axis)
        all_g = jax.lax.all_gather(gids, axis)
        B = q.shape[0]
        cat_s = jnp.moveaxis(all_s, 0, 1).reshape(B, n_shards * k_local)
        cat_g = jnp.moveaxis(all_g, 0, 1).reshape(B, n_shards * k_local)
        top_s, pos = jax.lax.top_k(cat_s, k)
        top_g = jnp.take_along_axis(cat_g, pos, axis=1)
        return top_s, top_g

    # outputs are replicated by construction (all_gather + identical top_k on
    # every shard); the varying-axis inference can't see through axis_index
    if scales is None:
        fn = jax.shard_map(
            lambda q, kb_shard: local(q, kb_shard, None), mesh=mesh,
            in_specs=(P(), P(axis, None)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(queries, kb)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(queries, kb, scales)


def sharded_gathered_topk(queries: jax.Array, kb: jax.Array, cand: jax.Array,
                          k: int, mesh, axis: str = "data", *,
                          n_total: Optional[int] = None,
                          scales: Optional[jax.Array] = None,
                          block_c: Optional[int] = None):
    """The ADR/IVF probe over the sharded KB: queries (B, d) and the padded
    candidate-id matrix cand (B, C) replicated; kb (N, d) sharded over
    ``axis``. -> (scores (B, k), global ids (B, k)); pad slots (-1 in cand,
    or slots beyond a row's real candidate count) surface as (NEG, -1).

    Each shard scores only the candidates RESIDENT in its row range (gather
    from its slice + mask everything else to -inf), takes a per-shard top-k,
    and the candidates all-gather + reduce exactly like the dense scan — so a
    fleet round's merged ADR probe is still ONE collective program. The
    canonical tie order survives because shard s owns the contiguous id range
    [s*shard_n, (s+1)*shard_n): across shards equal scores resolve to the
    lower shard = lower id, and within a shard cand's id-sorted columns make
    lax.top_k's positional tie break id-ascending.

    ``cand`` rows must be id-sorted with -1 pads last and contain no
    duplicate real ids (IVF buckets partition the KB, so probe gathers
    satisfy this by construction). The per-shard gather is TILED: the shard
    program walks ``cand`` in lane-aligned ``block_c`` chunks
    (`kernels.dense_topk.FUSED_BLOCK_C` by default, the same tile width the
    fused kernels use), gathering one (B, block_c, d) slab at a time via
    `lax.map` — peak per-shard candidate scratch is independent of the probe
    width C, and the (B, C) score matrix it builds chunk-wise is a factor d
    smaller. Chunking cannot change a bit: per-candidate dots are computed
    identically and the concatenated chunks reproduce the untiled score
    matrix column-for-column.

    ``scales`` (N,) f32, when given, marks ``kb`` as int8 codes with per-row
    symmetric scales: each shard gathers its resident candidates' codes AND
    row scales chunk-wise, scoring ``(q . codes) * scale`` before the
    residency mask — the probe rides the same single collective over the
    int8-resident mesh."""
    from repro.kernels.dense_topk import FUSED_BLOCK_C, fused_block_c

    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    N = kb.shape[0]
    if n_total is None:
        n_total = N
    shard_n = -(-N // n_shards)
    pad = shard_n * n_shards - N
    if pad:
        kb = jnp.pad(kb, ((0, pad), (0, 0)))
        if scales is not None:
            scales = jnp.pad(scales, ((0, pad),))
    C = cand.shape[1]
    # any single shard may hold ALL of a row's candidates, so the per-shard
    # contribution cannot be divided by n_shards
    k_local = min(k, C)
    # pad the candidate matrix to a tile multiple (-1 = pad -> not owned by
    # any shard -> NEG score, sentinel id; appended columns can't perturb the
    # positional tie break)
    bc = fused_block_c(C, block_c or FUSED_BLOCK_C)
    nbc = -(-C // bc)
    cpad = nbc * bc - C
    if cpad:
        cand = jnp.pad(cand, ((0, 0), (0, cpad)), constant_values=-1)

    def local(q, cd, kb_shard, scl_shard):
        kb2 = kb_shard[0] if kb_shard.ndim == 3 else kb_shard
        shard_idx = jax.lax.axis_index(axis)
        lo = shard_idx * shard_n
        own = (cd >= lo) & (cd < lo + shard_n) & (cd < n_total)
        B = q.shape[0]
        qf = q.astype(jnp.float32)
        scl2 = None
        if scl_shard is not None:
            scl2 = scl_shard[0] if scl_shard.ndim == 2 else scl_shard

        def score_chunk(ch):                   # (B, bc) ids -> (B, bc) f32
            idx = jnp.clip(ch - lo, 0, shard_n - 1)
            emb = jnp.take(kb2, idx, axis=0)   # (B, bc, d): the ONLY gather
            s = jnp.einsum("bcd,bd->bc", emb.astype(jnp.float32), qf,
                           precision=_HIGHEST)
            if scl2 is not None:
                s = s * jnp.take(scl2, idx, axis=0).astype(jnp.float32)
            return s

        chunks = cd.reshape(B, nbc, bc).transpose(1, 0, 2)
        s = jax.lax.map(score_chunk, chunks)   # sequential: one slab live
        s = s.transpose(1, 0, 2).reshape(B, nbc * bc)
        s = jnp.where(own, s, NEG)
        gids = jnp.where(own, cd, -1)          # non-resident/pad: sentinel id
        s_l, pos = jax.lax.top_k(s, k_local)
        g_l = jnp.take_along_axis(gids, pos, axis=1)
        all_s = jax.lax.all_gather(s_l, axis)  # (n_shards, B, k_local)
        all_g = jax.lax.all_gather(g_l, axis)
        B = q.shape[0]
        cat_s = jnp.moveaxis(all_s, 0, 1).reshape(B, n_shards * k_local)
        cat_g = jnp.moveaxis(all_g, 0, 1).reshape(B, n_shards * k_local)
        top_s, p = jax.lax.top_k(cat_s, k_local)
        top_g = jnp.take_along_axis(cat_g, p, axis=1)
        return top_s, top_g

    if scales is None:
        fn = jax.shard_map(
            lambda q, cd, kb_shard: local(q, cd, kb_shard, None), mesh=mesh,
            in_specs=(P(), P(), P(axis, None)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(queries, cand.astype(jnp.int32), kb)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(queries, cand.astype(jnp.int32), kb, scales)


def lower_sharded_retrieval(mesh, *, n_docs: int = 1_048_576, d: int = 256,
                            batch: int = 8, k: int = 20, axis: str = "data"):
    """Dry-run artifact: lower + compile the sharded batched-verification program."""
    q = jax.ShapeDtypeStruct((batch, d), jnp.float32)
    kb = jax.ShapeDtypeStruct((n_docs, d), jnp.float32)
    fn = partial(sharded_dense_topk, k=k, mesh=mesh, axis=axis)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(q, kb)
        return lowered.compile()
