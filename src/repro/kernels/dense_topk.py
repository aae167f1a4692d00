"""Pallas TPU kernel: blocked dense retrieval (similarity + streaming top-k).

This is the paper's exact-dense-retriever hot spot, adapted for TPU (DESIGN §3):
FAISS's GPU brute-force scan becomes a single fused kernel that

  * streams KB-embedding tiles (block_n, d) HBM -> VMEM via the BlockSpec pipeline,
  * scores them against the *whole query batch* on the MXU ((B, d) @ (d, block_n) —
    batched verification maps directly onto the B dimension, which is why batching
    is structurally cheap on TPU, cf. paper §A.1),
  * maintains a running top-k per query in VMEM scratch across grid steps using
    K rounds of max-extraction (no lax.top_k inside the kernel — portable and
    MXU/VPU-friendly for the small K regime retrieval lives in).

Grid: one dimension over KB tiles. The query block is small (B ≤ 128 rows padded to
8/128 lanes) and stays resident in VMEM for every grid step.

The GATHERED variant (:func:`gathered_topk_pallas`) is the ADR/IVF form of the
same scan: instead of every KB row, query b scores only its probed buckets'
members, handed in as a pre-gathered (B, C, d) candidate-embedding tensor plus
the (B, C) candidate-id matrix (-1 = padding). Pad slots are masked to the NEG
sentinel before the streaming top-k, so they can never displace a real
candidate; candidate columns arrive id-sorted (the backend contract), which
makes the kernel's first-position tie break the canonical id-ascending order.

The QUANT variants (:func:`quant_topk_pallas`, :func:`quant_gathered_topk_pallas`)
are the int8-KB form of both scans: the KB streams as int8 codes plus a per-row
fp32 scale (symmetric per-row quantization — see
`repro.retrieval.backends.quantize_kb`), and DEQUANT + MATMUL + TOP-K fuse into
one kernel. The int8→f32 cast happens tile-by-tile in VMEM, the scale multiply
lands on the (B, block) score tile, and nothing fp32-sized ever round-trips
through HBM — which is the point: HBM traffic (and KB residency) drop ~4x while
the streaming top-k machinery is byte-for-byte the same `_select_topk`.

The FUSED-GATHER variants (:func:`fused_gathered_topk_pallas`,
:func:`quant_fused_gathered_topk_pallas`) remove the pre-gathered (B, C, d)
tensor entirely: the kernel receives the DEVICE-RESIDENT KB (``pl.ANY``
memory space — HBM on TPU) plus the padded candidate-id matrix, and for every
candidate of the current ``(B, block_c)`` id tile DMAs the candidate's row
from the KB. An HBM slice must respect the array's (rows, 128) tiling, so the
DMA fetches the aligned row GROUP holding the candidate (8 rows for fp32, 32
for int8) into a ring of ``GATHER_SLOTS`` VMEM staging buffers, and the row is
picked out in VMEM; ``GATHER_SLOTS - 1`` group copies stay in flight ahead of
the one being scored. Each candidate's dot with its query lands straight in a
(B, block_c) score tile, so peak candidate scratch is that tile plus the
staging ring — independent of C, where the pre-gathered path materializes
B * C * d in HBM. The int8 form DMAs the (8, 128) tile of the row scales
(laid out (N / 128, 128)) the same way, so not even the (B, C) scale gather
materializes. Scores are the pre-gathered kernel's (per-candidate dots don't
care how the row arrived), and the merge is the same `_select_topk`.

Every kernel compiles for the chip's Mosaic compiler (tests/test_tpu_compile.py
compiles each at serving widths against a described v5e topology); the CPU
test suite runs the same bodies in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3.4e38
# scores at full f32 precision on the MXU: the f32 kernels are exact backends,
# held to byte parity with the numpy scan
_HIGHEST = jax.lax.Precision.HIGHEST


def _select_topk(scores, ids, k: int):
    """K rounds of (max, first argmax, mask) over axis 1. scores (B, M) f32,
    ids (B, M) -> (B, k) each.

    Written with reductions and selects only (no gather, no 1-D vectors), the
    forms Mosaic lowers: the first position holding the row max is a min over
    the positions equal to it, and the id there is a max over a one-hot
    mask. Results land in (B, k) accumulators by column select.

    An extracted slot's ID is masked to -1 along with its score: once a row
    runs out of real candidates (gathered scans with fewer than k real
    candidates), every further round re-picks an all-NEG position, and it
    must surface as the (-1, NEG) pad sentinel — not echo the id it extracted
    on an earlier grid step."""
    B, M = scores.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (B, M), 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (B, k), 1)
    out_s = jnp.full((B, k), NEG, jnp.float32)
    out_i = jnp.full((B, k), -1, jnp.int32)
    for r in range(k):
        m = jnp.max(scores, axis=1, keepdims=True)                 # (B, 1)
        a = jnp.min(jnp.where(scores == m, col, M), axis=1, keepdims=True)
        picked = col == a
        i = jnp.max(jnp.where(picked, ids, -1), axis=1, keepdims=True)
        out_s = jnp.where(out_col == r, m, out_s)
        out_i = jnp.where(out_col == r, i, out_i)
        scores = jnp.where(picked, NEG, scores)
        ids = jnp.where(picked, -1, ids)
    return out_s, out_i


def _stream_merge(s, ids, out_s_ref, out_i_ref, run_s, run_i, k: int):
    """One grid step of the streaming top-k: reset the running (B, k) carry
    on the first step, merge this step's scored tile ``s`` / ``ids`` into it
    (carry first, so ties keep resolving toward earlier columns), and write
    the carry out on the last step."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, NEG)
        run_i[...] = jnp.full_like(run_i, -1)

    merged_s = jnp.concatenate([run_s[...], s], axis=1)
    merged_i = jnp.concatenate([run_i[...], ids], axis=1)
    top_s, top_i = _select_topk(merged_s, merged_i, k)
    run_s[...] = top_s
    run_i[...] = top_i

    @pl.when(j == pl.num_programs(0) - 1)
    def _done():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


def _topk_outputs(B: int, k: int):
    """(out_shape, out_specs, scratch) of the (B, k) streaming top-k — shared
    by every kernel here."""
    out_shape = [jax.ShapeDtypeStruct((B, k), jnp.float32),
                 jax.ShapeDtypeStruct((B, k), jnp.int32)]
    out_specs = [pl.BlockSpec((B, k), lambda j: (0, 0)),
                 pl.BlockSpec((B, k), lambda j: (0, 0))]
    scratch = [pltpu.VMEM((B, k), jnp.float32), pltpu.VMEM((B, k), jnp.int32)]
    return out_shape, out_specs, scratch


def _rowwise_dot(q_ref, emb_ref):
    """q (B, d), emb (B, C, d) -> (B, C) f32 with q[b] . emb[b, c]: one
    (1, d) x (C, d)^T MXU matmul per query row. Mosaic has no batched
    matmul whose left operand is rank 2, and a broadcast multiply would hold
    a (B, C, d) f32 product in VMEM."""
    rows = [jax.lax.dot_general(q_ref[pl.ds(b, 1), :].astype(jnp.float32),
                                emb_ref[b].astype(jnp.float32),
                                (((1,), (1,)), ((), ())), precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
            for b in range(q_ref.shape[0])]
    return jnp.concatenate(rows, axis=0)


# VMEM the pre-gathered kernels' double-buffered (B, block_c, d) candidate
# tile may take: the scoped VMEM limit is 16 MiB on v5e
_GATHER_TILE_BUDGET = 8 << 20


def _pregathered_block_c(B: int, C: int, d: int, dtype, block_c: int) -> int:
    """Lane-aligned candidate tile of the pre-gathered kernels: never tiny,
    never wider than C rounded up to the lane grid, and narrowed until the
    double-buffered tile fits :data:`_GATHER_TILE_BUDGET`."""
    fit = _GATHER_TILE_BUDGET // (2 * B * d * jnp.dtype(dtype).itemsize)
    return max(min(block_c, -(-C // 128) * 128, fit // 128 * 128), 128)


def _topk_kernel(q_ref, kb_ref, out_s_ref, out_i_ref, run_s, run_i, *,
                 k: int, block_n: int, n_total: int):
    q = q_ref[...]                                        # (B, d)
    kb = kb_ref[...]                                      # (block_n, d)
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)   # (B, block_n)
    ids = (pl.program_id(0) * block_n
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    s = jnp.where(ids < n_total, s, NEG)                  # mask KB padding rows
    _stream_merge(s, ids, out_s_ref, out_i_ref, run_s, run_i, k)


def _gathered_topk_kernel(q_ref, emb_ref, cand_ref, out_s_ref, out_i_ref,
                          run_s, run_i, *, k: int):
    ids = cand_ref[...]                                   # (B, block_c)
    s = _rowwise_dot(q_ref, emb_ref)                      # (B, block_c)
    # mask candidate padding (id -1) — pad slots keep id -1 through _select_topk
    s = jnp.where(ids >= 0, s, NEG)
    _stream_merge(s, ids, out_s_ref, out_i_ref, run_s, run_i, k)


def gathered_topk_pallas(queries: jax.Array, cand_emb: jax.Array,
                         cand: jax.Array, k: int, *, block_c: int = 512,
                         interpret: bool = False):
    """queries (B, d) f32; cand_emb (B, C, d) f32; cand (B, C) int32 (-1 pad)
    -> (scores (B, k), ids (B, k)); pad slots surface as (NEG, -1)."""
    B, d = queries.shape
    C = cand.shape[1]
    block_c = _pregathered_block_c(B, C, d, cand_emb.dtype, block_c)
    nb = -(-C // block_c)
    pad = nb * block_c - C
    if pad:
        cand_emb = jnp.pad(cand_emb, ((0, 0), (0, pad), (0, 0)))
        cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)

    out_shape, out_specs, scratch = _topk_outputs(B, k)
    return pl.pallas_call(
        functools.partial(_gathered_topk_kernel, k=k),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((B, d), lambda j: (0, 0)),           # queries resident
            pl.BlockSpec((B, block_c, d), lambda j: (0, j, 0)),  # cand tiles
            pl.BlockSpec((B, block_c), lambda j: (0, j)),
        ],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, name="gathered_topk",
    )(queries, cand_emb, cand)


def dense_topk_pallas(queries: jax.Array, kb: jax.Array, k: int, *,
                      block_n: int = 1024, interpret: bool = False):
    """queries (B, d) f32; kb (N, d) f32 -> (scores (B, k), ids (B, k))."""
    B, d = queries.shape
    N = kb.shape[0]
    block_n = max(min(block_n, N), 128)     # MXU-aligned tile, never tiny
    nb = -(-N // block_n)
    pad = nb * block_n - N
    if pad:
        with jax.named_scope("kb_pad"):
            kb = jnp.pad(kb, ((0, pad), (0, 0)))

    out_shape, out_specs, scratch = _topk_outputs(B, k)
    with jax.named_scope("kb_scan"):
        return pl.pallas_call(
            functools.partial(_topk_kernel, k=k, block_n=block_n, n_total=N),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((B, d), lambda j: (0, 0)),      # queries resident
                pl.BlockSpec((block_n, d), lambda j: (j, 0)),  # KB tile stream
            ],
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret, name="dense_topk",
        )(queries, kb)


def _quant_topk_kernel(q_ref, kbq_ref, scale_ref, out_s_ref, out_i_ref,
                       run_s, run_i, *, k: int, block_n: int, n_total: int):
    """Fused dequant + matmul + streaming top-k over an int8 KB tile.

    The tile dequantizes in VMEM (int8 -> f32 cast feeds the MXU matmul) and
    the per-row scale lands on the (B, block_n) SCORE tile — one multiply per
    score instead of one per KB element, algebraically identical because the
    scale is constant along d: q . (s_i * c_i) == s_i * (q . c_i)."""
    q = q_ref[...]                                        # (B, d) f32
    kbq = kbq_ref[...].astype(jnp.float32)                # (block_n, d) int8
    s = jax.lax.dot_general(q, kbq, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)   # (B, block_n)
    s = s * scale_ref[...]                                # (1, block_n) scales
    ids = (pl.program_id(0) * block_n
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    s = jnp.where(ids < n_total, s, NEG)                  # mask KB padding rows
    _stream_merge(s, ids, out_s_ref, out_i_ref, run_s, run_i, k)


def _quant_gathered_topk_kernel(q_ref, emb_ref, scl_ref, cand_ref, out_s_ref,
                                out_i_ref, run_s, run_i, *, k: int):
    """Gathered (ADR/IVF) form of the fused dequant scan: per-row dot over
    int8 candidate embeddings, candidate-wise scale multiply, pad slots
    (-1 ids) masked to NEG before the streaming top-k."""
    ids = cand_ref[...]                                   # (B, block_c)
    s = _rowwise_dot(q_ref, emb_ref)                      # int8 codes -> f32
    s = s * scl_ref[...]                                  # (B, block_c) scales
    s = jnp.where(ids >= 0, s, NEG)
    _stream_merge(s, ids, out_s_ref, out_i_ref, run_s, run_i, k)


def quant_topk_pallas(queries: jax.Array, kb_q: jax.Array, scales: jax.Array,
                      k: int, *, block_n: int = 1024,
                      interpret: bool = False):
    """queries (B, d) f32; kb_q (N, d) int8; scales (N,) f32
    -> (scores (B, k), ids (B, k)) of the dequantized scan
    ``(q @ kb_q.T) * scales``."""
    B, d = queries.shape
    N = kb_q.shape[0]
    block_n = max(min(block_n, N), 128)     # MXU-aligned tile, never tiny
    nb = -(-N // block_n)
    pad = nb * block_n - N
    if pad:
        kb_q = jnp.pad(kb_q, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, (0, pad))
    # scales stream as one lane-aligned (1, block_n) slice of a single row
    # per grid step: a (1, block_n) block of an (nb, block_n) array would
    # break the (8, 128) block rule
    scales = scales.reshape(1, nb * block_n)

    out_shape, out_specs, scratch = _topk_outputs(B, k)
    return pl.pallas_call(
        functools.partial(_quant_topk_kernel, k=k, block_n=block_n,
                          n_total=N),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((B, d), lambda j: (0, 0)),          # queries resident
            pl.BlockSpec((block_n, d), lambda j: (j, 0)),    # int8 tile stream
            pl.BlockSpec((1, block_n), lambda j: (0, j)),    # row scales
        ],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, name="quant_topk",
    )(queries, kb_q, scales)


def quant_gathered_topk_pallas(queries: jax.Array, cand_emb: jax.Array,
                               cand_scl: jax.Array, cand: jax.Array, k: int, *,
                               block_c: int = 512, interpret: bool = False):
    """queries (B, d) f32; cand_emb (B, C, d) int8; cand_scl (B, C) f32;
    cand (B, C) int32 (-1 pad) -> (scores (B, k), ids (B, k)); pad slots
    surface as (NEG, -1)."""
    B, d = queries.shape
    C = cand.shape[1]
    block_c = _pregathered_block_c(B, C, d, cand_emb.dtype, block_c)
    nb = -(-C // block_c)
    pad = nb * block_c - C
    if pad:
        cand_emb = jnp.pad(cand_emb, ((0, 0), (0, pad), (0, 0)))
        cand_scl = jnp.pad(cand_scl, ((0, 0), (0, pad)))
        cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)

    out_shape, out_specs, scratch = _topk_outputs(B, k)
    return pl.pallas_call(
        functools.partial(_quant_gathered_topk_kernel, k=k),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((B, d), lambda j: (0, 0)),           # queries resident
            pl.BlockSpec((B, block_c, d), lambda j: (0, j, 0)),  # int8 tiles
            pl.BlockSpec((B, block_c), lambda j: (0, j)),     # cand scales
            pl.BlockSpec((B, block_c), lambda j: (0, j)),
        ],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, name="quant_gathered_topk",
    )(queries, cand_emb, cand_scl, cand)


# ---------------------------------------------------------------------------
# Fused in-kernel candidate gather: no pre-gathered (B, C, d) tensor.
# ---------------------------------------------------------------------------

FUSED_BLOCK_C = 256     # default candidate tile: one (B, 256) score tile
GATHER_SLOTS = 8        # staging ring: row-group DMAs in flight + the one scored
SCALE_LANES = 128       # int8 row scales are gathered from an (N/128, 128) view
SCALE_ROWS = 8          # rows of that view per DMA'd (8, 128) tile


def fused_block_c(C: int, block_c: int = FUSED_BLOCK_C) -> int:
    """The gather tile width a fused call at candidate width C actually uses:
    lane-aligned, never tiny, never wider than C rounded up to the lane grid.
    One definition shared by the kernels, the jnp oracle (so streaming merges
    agree chunk-for-chunk), and the backends' scratch accounting."""
    return max(min(block_c, -(-C // 128) * 128), 128)


def tile_rows(dtype) -> int:
    """Rows in one HBM tile of an (N, d) array of ``dtype`` — 8 for 32-bit,
    16 for 16-bit, 32 for 8-bit types. A DMA may slice rows only on this
    grid, so it is the row group the fused gather fetches per candidate."""
    return 32 // jnp.dtype(dtype).itemsize


def fused_scratch_bytes(B: int, C: int, d: int, dtype,
                        block_c: int = FUSED_BLOCK_C,
                        quant: bool = False) -> int:
    """Peak candidate scratch of one fused call: the (B, block_c) f32 score
    tile plus the staging ring of row groups (and of scale tiles when int8).
    Independent of C beyond the tile width."""
    ring = GATHER_SLOTS * tile_rows(dtype) * d * jnp.dtype(dtype).itemsize
    if quant:
        ring += GATHER_SLOTS * SCALE_ROWS * SCALE_LANES * 4
    return B * fused_block_c(C, block_c) * 4 + ring


def _pad_rows(x: jax.Array, multiple: int) -> jax.Array:
    pad = -x.shape[0] % multiple
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _pick(tile, mask):
    """The one element (per column) of ``tile`` where ``mask`` holds, by a
    max over -inf elsewhere — exact, and free of dynamic sublane indexing."""
    return jnp.max(jnp.where(mask, tile, -jnp.inf), axis=0, keepdims=True)


def _fused_gathered_kernel(cand_sref, q_ref, ids_ref, kb_ref, *refs, k: int,
                           quant: bool):
    """In-kernel gather form of the gathered scans. ``refs`` is
    ``[scl_ref,] out_s, out_i, stage, [scl_stage,] run_s, run_i, sem``.

    Candidate i of the tile is (query b, column c) = divmod(i, block_c); its
    id comes from this step's id tile in SMEM (``cand_sref`` — scalar reads
    are free there, and one (B, block_c) tile is all SMEM ever holds). Pad
    ids (-1) clamp to row 0, fetched-but-masked like the pre-gathered path's
    jnp.take(maximum(cand, 0))."""
    if quant:
        scl_ref, out_s_ref, out_i_ref, stage, scl_stage, run_s, run_i, sem = refs
    else:
        out_s_ref, out_i_ref, stage, run_s, run_i, sem = refs
    B, block_c = ids_ref.shape
    rows = stage.shape[1]
    total = B * block_c
    group = SCALE_ROWS * SCALE_LANES

    def locate(i):
        b = i // block_c
        c = i - b * block_c
        return b, c, jnp.maximum(cand_sref[b, c], 0)

    def copies(i):
        row = locate(i)[2]
        slot = jax.lax.rem(i, GATHER_SLOTS)
        cps = [pltpu.make_async_copy(
            kb_ref.at[pl.ds(pl.multiple_of(row // rows * rows, rows), rows)],
            stage.at[slot], sem.at[0, slot])]
        if quant:
            cps.append(pltpu.make_async_copy(
                scl_ref.at[pl.ds(pl.multiple_of(row // group * SCALE_ROWS,
                                                SCALE_ROWS), SCALE_ROWS)],
                scl_stage.at[slot], sem.at[1, slot]))
        return cps

    for i in range(min(GATHER_SLOTS - 1, total)):       # fill the ring
        for cp in copies(i):
            cp.start()

    sub = jax.lax.broadcasted_iota(jnp.int32, (B, block_c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, block_c), 1)
    d = stage.shape[2]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0)
    s_sub = jax.lax.broadcasted_iota(jnp.int32, (SCALE_ROWS, SCALE_LANES), 0)
    s_lane = jax.lax.broadcasted_iota(jnp.int32, (SCALE_ROWS, SCALE_LANES), 1)

    def body(i, s):
        @pl.when(i + GATHER_SLOTS - 1 < total)
        def _prefetch():
            for cp in copies(i + GATHER_SLOTS - 1):
                cp.start()

        for cp in copies(i):
            cp.wait()
        b, c, row = locate(i)
        slot = jax.lax.rem(i, GATHER_SLOTS)
        r = jax.lax.rem(row, rows)
        if stage.dtype.itemsize == 4:                   # 32-bit: direct load
            emb = stage[slot, pl.ds(r, 1), :]
        else:                                           # packed: pick in f32
            emb = _pick(stage[slot].astype(jnp.float32), row_iota == r)
        v = jnp.sum(q_ref[pl.ds(b, 1), :] * emb.astype(jnp.float32),
                    axis=1, keepdims=True)              # (1, 1)
        if quant:
            hit = ((s_sub == jax.lax.rem(row // SCALE_LANES, SCALE_ROWS))
                   & (s_lane == jax.lax.rem(row, SCALE_LANES)))
            v = v * jnp.max(_pick(scl_stage[slot], hit), axis=1,
                            keepdims=True)
        return jnp.where((sub == b) & (lane == c), v, s)

    s = jax.lax.fori_loop(0, total, body,
                          jnp.zeros((B, block_c), jnp.float32))
    ids = ids_ref[...]                                    # (B, block_c)
    s = jnp.where(ids >= 0, s, NEG)
    _stream_merge(s, ids, out_s_ref, out_i_ref, run_s, run_i, k)


def _fused_call(queries, kb, scales, cand, k, block_c, interpret):
    B, d = queries.shape
    C = cand.shape[1]
    block_c = fused_block_c(C, block_c)
    nb = -(-C // block_c)
    pad = nb * block_c - C
    if pad:
        cand = jnp.pad(cand, ((0, 0), (0, pad)), constant_values=-1)
    rows = tile_rows(kb.dtype)
    quant = scales is not None
    # a KB whose row count is off the tile grid is padded, a copy per call;
    # the backends' KBs are sized on it
    operands = [cand, queries.astype(jnp.float32), cand, _pad_rows(kb, rows)]
    in_specs = [
        pl.BlockSpec((B, block_c), lambda j: (0, j),        # id tiles: SMEM
                     memory_space=pltpu.SMEM),              # scalars for DMAs
        pl.BlockSpec((B, d), lambda j: (0, 0)),             # queries resident
        pl.BlockSpec((B, block_c), lambda j: (0, j)),       # id tiles: VMEM
        pl.BlockSpec(memory_space=pl.ANY),                  # resident KB
    ]
    staging = [pltpu.VMEM((GATHER_SLOTS, rows, d), kb.dtype)]
    if quant:
        operands.append(_pad_rows(scales.astype(jnp.float32),
                                  SCALE_ROWS * SCALE_LANES)
                        .reshape(-1, SCALE_LANES))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # resident scales
        staging.append(pltpu.VMEM((GATHER_SLOTS, SCALE_ROWS, SCALE_LANES),
                                  jnp.float32))
    out_shape, out_specs, scratch = _topk_outputs(B, k)
    return pl.pallas_call(
        functools.partial(_fused_gathered_kernel, k=k, quant=quant),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=staging + scratch
        + [pltpu.SemaphoreType.DMA((2, GATHER_SLOTS))],
        interpret=interpret,
        name="quant_fused_gathered_topk" if quant else "fused_gathered_topk",
    )(*operands)


def fused_gathered_topk_pallas(queries: jax.Array, kb: jax.Array,
                               cand: jax.Array, k: int, *,
                               block_c: int = FUSED_BLOCK_C,
                               interpret: bool = False):
    """queries (B, d) f32; kb (N, d) f32 DEVICE-RESIDENT; cand (B, C) int32
    (-1 pad) -> (scores (B, k), ids (B, k)); pad slots surface as (NEG, -1).

    C never materializes: peak candidate scratch is
    :func:`fused_scratch_bytes`. ``cand`` rides twice, tile by tile: into
    SMEM (scalar reads drive the row DMAs) and into VMEM (vectorized pad
    masking + id merge)."""
    return _fused_call(queries, kb, None, cand, k, block_c, interpret)


def quant_fused_gathered_topk_pallas(queries: jax.Array, kb_q: jax.Array,
                                     scales: jax.Array, cand: jax.Array,
                                     k: int, *, block_c: int = FUSED_BLOCK_C,
                                     interpret: bool = False):
    """queries (B, d) f32; kb_q (N, d) int8 + scales (N,) f32 both
    DEVICE-RESIDENT; cand (B, C) int32 (-1 pad) -> (scores (B, k),
    ids (B, k)); pad slots surface as (NEG, -1). Each candidate's int8 row
    group and scale tile DMA in-kernel: neither the (B, C, d) codes nor the
    (B, C) scales materialize."""
    return _fused_call(queries, kb_q, scales, cand, k, block_c, interpret)
