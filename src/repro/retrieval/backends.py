"""The retrieval-backend layer: one interface, three execution strategies.

Every dense retriever in this repo ultimately runs the same scan — score a
query batch against the KB embedding matrix, keep the top-k — but *where* that
scan executes is a serving-level decision, not a retriever-level one:

  * :class:`FlatBackend`    — the numpy argpartition scan (single host, BLAS).
  * :class:`KernelBackend`  — the Pallas blocked top-k (`kernels/dense_topk`,
                              interpret mode on CPU, Mosaic on TPU), with the
                              KB embeddings resident on device: uploaded once
                              at construction instead of per call.
  * :class:`ShardedBackend` — the KB sharded across a mesh
                              (`retrieval/sharded.py`): per-shard blocked
                              top-k + ONE all-gather per call, so the fleet's
                              merged verification round is a single collective
                              program however many requests participate.

Each backend offers TWO scans over the same resident KB:

  * :meth:`~DenseSearchBackend.search` — the full scan (EDR / KNN-LM): every
    KB row scored against every query.
  * :meth:`~DenseSearchBackend.search_gathered` — the masked/gathered scan
    (ADR): each query scores only ITS candidate rows, given as a fixed-shape
    padded id matrix (the IVF probe's bucket gather). Pad slots are ``-1``
    and score ``-inf``; the sharded backend scans only the candidates
    resident on each shard, so a fleet round's merged ADR probe is still ONE
    collective (centroid scoring stays host-side in the retriever).

All scans return identical ``(ids, scores)`` under the CANONICAL tie order —
score descending, then id ascending — so the serving layers can swap backends
without perturbing a single served token (tests/test_backends.py asserts
byte-identity across batch sizes, k values, tie-heavy KBs, and KB sizes that
don't divide the shard count). Backends are *pure* scans: no timing, no stats
— the `RetrieverStats` bookkeeping lives in the retriever wrapper
(`retrievers._TimedRetriever`), which consults :meth:`~DenseSearchBackend.cold_shape`
to exclude compile-polluted first calls per shape from the latency-unit
calibration.

Each of the three strategies also has an **int8 quantized** sibling holding
the KB as per-row symmetric int8 codes + fp32 scales (~4x less index memory;
:func:`quantize_kb`):

  * :class:`QuantizedFlatBackend`    (``int8``) — the numpy reference:
    chunked dequant matmul, never materializing a full fp32 KB copy.
  * :class:`QuantizedKernelBackend`  (``int8-kernel``) — the fused Pallas
    dequant+matmul+top-k (`kernels.ops.quant_dense_topk`): only int8 codes
    stream HBM -> VMEM; the cast + scale multiply happen tile-wise on chip.
  * :class:`QuantizedShardedBackend` (``int8-sharded``) — per-shard int8
    residency on the mesh; the dequant multiply rides the same single
    collective per call as the fp32 sharded scan.

Quantized backends are INEXACT: they carry ``exact = False`` and promise a
*recall contract* (recall@k >= 0.95 vs :class:`FlatBackend` across the
property-test KB grid, tests/test_quantized.py) instead of byte-parity. The
three int8 backends share ONE host-side quantization (:func:`quantize_kb`)
and the same score expression ``(q @ codes.T) * scales``, so they remain
byte-comparable with *each other* on grid-quantized inputs, and
speculate+verify through the same inexact backend still byte-matches a
sequential run on that backend (determinism, not exactness, is what the
serving layers need). Every backend reports its resident index footprint as
``kb_bytes``.

Adding a backend (multi-host, quantized index, ...) is a leaf change here plus
a name in :func:`make_backend`; no retriever or server grows a constructor
branch for it.
"""
from __future__ import annotations

import os
import sys
from typing import Optional, Protocol, Tuple, runtime_checkable

import numpy as np


def bootstrap_mesh_shards() -> None:
    """``--mesh-shards N`` needs N host-platform devices, and XLA only reads
    ``xla_force_host_platform_device_count`` before the backend initializes —
    so drivers call this to peek at argv and set the flag BEFORE anything
    imports jax. A no-op when jax is already loaded, when the operator set
    the flag themselves, or when the value isn't a plain int (argparse will
    report that properly once the driver parses for real)."""
    if "jax" in sys.modules:
        return
    n = 0
    argv = sys.argv
    for i, a in enumerate(argv):
        try:
            if a == "--mesh-shards" and i + 1 < len(argv):
                n = int(argv[i + 1])
            elif a.startswith("--mesh-shards="):
                n = int(a.split("=", 1)[1])
        except ValueError:
            return                    # malformed: leave it to argparse
    if n > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()


@runtime_checkable
class DenseSearchBackend(Protocol):
    """Pure dense top-k scan over a fixed KB embedding matrix."""

    name: str            # CLI spelling (one of BACKENDS)
    calls: int           # completed scans (sharded backends: collectives issued)
    exact: bool          # True: byte-parity with FlatBackend is contractual;
    #                      False: the bounded-recall contract applies instead
    #                      (recall@k >= 0.95 vs FlatBackend + determinism)
    kb_bytes: int        # resident index footprint (codes + scales if int8)

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """queries (B, d) float32 -> (ids (B, k) int64, scores (B, k) float32),
        rows sorted canonically: score desc, ties by id asc."""
        ...

    def search_gathered(self, queries: np.ndarray, cand: np.ndarray,
                        k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Masked/gathered scan: query b scores only the KB rows named by
        ``cand[b]`` (the IVF probe's padded bucket gather).

        ``cand`` is (B, C) int64: each row's candidate doc ids, sorted
        ascending, unique, padded with ``-1`` at the END (the retriever
        normalizes probe-order gathers into this form once — with ids in
        column order, every backend's position-stable top-k IS the canonical
        id-asc tie break). Returns ``(ids (B, k'), scores (B, k'))`` with
        ``k' = min(k, C)``, canonically ordered; slots beyond a row's real
        candidate count come back as ``(id=-1, score=-inf)``."""
        ...

    def cold_shape(self, B: int, k: int) -> bool:
        """True iff the NEXT search at this shape pays an XLA compile (and
        records the shape as seen). The compile cache lives on the backend,
        so retrievers sharing one backend agree on what is warm."""
        ...

    def cold_shape_gathered(self, B: int, C: int, k: int) -> bool:
        """`cold_shape` for the gathered scan — its compiled program is also
        shaped by the candidate width ``C``."""
        ...

    def gathered_scratch_bytes(self, B: int, C: int) -> int:
        """Peak candidate-buffer bytes ONE ``search_gathered`` call at batch B
        and candidate width C materializes — the gathered-embedding scratch,
        not the resident KB. Kernel/sharded backends route through the fused
        in-kernel gather, so this is a (B, block_c, d) tile independent of C;
        the numpy paths report their row-chunked host scratch. Benchmarks
        record it next to :meth:`pregathered_scratch_bytes` (the (B, C, d)
        tensor the pre-gathered path would build) to track the reduction."""
        ...

    def pregathered_scratch_bytes(self, B: int, C: int) -> int:
        """What a naive pre-gathered (B, C, d) candidate materialization costs
        at this backend's resident dtype (int8 backends also gather a (B, C)
        fp32 scale row). The baseline `gathered_scratch_bytes` is measured
        against."""
        ...


class _JitShapeMixin:
    """Per-(B, k) compile tracking for jit-backed scans. ``n_rows`` is the
    KB size the backend clamps k against — distinct raw k values that clamp
    to the same compiled program must share one cache entry."""

    def _init_shapes(self, n_rows: int):
        self._shapes = set()
        self._n_rows = n_rows

    def cold_shape(self, B: int, k: int) -> bool:
        key = (B, min(k, self._n_rows))
        if key in self._shapes:
            return False
        self._shapes.add(key)
        return True

    def cold_shape_gathered(self, B: int, C: int, k: int) -> bool:
        key = (B, C, min(k, C))          # 3-tuples: never collide with dense
        if key in self._shapes:
            return False
        self._shapes.add(key)
        return True


def canonical_topk(s: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k of a scored matrix ``s`` (B, N) under the canonical tie order
    (score desc, id asc) — the order ``jax.lax.top_k`` and the Pallas kernel's
    max-extraction loop both produce, so numpy results are comparable
    byte-for-byte with the accelerator backends.

    Vectorized fast path: argpartition for the top-k *set*, candidate ids
    sorted ascending, then a stable sort on score. argpartition picks
    arbitrary members among ties AT the k-th score, so rows where the
    boundary is ambiguous (more ties at the threshold than slots left) are
    re-selected exactly: all ids strictly above the threshold, then the
    lowest ids at it."""
    B, N = s.shape
    k = min(k, N)
    cand = np.argpartition(-s, kth=k - 1, axis=1)[:, :k] if k < N \
        else np.tile(np.arange(N), (B, 1))
    cand = np.sort(cand, axis=1)                      # ties resolve id-asc
    part = np.take_along_axis(s, cand, axis=1)
    thresh = part.min(axis=1)                         # k-th largest per row
    n_gt = (s > thresh[:, None]).sum(axis=1)
    ambiguous = np.nonzero((s == thresh[:, None]).sum(axis=1) > k - n_gt)[0]
    for b in ambiguous:                               # boundary ties: exact fix
        gt = np.nonzero(s[b] > thresh[b])[0]
        eq = np.nonzero(s[b] == thresh[b])[0][:k - gt.size]
        cand[b] = np.concatenate([gt, eq])
        part[b] = s[b, cand[b]]
    order = np.argsort(-part, axis=1, kind="stable")  # stable: keeps id-asc
    ids = np.take_along_axis(cand, order, axis=1).astype(np.int64)
    return ids, np.take_along_axis(part, order, axis=1).astype(np.float32)


def gathered_scores(embeddings: np.ndarray, queries: np.ndarray,
                    cand: np.ndarray) -> np.ndarray:
    """Score each query against ITS candidate rows: ``(B, C)`` float32 with
    pad slots (``cand < 0``) at ``-inf``. Row-chunked so the ``(rows, C, d)``
    gather stays ~64MB — big-KB probes would otherwise materialize GB-scale
    scratch per merged verification call. ``np.matmul`` over a stacked batch
    is per-row deterministic, so chunking cannot change a single bit."""
    B, C = cand.shape
    d = embeddings.shape[1]
    s = np.empty((B, C), np.float32)
    step = max(1, 16_000_000 // max(C * d, 1))
    for i in range(0, B, step):
        emb = embeddings[np.maximum(cand[i:i + step], 0)]
        s[i:i + step] = np.matmul(emb, queries[i:i + step, :, None])[..., 0]
    return np.where(cand >= 0, s, -np.inf)


def quantize_kb(embeddings: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantization of a KB embedding matrix:
    ``(N, d) float -> (codes (N, d) int8, scales (N,) float32)`` with
    ``scales = max(|row|) / 127`` (floored at 1e-12 so all-zero rows stay
    finite) and ``codes = clip(rint(row / scale), -127, 127)``.

    Properties the tests pin down (tests/test_quantized.py): scales are
    strictly positive; ``127 * scale`` recovers each row's max-abs to a few
    ulp; dequant error is at most ``scale / 2`` per element; identical rows
    get identical codes+scales. Every int8 backend calls THIS function, so
    the three quantized execution strategies score one and the same code
    matrix."""
    emb = np.asarray(embeddings, np.float32)
    maxabs = np.abs(emb).max(axis=1, initial=0.0)
    scales = (np.maximum(maxabs, np.float32(1e-12))
              / np.float32(127.0)).astype(np.float32)
    codes = np.clip(np.rint(emb / scales[:, None]), -127, 127).astype(np.int8)
    return codes, scales


def quant_scores(codes: np.ndarray, scales: np.ndarray,
                 queries: np.ndarray) -> np.ndarray:
    """Dequantized full scan ``(q @ codes.T) * scales`` -> (B, N) float32.
    The scale multiply lands on the score matrix (a per-row scale is constant
    along d, so ``q . (s*c) == s * (q . c)`` exactly in the reals) — the same
    operation order as the fused kernel and the sharded program. KB-row
    chunked so the fp32 cast of the codes stays ~64MB scratch instead of a
    full fp32 KB copy per call."""
    B, (N, d) = queries.shape[0], codes.shape
    s = np.empty((B, N), np.float32)
    step = max(1, 16_000_000 // max(d, 1))
    for i in range(0, N, step):
        blk = codes[i:i + step].astype(np.float32)
        s[:, i:i + step] = (queries @ blk.T) * scales[None, i:i + step]
    return s


def quant_gathered_scores(codes: np.ndarray, scales: np.ndarray,
                          queries: np.ndarray, cand: np.ndarray) -> np.ndarray:
    """:func:`gathered_scores` over an int8 KB: each query scores ITS
    candidate rows as ``(q . code) * scale``; pad slots (``cand < 0``) at
    ``-inf``. Same ~64MB row chunking as the fp32 path."""
    B, C = cand.shape
    d = codes.shape[1]
    s = np.empty((B, C), np.float32)
    step = max(1, 16_000_000 // max(C * d, 1))
    for i in range(0, B, step):
        idx = np.maximum(cand[i:i + step], 0)
        emb = codes[idx].astype(np.float32)
        s[i:i + step] = (np.matmul(emb, queries[i:i + step, :, None])[..., 0]
                         * scales[idx])
    return np.where(cand >= 0, s, -np.inf)


def _sentinels_to_contract(ids, scores) -> Tuple[np.ndarray, np.ndarray]:
    """Device gathered-scan output -> the search_gathered contract: pad slots
    carry the NEG sentinel on device (kernels/dense_topk.NEG) with id -1;
    the contract (and the numpy path) says (id=-1, score=-inf)."""
    ids = np.asarray(ids, np.int64)
    return ids, np.where(ids < 0, np.float32(-np.inf),
                         np.asarray(scores, np.float32))


class FlatBackend:
    """Single-host numpy scan: one BLAS matmul + canonical argpartition top-k."""

    name = "numpy"
    exact = True

    def __init__(self, embeddings: np.ndarray):
        self.embeddings = embeddings
        self.kb_bytes = embeddings.nbytes
        self.calls = 0

    def cold_shape(self, B: int, k: int) -> bool:
        return False                     # nothing compiles

    def cold_shape_gathered(self, B: int, C: int, k: int) -> bool:
        return False

    def gathered_scratch_bytes(self, B: int, C: int) -> int:
        # gathered_scores row-chunks the (rows, C, d) f32 gather to ~64MB
        d = self.embeddings.shape[1]
        step = max(1, 16_000_000 // max(C * d, 1))
        return min(B, step) * C * d * 4

    def pregathered_scratch_bytes(self, B: int, C: int) -> int:
        return B * C * self.embeddings.shape[1] * 4

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        s = queries @ self.embeddings.T                  # (B, N)
        self.calls += 1
        return canonical_topk(s, k)

    def search_gathered(self, queries: np.ndarray, cand: np.ndarray,
                        k: int) -> Tuple[np.ndarray, np.ndarray]:
        s = gathered_scores(self.embeddings, queries, cand)
        k2 = min(k, cand.shape[1])
        # cand columns are id-sorted with pads (-inf) last, so a stable sort
        # on score alone IS the canonical order — and pads can never displace
        # real candidates
        order = np.argsort(-s, axis=1, kind="stable")[:, :k2]
        ids = np.take_along_axis(cand, order, axis=1).astype(np.int64)
        self.calls += 1
        return ids, np.take_along_axis(s, order, axis=1).astype(np.float32)


class KernelBackend(_JitShapeMixin):
    """Pallas blocked top-k (`kernels.ops.dense_topk`): KB tiles stream
    HBM -> VMEM, the query block stays MXU-resident. The KB embedding matrix
    is put on device ONCE here — per-call uploads of a multi-GB index would
    dwarf the scan itself. The gathered (ADR) scan routes through the FUSED
    in-kernel gather (`kernels.ops.fused_gathered_topk`): each candidate's
    aligned row group DMAs from the resident KB into a small staging ring,
    so no (B, C, d) tensor materializes however wide the probe. ``force_ref=True`` swaps the kernel
    bodies for their jnp oracles (same results — the fused oracle streams the
    same tiles; wall-clock benchmarks use it off-TPU, where interpret-mode
    overhead would swamp the numbers)."""

    name = "kernel"
    exact = True

    def __init__(self, embeddings: np.ndarray, force_ref: bool = False,
                 block_c: Optional[int] = None):
        import jax

        from repro.kernels.dense_topk import FUSED_BLOCK_C
        from repro.kernels.ops import dense_topk, fused_gathered_topk
        self._fn = dense_topk
        self._fn_gathered = fused_gathered_topk
        self._force_ref = force_ref
        self._block_c = block_c or FUSED_BLOCK_C
        self._kb = jax.device_put(np.asarray(embeddings, np.float32))
        self.kb_bytes = self._kb.nbytes
        self.calls = 0
        self._init_shapes(self._kb.shape[0])

    def gathered_scratch_bytes(self, B: int, C: int) -> int:
        from repro.kernels.dense_topk import fused_scratch_bytes
        return fused_scratch_bytes(B, C, self._kb.shape[1], self._kb.dtype,
                                   self._block_c)

    def pregathered_scratch_bytes(self, B: int, C: int) -> int:
        return B * C * self._kb.shape[1] * 4

    def program_text(self, B: int, k: int, C: Optional[int] = None) -> str:
        """The lowered program ``search`` runs at batch B (with a candidate
        width C: ``search_gathered``'s) — on TPU it holds the Pallas kernel
        as a ``tpu_custom_call``, which is how a caller checks that the
        kernel itself, and not an interpreter or oracle, serves the call."""
        import jax
        import jax.numpy as jnp
        q = jax.ShapeDtypeStruct((B, self._kb.shape[1]), jnp.float32)
        if C is None:
            return self._fn.lower(q, self._kb, min(k, self._kb.shape[0]),
                                  force_ref=self._force_ref).as_text()
        cand = jax.ShapeDtypeStruct((B, C), jnp.int32)
        return self._fn_gathered.lower(q, self._kb, cand, min(k, C),
                                       block_c=self._block_c,
                                       force_ref=self._force_ref).as_text()

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp
        # same k > N clamp as the other backends: identical (B, min(k, N))
        # results everywhere, and lax.top_k never sees an oversized k
        scores, ids = self._fn(jnp.asarray(queries), self._kb,
                               min(k, self._kb.shape[0]),
                               force_ref=self._force_ref)
        self.calls += 1
        return np.asarray(ids, np.int64), np.asarray(scores, np.float32)

    def search_gathered(self, queries: np.ndarray, cand: np.ndarray,
                        k: int) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp
        scores, ids = self._fn_gathered(jnp.asarray(queries, jnp.float32),
                                        self._kb,
                                        jnp.asarray(cand, jnp.int32),
                                        min(k, cand.shape[1]),
                                        block_c=self._block_c,
                                        force_ref=self._force_ref)
        self.calls += 1
        return _sentinels_to_contract(ids, scores)


class ShardedBackend(_JitShapeMixin):
    """KB sharded over a live mesh: every ``search`` is ONE collective program
    (`sharded_dense_topk`: per-shard scan + all-gather of k candidates per
    shard + replicated global reduce). The KB is padded to a shard multiple
    and placed shard-wise at BUILD time, so per-call work is only the
    replicated query upload; padded rows score ``-inf`` and can never reach
    the global top-k. ``calls`` counts collectives issued — the fleet's
    one-merged-call-per-round invariant is asserted against it.

    The resident representation is a subclass hook (:meth:`_encode`):
    :class:`QuantizedShardedBackend` overrides it to place int8 codes +
    per-row scales shard-wise instead of the fp32 matrix — same program
    structure, same single collective."""

    name = "sharded"
    exact = True

    def __init__(self, embeddings: np.ndarray, n_shards: Optional[int] = None,
                 axis: str = "data", mesh=None,
                 block_c: Optional[int] = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.kernels.dense_topk import FUSED_BLOCK_C
        from repro.retrieval.sharded import (sharded_dense_topk,
                                             sharded_gathered_topk)
        self._block_c = block_c or FUSED_BLOCK_C
        if mesh is None:
            devs = jax.devices()
            if n_shards and n_shards > len(devs):
                raise ValueError(
                    f"{n_shards} KB shards requested but only {len(devs)} "
                    f"{devs[0].platform} device(s) are visible")
            n = n_shards or len(devs)
            mesh = jax.sharding.Mesh(np.asarray(devs[:n]), (axis,))
        self.mesh, self.axis = mesh, axis
        self.n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
        self.n_total = embeddings.shape[0]
        shard_n = -(-self.n_total // self.n_shards)
        pad = shard_n * self.n_shards - self.n_total
        matrix, scales = self._encode(embeddings)
        if pad:
            matrix = np.pad(matrix, ((0, pad), (0, 0)))
            if scales is not None:
                scales = np.pad(scales, ((0, pad),))
        # straight from host memory: each device receives only its shard
        # (a jnp array here would first land whole on the default device)
        self._kb = jax.device_put(matrix, NamedSharding(mesh, P(axis, None)))
        self._scales = None if scales is None else jax.device_put(
            scales, NamedSharding(mesh, P(axis)))
        self.kb_bytes = matrix.nbytes + (0 if scales is None else scales.nbytes)
        self.calls = 0
        self._init_shapes(self.n_total)

        import functools

        # `scales` is an ordinary jit argument: None is an empty pytree, so
        # the exact and int8 variants trace to their own programs without a
        # static flag
        @functools.partial(jax.jit, static_argnames=("k",))
        def _scan(q, kb, scales, k):
            return sharded_dense_topk(q, kb, k, self.mesh, axis=self.axis,
                                      n_total=self.n_total, scales=scales)

        @functools.partial(jax.jit, static_argnames=("k",))
        def _scan_gathered(q, kb, scales, cand, k):
            return sharded_gathered_topk(q, kb, cand, k, self.mesh,
                                         axis=self.axis, n_total=self.n_total,
                                         scales=scales,
                                         block_c=self._block_c)

        self._scan = _scan
        self._scan_gathered = _scan_gathered

    def _encode(self, embeddings: np.ndarray):
        """Resident representation: ``(matrix (N, d), per-row scales | None)``."""
        return np.asarray(embeddings, np.float32), None

    def gathered_scratch_bytes(self, B: int, C: int) -> int:
        # per-shard peak: the shard program's chunked gather holds one
        # (B, block_c, d) tile (+ a (B, block_c) scale chunk when int8)
        from repro.kernels.dense_topk import fused_block_c
        bc = fused_block_c(C, self._block_c)
        item = self._kb.dtype.itemsize
        return B * bc * (self._kb.shape[1] * item
                         + (4 if self._scales is not None else 0))

    def pregathered_scratch_bytes(self, B: int, C: int) -> int:
        item = self._kb.dtype.itemsize
        return B * C * (self._kb.shape[1] * item
                        + (4 if self._scales is not None else 0))

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        import jax
        import jax.numpy as jnp
        with jax.set_mesh(self.mesh):
            scores, gids = self._scan(jnp.asarray(queries, jnp.float32),
                                      self._kb, self._scales,
                                      min(k, self.n_total))
        self.calls += 1
        return np.asarray(gids, np.int64), np.asarray(scores, np.float32)

    def search_gathered(self, queries: np.ndarray, cand: np.ndarray,
                        k: int) -> Tuple[np.ndarray, np.ndarray]:
        import jax
        import jax.numpy as jnp
        with jax.set_mesh(self.mesh):
            scores, gids = self._scan_gathered(
                jnp.asarray(queries, jnp.float32), self._kb, self._scales,
                jnp.asarray(cand, jnp.int32), min(k, cand.shape[1]))
        self.calls += 1
        return _sentinels_to_contract(gids, scores)


class QuantizedFlatBackend:
    """Single-host numpy scan over the int8 KB: the quantized family's
    reference semantics. Scores are ``(q @ codes.T) * scales`` with the scale
    multiply on the score matrix (the kernel/sharded operation order), then
    the same canonical top-k as :class:`FlatBackend`. Inexact by contract —
    what it promises is recall@k >= 0.95 vs the fp32 scan, not byte-parity."""

    name = "int8"
    exact = False

    def __init__(self, embeddings: np.ndarray):
        self.codes, self.scales = quantize_kb(embeddings)
        self.kb_bytes = self.codes.nbytes + self.scales.nbytes
        self.calls = 0

    def cold_shape(self, B: int, k: int) -> bool:
        return False                     # nothing compiles

    def cold_shape_gathered(self, B: int, C: int, k: int) -> bool:
        return False

    def gathered_scratch_bytes(self, B: int, C: int) -> int:
        # quant_gathered_scores casts each row-chunk's codes to f32
        d = self.codes.shape[1]
        step = max(1, 16_000_000 // max(C * d, 1))
        return min(B, step) * C * d * 4

    def pregathered_scratch_bytes(self, B: int, C: int) -> int:
        return B * C * (self.codes.shape[1] + 4)    # int8 codes + f32 scales

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        s = quant_scores(self.codes, self.scales,
                         np.asarray(queries, np.float32))
        self.calls += 1
        return canonical_topk(s, k)

    def search_gathered(self, queries: np.ndarray, cand: np.ndarray,
                        k: int) -> Tuple[np.ndarray, np.ndarray]:
        s = quant_gathered_scores(self.codes, self.scales,
                                  np.asarray(queries, np.float32), cand)
        k2 = min(k, cand.shape[1])
        # same argument as FlatBackend: cand columns are id-sorted, pads
        # (-inf) last, so a stable sort on score IS the canonical order
        order = np.argsort(-s, axis=1, kind="stable")[:, :k2]
        ids = np.take_along_axis(cand, order, axis=1).astype(np.int64)
        self.calls += 1
        return ids, np.take_along_axis(s, order, axis=1).astype(np.float32)


class QuantizedKernelBackend(_JitShapeMixin):
    """The fused Pallas dequant+matmul+top-k (`kernels.ops.quant_dense_topk`
    / `quant_fused_gathered_topk`): int8 codes + fp32 row scales are put on
    device ONCE; KB tiles stream HBM -> VMEM as int8 (4x less scan traffic
    than the fp32 kernel) and the cast + scale multiply happen on chip. The
    gathered (ADR) scan uses the fused in-kernel gather — each candidate
    row's codes AND scale DMA per tile, so neither gather materializes at
    probe width. ``force_ref`` routes to the jnp oracles exactly like
    :class:`KernelBackend`."""

    name = "int8-kernel"
    exact = False

    def __init__(self, embeddings: np.ndarray, force_ref: bool = False,
                 block_c: Optional[int] = None):
        import jax

        from repro.kernels.dense_topk import FUSED_BLOCK_C
        from repro.kernels.ops import (quant_dense_topk,
                                       quant_fused_gathered_topk)
        codes, scales = quantize_kb(embeddings)
        self._fn = quant_dense_topk
        self._fn_gathered = quant_fused_gathered_topk
        self._force_ref = force_ref
        self._block_c = block_c or FUSED_BLOCK_C
        self._kb = jax.device_put(codes)
        self._kb_scales = jax.device_put(scales)
        self.kb_bytes = codes.nbytes + scales.nbytes
        self.calls = 0
        self._init_shapes(codes.shape[0])

    def gathered_scratch_bytes(self, B: int, C: int) -> int:
        from repro.kernels.dense_topk import fused_scratch_bytes
        return fused_scratch_bytes(B, C, self._kb.shape[1], self._kb.dtype,
                                   self._block_c, quant=True)

    def pregathered_scratch_bytes(self, B: int, C: int) -> int:
        return B * C * (self._kb.shape[1] + 4)

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp
        scores, ids = self._fn(jnp.asarray(queries, jnp.float32), self._kb,
                               self._kb_scales, min(k, self._kb.shape[0]),
                               force_ref=self._force_ref)
        self.calls += 1
        return np.asarray(ids, np.int64), np.asarray(scores, np.float32)

    def search_gathered(self, queries: np.ndarray, cand: np.ndarray,
                        k: int) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp
        scores, ids = self._fn_gathered(jnp.asarray(queries, jnp.float32),
                                        self._kb, self._kb_scales,
                                        jnp.asarray(cand, jnp.int32),
                                        min(k, cand.shape[1]),
                                        block_c=self._block_c,
                                        force_ref=self._force_ref)
        self.calls += 1
        return _sentinels_to_contract(ids, scores)


class QuantizedShardedBackend(ShardedBackend):
    """Per-shard int8 residency: each device holds its slice of the code
    matrix + row scales, dequantizes into its shard-local score matrix, and
    the program is otherwise the fp32 sharded scan — per-shard top-k, ONE
    all-gather per call, replicated reduce. The fleet's merged verification
    (and ADR's merged probe) through an int8 mesh is still exactly one
    collective per round; ``calls`` keeps counting collectives."""

    name = "int8-sharded"
    exact = False

    def _encode(self, embeddings: np.ndarray):
        return quantize_kb(embeddings)


BACKENDS = ("numpy", "kernel", "sharded", "int8", "int8-kernel",
            "int8-sharded")


def make_backend(name: str, embeddings: np.ndarray, *,
                 n_shards: Optional[int] = None, mesh=None,
                 force_ref: bool = False,
                 block_c: Optional[int] = None) -> DenseSearchBackend:
    """CLI-name -> backend instance (the one constructor branch in the repo).

    ``n_shards``/``mesh`` configure the sharded backends (default: one
    shard per visible device); ``force_ref`` routes the kernel backends
    through the jnp oracle instead of the Pallas body; ``block_c`` overrides
    the fused-gather tile width (kernel/sharded families; default
    `kernels.dense_topk.FUSED_BLOCK_C`)."""
    if name == "numpy":
        return FlatBackend(embeddings)
    if name == "kernel":
        return KernelBackend(embeddings, force_ref=force_ref, block_c=block_c)
    if name == "sharded":
        return ShardedBackend(embeddings, n_shards=n_shards, mesh=mesh,
                              block_c=block_c)
    if name == "int8":
        return QuantizedFlatBackend(embeddings)
    if name == "int8-kernel":
        return QuantizedKernelBackend(embeddings, force_ref=force_ref,
                                      block_c=block_c)
    if name == "int8-sharded":
        return QuantizedShardedBackend(embeddings, n_shards=n_shards,
                                       mesh=mesh, block_c=block_c)
    raise KeyError(f"unknown retrieval backend {name!r}; known: {BACKENDS}")
