"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs pure-jnp
oracle (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.dense_topk import (dense_topk_pallas,
                                      fused_gathered_topk_pallas,
                                      gathered_topk_pallas,
                                      quant_fused_gathered_topk_pallas,
                                      quant_gathered_topk_pallas,
                                      quant_topk_pallas)
from repro.retrieval.backends import quantize_kb


@pytest.mark.parametrize("B,N,d,k", [
    (1, 257, 32, 1), (4, 1000, 64, 8), (8, 4096, 128, 16),
    (3, 130, 16, 4), (16, 2048, 64, 32),
])
def test_dense_topk_matches_ref(B, N, d, k):
    kq, kk = jax.random.split(jax.random.PRNGKey(B * N + k))
    q = jax.random.normal(kq, (B, d), jnp.float32)
    kb = jax.random.normal(kk, (N, d), jnp.float32)
    s_k, i_k = dense_topk_pallas(q, kb, k, interpret=True)
    s_r, i_r = ref.dense_topk_ref(q, kb, k)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_topk_dtypes(dtype):
    kq, kk = jax.random.split(jax.random.PRNGKey(7))
    q = jax.random.normal(kq, (4, 64)).astype(dtype)
    kb = jax.random.normal(kk, (512, 64)).astype(dtype)
    s_k, i_k = dense_topk_pallas(q, kb, 8, interpret=True)
    s_r, i_r = ref.dense_topk_ref(q, kb, 8)
    np.testing.assert_allclose(np.asarray(s_k, np.float32),
                               np.asarray(s_r, np.float32), atol=3e-2, rtol=3e-2)


def test_dense_topk_block_boundary_ids():
    """Ids crossing KB-tile boundaries must be globally correct."""
    d, N = 8, 700
    kb = np.zeros((N, d), np.float32)
    hot = [3, 255, 256, 511, 512, 699]
    for rank, idx in enumerate(hot):
        kb[idx, 0] = 10.0 - rank
    q = np.zeros((1, d), np.float32)
    q[0, 0] = 1.0
    s, i = dense_topk_pallas(jnp.asarray(q), jnp.asarray(kb), len(hot),
                             block_n=256, interpret=True)
    assert list(np.asarray(i[0])) == hot


# --------------------------------------------------------------------------------------
# int8 fused dequant+matmul+top-k kernels
# --------------------------------------------------------------------------------------
@pytest.mark.parametrize("B,N,d,k,block_n", [
    (1, 257, 32, 1, 1024), (4, 1000, 64, 8, 1024), (3, 130, 16, 4, 1024),
    (2, 700, 8, 6, 256),            # several KB tiles, ids cross boundaries
    (8, 2048, 64, 16, 512),
])
def test_quant_topk_matches_ref(B, N, d, k, block_n):
    kq, kk = jax.random.split(jax.random.PRNGKey(B * N + k))
    q = jax.random.normal(kq, (B, d), jnp.float32)
    codes, scales = quantize_kb(np.asarray(
        jax.random.normal(kk, (N, d), jnp.float32)))
    s_k, i_k = quant_topk_pallas(q, jnp.asarray(codes), jnp.asarray(scales),
                                 k, block_n=block_n, interpret=True)
    s_r, i_r = ref.quant_dense_topk_ref(q, jnp.asarray(codes),
                                        jnp.asarray(scales), k)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))


@pytest.mark.parametrize("B,N,C,d,k,block_c", [
    (2, 300, 64, 16, 8, 512), (3, 500, 130, 32, 5, 64),
    (1, 128, 16, 8, 16, 512),       # k > real candidates -> pad sentinels
])
def test_quant_gathered_topk_matches_ref(B, N, C, d, k, block_c):
    """ADR-probe path: gathered int8 candidates + per-candidate scales, with
    ragged candidate rows (-1 padding) and block_c crossing tile boundaries."""
    ks = jax.random.split(jax.random.PRNGKey(N + C), 3)
    q = jax.random.normal(ks[0], (B, d), jnp.float32)
    codes, scales = quantize_kb(np.asarray(
        jax.random.normal(ks[1], (N, d), jnp.float32)))
    cand = np.full((B, C), -1, np.int64)
    g = np.random.default_rng(C)
    for b in range(B):
        w = int(g.integers(1, min(C, N)))
        cand[b, :w] = np.sort(g.choice(N, size=w, replace=False))
    safe = np.maximum(cand, 0)
    cand_emb = jnp.asarray(codes[safe])
    cand_scl = jnp.asarray(scales[safe])
    cand_j = jnp.asarray(cand, jnp.int32)
    s_k, i_k = quant_gathered_topk_pallas(q, cand_emb, cand_scl, cand_j, k,
                                          block_c=block_c, interpret=True)
    s_r, i_r = ref.quant_gathered_topk_ref(q, cand_emb, cand_scl, cand_j, k)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))
    # pad slots surface the canonical sentinels
    n_real = int((cand[0] >= 0).sum())
    if k > n_real:
        assert np.all(np.asarray(i_k)[0, n_real:] == -1)


# --------------------------------------------------------------------------------------
# fused in-kernel candidate gather (fp32 + int8): the tiled DMA path
# --------------------------------------------------------------------------------------
def _ragged_cand(g, B, C, N, dup_row=None, empty_row=None):
    """Id-sorted candidate rows with -1 tail padding; optionally one row with
    a duplicated real id and one all-pad row."""
    cand = np.full((B, C), -1, np.int64)
    for b in range(B):
        if b == empty_row:
            continue
        w = int(g.integers(1, min(C, N)))
        row = np.sort(g.choice(N, size=w, replace=False))
        if b == dup_row and w >= 2:
            row[1] = row[0]
        cand[b, :w] = row
    return cand


@pytest.mark.parametrize("B,N,C,d,k,block_c", [
    (2, 500, 130, 32, 5, 128),      # C not a multiple of 128; ragged tail tile
    (3, 300, 384, 16, 8, 128),      # ids cross gather-tile boundaries, 3 tiles
    (1, 128, 16, 8, 16, 256),       # k > real candidates -> pad sentinels
])
def test_fused_gathered_topk_matches_ref(B, N, C, d, k, block_c):
    """In-kernel DMA gather (interpret) vs the streaming jnp oracle."""
    ks = jax.random.split(jax.random.PRNGKey(N + C), 2)
    q = jax.random.normal(ks[0], (B, d), jnp.float32)
    kb = jax.random.normal(ks[1], (N, d), jnp.float32)
    cand = jnp.asarray(_ragged_cand(np.random.default_rng(C), B, C, N),
                       jnp.int32)
    s_k, i_k = fused_gathered_topk_pallas(q, kb, cand, k, block_c=block_c,
                                          interpret=True)
    s_r, i_r = ref.fused_gathered_topk_ref(q, kb, cand, k, block_c=block_c)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))


def test_fused_gathered_duplicates_and_allpad_rows():
    """Duplicate candidate ids tie-break to the earlier column (both paths);
    an all-pad row comes back entirely sentinel (NEG, -1)."""
    B, N, C, d, k = 3, 200, 140, 16, 6
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(ks[0], (B, d), jnp.float32)
    kb = jax.random.normal(ks[1], (N, d), jnp.float32)
    cand = jnp.asarray(
        _ragged_cand(np.random.default_rng(9), B, C, N, dup_row=0,
                     empty_row=2), jnp.int32)
    s_k, i_k = fused_gathered_topk_pallas(q, kb, cand, k, block_c=128,
                                          interpret=True)
    s_r, i_r = ref.fused_gathered_topk_ref(q, kb, cand, k, block_c=128)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))
    assert np.all(np.asarray(i_k)[2] == -1)           # all-pad row: sentinels
    assert np.all(np.asarray(s_k)[2] < -1e37)


def test_fused_gather_byte_parity_with_pregathered():
    """Fused in-kernel gather == pre-gathered (B, C, d) kernel, bit for bit,
    fp32 and int8 — the serve-path byte-parity invariant at kernel level."""
    B, N, C, d, k = 2, 300, 260, 16, 8
    g = np.random.default_rng(3)
    kb = (g.integers(-2, 3, size=(N, d)) / 2).astype(np.float32)
    q = jnp.asarray((g.integers(-2, 3, size=(B, d)) / 2).astype(np.float32))
    cand = _ragged_cand(g, B, C, N, empty_row=1)
    cand_j = jnp.asarray(cand, jnp.int32)
    safe = np.maximum(cand, 0)

    s_f, i_f = fused_gathered_topk_pallas(q, jnp.asarray(kb), cand_j, k,
                                          block_c=128, interpret=True)
    s_p, i_p = gathered_topk_pallas(q, jnp.asarray(kb[safe]), cand_j, k,
                                    interpret=True)
    assert np.array_equal(np.asarray(s_f), np.asarray(s_p))
    assert np.array_equal(np.asarray(i_f), np.asarray(i_p))

    codes, scales = quantize_kb(kb)
    s_qf, i_qf = quant_fused_gathered_topk_pallas(
        q, jnp.asarray(codes), jnp.asarray(scales), cand_j, k, block_c=128,
        interpret=True)
    s_qp, i_qp = quant_gathered_topk_pallas(
        q, jnp.asarray(codes[safe]), jnp.asarray(scales[safe]), cand_j, k,
        interpret=True)
    assert np.array_equal(np.asarray(s_qf), np.asarray(s_qp))
    assert np.array_equal(np.asarray(i_qf), np.asarray(i_qp))


@pytest.mark.parametrize("B,N,C,d,k,block_c", [
    (2, 500, 130, 32, 5, 128),      # C not a multiple of 128
    (1, 128, 16, 8, 16, 256),       # k > real candidates -> pad sentinels
    (3, 300, 270, 16, 6, 128),      # duplicate ids + tile-crossing rows
])
def test_quant_fused_gathered_topk_matches_ref(B, N, C, d, k, block_c):
    """int8 fused gather: codes AND per-row scales DMA in-kernel (interpret)
    vs the streaming oracle."""
    ks = jax.random.split(jax.random.PRNGKey(N + C + 1), 2)
    q = jax.random.normal(ks[0], (B, d), jnp.float32)
    codes, scales = quantize_kb(np.asarray(
        jax.random.normal(ks[1], (N, d), jnp.float32)))
    cand = jnp.asarray(
        _ragged_cand(np.random.default_rng(C + 1), B, C, N,
                     dup_row=0 if B > 2 else None), jnp.int32)
    s_k, i_k = quant_fused_gathered_topk_pallas(
        q, jnp.asarray(codes), jnp.asarray(scales), cand, k,
        block_c=block_c, interpret=True)
    s_r, i_r = ref.quant_fused_gathered_topk_ref(
        q, jnp.asarray(codes), jnp.asarray(scales), cand, k, block_c=block_c)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))


def test_quant_topk_block_boundary_ids():
    """Global ids stay correct when hot docs straddle int8 KB tiles."""
    d, N = 8, 700
    emb = np.zeros((N, d), np.float32)
    hot = [3, 255, 256, 511, 512, 699]
    for rank, idx in enumerate(hot):
        emb[idx, 0] = 10.0 - rank
    emb[:, 1] = 0.01                    # keep every row's scale positive
    codes, scales = quantize_kb(emb)
    q = np.zeros((1, d), np.float32)
    q[0, 0] = 1.0
    s, i = quant_topk_pallas(jnp.asarray(q), jnp.asarray(codes),
                             jnp.asarray(scales), len(hot),
                             block_n=256, interpret=True)
    assert list(np.asarray(i[0])) == hot


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "quant"])
@pytest.mark.parametrize("N,block_n", [
    (2051, 512),        # five tiles, the last holds 3 rows
    (1500, 512),        # the last tile holds 476 of its 512 rows
    (300, 128),         # the last tile holds 44 rows
    (5, 1024),          # the smallest KB: one 128-row tile over 5 rows
])
def test_exact_scan_ragged_last_block(quant, N, block_n):
    """A KB off the tile is scanned in place: the last tile runs past N
    (interpret mode fills it with NaN, or -128 for int8 codes, whose padded
    scales are 0) and only the kernel's mask keeps those rows out. The true
    top-k sits in the last tile and straddles its start, and every other
    row scores below zero, so a tail row that got through would outrank
    them."""
    d, B = 16, 3
    tile = max(min(block_n, N), 128)
    start = (-(-N // tile) - 1) * tile
    hot = sorted({r for r in (start - 1, start, (start + N - 1) // 2, N - 1)
                  if r >= 0})
    k = min(len(hot) + 2, N)
    g = np.random.default_rng(N)
    kb = (0.1 * g.standard_normal((N, d))).astype(np.float32)
    kb[:, 0] = -1.0
    for rank, row in enumerate(hot):
        kb[row, 0] = 10.0 - rank
    q = g.standard_normal((B, d)).astype(np.float32)
    q[:, 0] = 4.0
    q = jnp.asarray(q)
    if quant:
        codes, scales = quantize_kb(kb)
        s_k, i_k = quant_topk_pallas(q, jnp.asarray(codes),
                                     jnp.asarray(scales), k, block_n=block_n,
                                     interpret=True)
        s_r, i_r = ref.quant_dense_topk_ref(q, jnp.asarray(codes),
                                            jnp.asarray(scales), k)
    else:
        s_k, i_k = dense_topk_pallas(q, jnp.asarray(kb), k, block_n=block_n,
                                     interpret=True)
        s_r, i_r = ref.dense_topk_ref(q, jnp.asarray(kb), k)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=1e-4,
                               rtol=1e-4)
    assert np.array_equal(np.asarray(i_k), np.asarray(i_r))
    assert np.all(np.asarray(i_k)[:, :len(hot)] == hot)


@pytest.mark.parametrize("B,H,KV,hd,W,cl", [
    (1, 4, 4, 32, 64, 64), (2, 8, 2, 32, 300, 123), (4, 16, 8, 64, 1024, 1000),
    (1, 8, 1, 128, 129, 57),
])
def test_decode_attention_matches_ref(B, H, KV, hd, W, cl):
    ks = jax.random.split(jax.random.PRNGKey(B + W), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, W, KV, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, W, KV, hd), jnp.float32)
    cls = jnp.asarray([cl] + [max(1, cl // 2)] * (B - 1), jnp.int32)
    o_k = decode_attention_pallas(q, kc, vc, cls, block_w=128, interpret=True)
    o_r = ref.decode_attention_ref(q, kc, vc, cls)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5,
                               rtol=2e-5)


def test_decode_attention_masks_invalid_slots():
    """Entries past cache_len must not influence the output."""
    B, H, KV, hd, W = 1, 2, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    kc = jax.random.normal(ks[1], (B, W, KV, hd))
    vc = jax.random.normal(ks[2], (B, W, KV, hd))
    cl = jnp.asarray([17], jnp.int32)
    o1 = decode_attention_pallas(q, kc, vc, cl, block_w=32, interpret=True)
    kc2 = kc.at[:, 17:].set(99.0)
    vc2 = vc.at[:, 17:].set(-99.0)
    o2 = decode_attention_pallas(q, kc2, vc2, cl, block_w=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


def test_retriever_kernel_backend_agrees_with_numpy():
    """ExactDenseRetriever('kernel') == ExactDenseRetriever('numpy')."""
    from repro.retrieval.encoder import ContextEncoder
    from repro.retrieval.kb import DenseKB
    from repro.retrieval.retrievers import ExactDenseRetriever
    from repro.training.data import synthetic_corpus
    docs = synthetic_corpus(400, 512)
    enc = ContextEncoder(512, d=32)
    kb = DenseKB.build(docs, enc)
    r_np = ExactDenseRetriever(kb, backend="numpy")
    r_kn = ExactDenseRetriever(kb, backend="kernel")
    q = enc.encode_batch([d[:10] for d in docs[:3]])
    i1, s1 = r_np.retrieve(q, 5)
    i2, s2 = r_kn.retrieve(q, 5)
    np.testing.assert_allclose(s1, s2, atol=1e-4)
    assert np.array_equal(i1, i2)


# --------------------------------------------------------------------------------------
# prefill (flash) attention kernel
# --------------------------------------------------------------------------------------
from repro.kernels.prefill_attention import prefill_attention_pallas  # noqa: E402


@pytest.mark.parametrize("B,S,H,KV,hd,causal,window,prefix", [
    (1, 128, 4, 2, 32, True, 0, 0),
    (2, 300, 4, 4, 16, True, 0, 0),
    (1, 257, 8, 2, 32, True, 64, 0),
    (1, 200, 4, 1, 32, True, 0, 37),      # prefix-LM (paligemma)
    (2, 160, 4, 2, 32, False, 0, 0),      # bidirectional (whisper encoder)
])
def test_prefill_attention_matches_ref(B, S, H, KV, hd, causal, window, prefix):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    o_k = prefill_attention_pallas(q, k, v, causal=causal, window=window,
                                   prefix_len=prefix, bq=64, bk=64,
                                   interpret=True)
    o_r = ref.prefill_attention_ref(q, k, v, causal=causal, window=window,
                                    prefix_len=prefix)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5,
                               rtol=2e-5)


def test_prefill_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32)).astype(jnp.bfloat16)
    o_k = prefill_attention_pallas(q, k, v, bq=64, bk=64, interpret=True)
    o_r = ref.prefill_attention_ref(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(o_k, np.float32), np.asarray(o_r),
                               atol=5e-2, rtol=5e-2)
