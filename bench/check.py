"""The comparison that decides ``correct``: the served path against the
plain reference, at the timed sizes, once the window has closed.

Two numbers, each with its own limit (the configuration's ``limits``):

  * ``lm_gap``: over a seeded sample of finished requests (the longest
    among them), the widest gap by which a served token's score lies below
    the reference's best at that position. The reference re-derives the
    whole served path independently: it encodes each context itself, finds
    the KB's best rows by an exact float32 scan, and runs the plain decoder
    over the prompt and the served tokens. For iterative RaLM the score is
    the logit, with the top-1 passage (first ``chunk_len`` tokens) retrieved
    at every ``generation_stride`` boundary prepended, as the sequential
    baseline does; for KNN-LM it is the log-probability of the kNN
    interpolation. Where the reference's choice of rows is a near tie
    (within ``tie_eps``), each alternative is scored and the closest counts.
  * ``kb_gap``: over a seeded sample of the window's merged KB calls, the
    widest gap between the scores the program returned, the true scores of
    the ids it returned, and the exact scan's top-k scores, rank by rank.

The reference computes at the precision the configuration states for each
phase of the served path: the rows the engine prefills at
``other_matmul_precision``, the rows it decodes at
``decode_matmul_precision``, the KB scan in float32 at "highest".

``controls`` re-derives the same numbers with the reference itself put in
the program's place at a lower precision (every row and the scan): at every
position the token the lower precision puts first is read against the
reference."""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import stack as stack_mod
from bench import traffic

_PREC = {"highest": jax.lax.Precision.HIGHEST, "high": jax.lax.Precision.HIGH,
         "default": jax.lax.Precision.DEFAULT,
         "bfloat16": jax.lax.Precision.DEFAULT}


@partial(jax.jit, static_argnames=("k", "precision"))
def scan_topk(q, keys, k: int, precision: str = "highest"):
    """Exact inner-product top-k of queries q (B, d) over keys (N, d)."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    s = jnp.einsum("bd,nd->bn", q.astype(dt), keys.astype(dt),
                   precision=_PREC[precision],
                   preferred_element_type=jnp.float32)
    return jax.lax.top_k(s, k)


def topk(q: np.ndarray, keys_dev, k: int, precision: str, block: int = 32):
    """Blocked, shape-padded scan: (scores (B, k), ids (B, k)) on the host."""
    ss, ii = [], []
    for i in range(0, len(q), block):
        qb = q[i:i + block]
        pad = np.zeros((block, q.shape[1]), np.float32)
        pad[:len(qb)] = qb
        s, ids = scan_topk(jnp.asarray(pad), keys_dev, k, precision)
        ss.append(np.asarray(s)[:len(qb)])
        ii.append(np.asarray(ids)[:len(qb)])
    return np.concatenate(ss), np.concatenate(ii)


def sample(served: Sequence[tuple], seed: int, min_tokens: int,
           max_requests: int) -> list:
    """Indices of a seeded sample of finished requests, the longest first,
    until it holds ``min_tokens`` served tokens."""
    lens = [len(t) for _, t in served]
    longest = int(np.argmax(lens))
    picked, total = [longest], lens[longest]
    for i in traffic.rng(seed, "check").permutation(len(served)):
        if total >= min_tokens or len(picked) >= max_requests:
            break
        if int(i) != longest and lens[i]:
            picked.append(int(i))
            total += lens[i]
    return picked


def max_context(cfg: dict, mix: dict) -> int:
    """Longest sequence the reference runs for this cell, padded to 64."""
    n = max(mix["prompt"]["lengths"]) + traffic.max_output(mix)
    if cfg["workload"] == "ralm":
        n += cfg["serving"]["chunk_len"]
    return 64 * -(-n // 64)


def stated_precision(cfg: dict) -> tuple:
    """(prefilled rows, decoded rows): the matmul precisions the
    configuration states for the served path."""
    return (cfg["other_matmul_precision"], cfg["decode_matmul_precision"])


def _forward_rows(sysm, seqs, splits, rows, precision: tuple,
                  batch: int = 8):
    """Logits (len(rows[i]), V) of each token sequence at the given
    positions, from the plain decoder; rows before ``splits[i]`` are the
    ones the engine prefills. Sequences are padded at the end to one length
    per cell (a causal model's earlier positions do not see the padding),
    so each precision compiles once."""
    ref = stack_mod.reference(sysm.cfg)
    mc = ref.model_cfg(sysm.cfg)
    S = max_context(sysm.cfg, sysm.mix)
    R = (sysm.cfg["serving"]["generation_stride"]
         if sysm.cfg["workload"] == "ralm" else traffic.max_output(sysm.mix))
    out = []
    for i in range(0, len(seqs), batch):
        sb, rb = seqs[i:i + batch], rows[i:i + batch]
        toks = np.ones((batch, S), np.int32)
        pos = np.zeros((batch, R), np.int32)
        split = np.zeros((batch,), np.int32)
        split[:len(sb)] = splits[i:i + batch]
        for j, (s, r) in enumerate(zip(sb, rb)):
            toks[j, :len(s)] = s
            pos[j, :len(r)] = r
        h = ref.hidden(sysm.params, jnp.asarray(toks), jnp.asarray(split),
                       mc, precision)
        hs = jnp.take_along_axis(h, jnp.asarray(pos)[..., None], axis=1)
        lg = np.asarray(ref.logits_at(sysm.params, hs,
                                      pos < split[:, None], precision))
        out += [lg[j, :len(r)] for j, r in enumerate(rb)]
    return out


def _alternatives(scores: np.ndarray, ids: np.ndarray, k: int,
                  eps: float) -> list:
    """Row sets the exact scan cannot tell apart: the top k, and where the
    k-th and (k+1)-th scores tie within ``eps``, the set with the (k+1)-th
    in place of the k-th."""
    alts = [(ids[:k], scores[:k])]
    if len(scores) > k and scores[k - 1] - scores[k] <= eps:
        i2, s2 = ids[:k].copy(), scores[:k].copy()
        i2[k - 1], s2[k - 1] = ids[k], scores[k]
        alts.append((i2, s2))
    return alts


def _ralm_scores(sysm, picked_served, precision: tuple, scan: str):
    """Per position: list over row-set alternatives of logit rows. A stride
    counts as re-prefilled up to its first new token (a doc swap)."""
    cfg, corpus = sysm.cfg, sysm.corpus
    ref = stack_mod.reference(cfg)
    g, chunk = cfg["serving"]["generation_stride"], cfg["serving"]["chunk_len"]
    kb, eps = cfg["kb"], cfg["check"]["tie_eps"]
    strides = []
    for prompt, toks in picked_served:
        for j in range(-(-len(toks) // g)):
            ctx = list(prompt) + list(toks[:g * j])
            strides.append((prompt, toks, j,
                            ref.encode(ctx, corpus.table, kb["encoder_window"],
                                       kb["encoder_decay"])))
    sc, ids = topk(np.stack([s[3] for s in strides]), corpus.keys_on_device(),
                   2, scan)
    seqs, splits, rows, owner = [], [], [], []
    for n, (prompt, toks, j, _) in enumerate(strides):
        nxt = toks[g * j:g * j + g]
        for did, _ in _alternatives(sc[n], ids[n], 1, eps):
            doc = list(corpus.passages[int(did[0])][:chunk])
            doc += [1] * (chunk - len(doc))
            seq = doc + list(prompt) + list(toks[:g * j + len(nxt)])
            base = chunk + len(prompt) + g * j - 1
            seqs.append(seq)
            splits.append(base + 1)
            rows.append([base + i for i in range(len(nxt))])
            owner.append(n)
    lg = _forward_rows(sysm, seqs, splits, rows, precision)
    per_pos = []
    for n, (prompt, toks, j, _) in enumerate(strides):
        alts = [lg[m] for m in range(len(owner)) if owner[m] == n]
        for i, tok in enumerate(toks[g * j:g * j + g]):
            per_pos.append((int(tok), [a[i].astype(np.float64) for a in alts]))
    return per_pos


def _knnlm_scores(sysm, picked_served, precision: tuple, scan: str):
    """Per position: list over neighbour-set alternatives of the
    interpolated log-probabilities."""
    cfg, corpus = sysm.cfg, sysm.corpus
    ref = stack_mod.reference(cfg)
    kb, srv, eps = cfg["kb"], cfg["serving"], cfg["check"]["tie_eps"]
    k, lam, beta = srv["knn_k"], srv["knn_lambda"], srv["knn_beta"]
    seqs = [list(p) + list(t) for p, t in picked_served]
    rows = [[len(p) - 1 + i for i in range(len(t))] for p, t in picked_served]
    lms = _forward_rows(sysm, seqs, [len(p) for p, _ in picked_served], rows,
                        precision)
    qs = [ref.encode(list(p) + list(t[:i]), corpus.table,
                     kb["encoder_window"], kb["encoder_decay"])
          for p, t in picked_served for i in range(len(t))]
    sc, ids = topk(np.stack(qs), corpus.keys_on_device(), k + 1, scan)
    per_pos, n = [], 0
    for (p, t), lm in zip(picked_served, lms):
        for i, tok in enumerate(t):
            x = lm[i].astype(np.float64)
            p_lm = np.exp(x - x.max())
            p_lm /= p_lm.sum()
            alts = []
            for a_ids, a_sc in _alternatives(sc[n], ids[n], k, eps):
                w = np.exp(beta * (a_sc.astype(np.float64) - a_sc.max()))
                p_knn = np.zeros_like(p_lm)
                np.add.at(p_knn, corpus.values[a_ids], w / w.sum())
                alts.append(np.log((1 - lam) * p_lm + lam * p_knn))
            per_pos.append((int(tok), alts))
            n += 1
    return per_pos


SCORERS = {"ralm": _ralm_scores, "knnlm": _knnlm_scores}


def lm_gaps(sysm, picked_served, controls: Sequence[str] = ()) -> Dict[str, float]:
    """``{"program": gap, <control precision>: gap, ...}``."""
    scorer = SCORERS[sysm.cfg["workload"]]
    ref = scorer(sysm, picked_served, stated_precision(sysm.cfg), "highest")
    out = {"program": max(min(a.max() - a[tok] for a in alts)
                          for tok, alts in ref)}
    for prec in controls:
        ctl = scorer(sysm, picked_served, (prec, prec), prec)
        out[prec] = max(min(a.max() - a[int(np.argmax(c[0]))] for a in alts)
                        for (_, alts), (_, c) in zip(ref, ctl))
    return {k: float(v) for k, v in out.items()}


def kb_gaps(sysm, records, controls: Sequence[str] = ()) -> Dict[str, float]:
    """``{"program": gap, <control precision>: gap, ...}`` over the sampled
    KB calls (queries, ids, scores, k)."""
    keys, keys_dev = sysm.corpus.keys, sysm.corpus.keys_on_device()
    out = {"program": 0.0, **{c: 0.0 for c in controls}}
    for q, ids, scores, k in records:
        k = ids.shape[1]
        s_ref, _ = topk(q, keys_dev, k, "highest")

        def gap(i, s):
            true = np.einsum("bkd,bd->bk", keys[i].astype(np.float64),
                             q.astype(np.float64))
            return max(np.abs(s - s_ref).max(), np.abs(true - s_ref).max())
        out["program"] = max(out["program"], gap(ids, scores))
        for c in controls:
            s_c, i_c = topk(q, keys_dev, k, c)
            out[c] = max(out[c], gap(i_c, s_c))
    return {k: float(v) for k, v in out.items()}


def numbers(sysm, served, kb_records, seed: int,
            controls: Sequence[str] = ()) -> Dict[str, Dict[str, float]]:
    """Every compared number, for the program and each control."""
    chk = sysm.cfg["check"]
    picked = sample(served, seed, chk["min_tokens"], chk["max_requests"])
    lm = lm_gaps(sysm, [served[i] for i in picked], controls)
    kb = kb_gaps(sysm, kb_records, controls)
    return {who: {"lm_gap": lm[who], "kb_gap": kb[who]} for who in lm}


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(values[n]) and values[n] <= limits[n]
               for n in limits)
