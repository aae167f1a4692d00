"""Shared benchmark infrastructure.

Corpus/KB/datastore builders are disk-cached (.bench_cache/) so the six paper-table
benchmarks share one corpus build. Sizes are chosen so the retriever-vs-LM latency
*ratios* land in the paper's regimes on CPU:

  EDR — flat scan over a large embedding matrix (memory-bound stream) >= one LM
        generation stride  -> big speed-up headroom (paper: 1.75-2.39x),
  ADR — IVF probe ~ small fraction of a stride -> fixed s=3 can regress, OS3 rescues
        (paper: 0.58-1.39x),
  SR  — BM25 over term arrays, between the two (paper: 0.97-1.77x).
"""
from __future__ import annotations

import json
import os
import pickle
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import RaLMConfig, get_config, reduced  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.retrieval.encoder import ContextEncoder  # noqa: E402
from repro.retrieval.kb import DenseKB, SparseKB, build_knn_datastore  # noqa: E402
from repro.retrieval.retrievers import (BM25Retriever, ExactDenseRetriever,  # noqa: E402
                                        IVFRetriever)
from repro.serving.engine import ServeEngine  # noqa: E402
from repro.training.data import make_queries, synthetic_corpus  # noqa: E402

CACHE = os.path.join(os.path.dirname(__file__), "..", ".bench_cache")
ENC_DIM = 512   # 400k x 512 f32 -> ~800MB stream per exact-dense call
N_DOCS_DENSE = 400_000
N_DOCS_SPARSE = 30_000
KNN_ENTRIES = 1_000_000
KNN_DIM = 128
VOCAB = 50257   # gpt2-medium class host LM

# every benchmark imports this module: one persistent compile cache for all
enable_compile_cache()


def _cached(name, builder):
    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, name + ".pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    obj = builder()
    with open(path, "wb") as f:
        pickle.dump(obj, f)
    return obj


def host_lm(seed: int = 0):
    cfg = reduced(get_config("ralm-gpt2-medium"), layers=2, d_model=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def dense_stack():
    def build():
        docs = synthetic_corpus(N_DOCS_DENSE, VOCAB)
        enc = ContextEncoder(VOCAB, d=ENC_DIM)
        emb = np.stack([enc.encode_doc(d) for d in docs])
        return docs, emb
    docs, emb = _cached(f"dense_{N_DOCS_DENSE}_{ENC_DIM}", build)
    enc = ContextEncoder(VOCAB, d=ENC_DIM)
    return docs, enc, DenseKB(embeddings=emb, docs=docs)


def sparse_stack():
    def build():
        docs = synthetic_corpus(N_DOCS_SPARSE, VOCAB, seed=9)
        kb = SparseKB.build(docs)
        return docs, kb
    docs, kb = _cached(f"sparse_{N_DOCS_SPARSE}", build)
    return docs, ContextEncoder(VOCAB, d=ENC_DIM), kb


def knn_stack():
    def build():
        docs = synthetic_corpus(KNN_ENTRIES // 40, VOCAB, seed=21)
        stream = np.concatenate([np.asarray(d, np.int32) for d in docs])
        enc = ContextEncoder(VOCAB, d=KNN_DIM, window=16)
        ds = build_knn_datastore(stream, enc, context=16, limit=KNN_ENTRIES)
        return stream, ds
    stream, ds = _cached(f"knn_{KNN_ENTRIES}_{KNN_DIM}", build)
    return stream, ContextEncoder(VOCAB, d=KNN_DIM, window=16), ds


def make_retriever(name: str):
    if name == "edr":
        docs, enc, kb = dense_stack()
        return docs, enc, ExactDenseRetriever(kb)
    if name == "adr":
        docs, enc, kb = dense_stack()
        return docs, enc, _cached_ivf(kb, docs)
    if name == "sr":
        docs, enc, kb = sparse_stack()
        return docs, enc, BM25Retriever(kb)
    raise KeyError(name)


def _cached_ivf(kb, docs):
    def build():
        r = IVFRetriever(kb, n_clusters=256, nprobe=2, iters=4)
        return r.centroids, r.buckets
    cents, buckets = _cached(f"ivf_{kb.size}", build)
    r = IVFRetriever.__new__(IVFRetriever)
    r.kb = kb
    r.nprobe = 2
    r.centroids = cents
    r.buckets = buckets
    from repro.retrieval.retrievers import RetrieverStats
    r.stats = RetrieverStats("linear_intercept")
    return r


def bench_prompts(docs, n: int, seed: int = 3):
    # exactly 48 tokens: prompts must sit on the warmed jit shape grid (a single
    # off-grid prompt charges an XLA compile to whichever server runs first)
    return [(q * 32)[:48] for q in make_queries(docs, n, seed=seed)]


def warm_engine(eng, rcfg, prompt_len: int = 48, chunk_len: int = 64) -> None:
    """Compile every prefill shape the serving grid can hit (doc chunk + prompt +
    i*generation_stride, plus the doc-less initial prefill)."""
    grid = [prompt_len + i * rcfg.generation_stride
            for i in range(rcfg.max_new_tokens // rcfg.generation_stride + 1)]
    eng.warm(grid + [chunk_len + g for g in grid])


def run_requests(server, prompts, warmup: int = 1):
    """-> dict of aggregate latencies. Warmup request amortizes jit compiles."""
    warm_engine(server.engine, server.rcfg)
    for p in prompts[:warmup]:
        server.serve(p)
    agg = dict(wall=0.0, analytic=0.0, gen=0.0, retr=0.0, kb_calls=0,
               kb_queries=0, mismatches=0, rounds=0, tokens=[])
    for p in prompts:
        r = server.serve(p)
        agg["wall"] += r.wall_time
        agg["analytic"] += r.analytic_time
        agg["gen"] += r.gen_time
        agg["retr"] += r.retrieval_time
        agg["kb_calls"] += r.kb_calls
        agg["kb_queries"] += r.kb_queries
        agg["mismatches"] += r.mismatches
        agg["rounds"] += r.rounds
        agg["tokens"].append(tuple(r.tokens))
    agg["n"] = len(prompts)
    return agg


def measure_wall(fn, *, repeats: int = 3, warmup: int = 1):
    """Monotonic-clock wall timing with warmup discard: runs ``fn`` ``warmup``
    times untimed (jit compiles, cache fills), then ``repeats`` timed times,
    and returns ``(median_seconds, samples, last_result)``. The median over
    repeats is the committed number everywhere a BENCH_*.json reports wall
    time — single-shot walls on a shared 1-core container are too noisy to
    gate on."""
    for _ in range(max(0, warmup)):
        fn()
    samples = []
    result = None
    for _ in range(max(1, repeats)):
        t0 = time.monotonic()
        result = fn()
        samples.append(time.monotonic() - t0)
    return float(np.median(samples)), samples, result


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


def add_tiny_arg(ap) -> None:
    """Shared smoke-test flag: ``--tiny`` shrinks the module-level corpus /
    datastore sizes so every bench runs end to end in seconds (the CI
    bench-smoke job). Numbers from a tiny run are NOT paper-comparable —
    it exists to keep the BENCH_*.json producers from silently rotting."""
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test sizes: tiny shared corpora/stacks "
                         "(schema checks only; timings not comparable)")


def apply_tiny(args) -> None:
    """Apply ``--tiny`` by rebinding the stack-size globals the builders read
    at call time (cache keys include the sizes, so tiny and full stacks never
    collide in .bench_cache)."""
    global N_DOCS_DENSE, N_DOCS_SPARSE, KNN_ENTRIES, KNN_DIM, ENC_DIM
    if getattr(args, "tiny", False):
        N_DOCS_DENSE, N_DOCS_SPARSE = 1500, 600
        KNN_ENTRIES, KNN_DIM, ENC_DIM = 3000, 32, 64


def rows_to_json(rows) -> list:
    """csv_row strings -> JSON row dicts (name, us_per_call, derived)."""
    out = []
    for r in rows:
        name, us, derived = r.split(",", 2)
        out.append(dict(name=name, us_per_call=float(us), derived=derived))
    return out


def add_json_arg(ap) -> None:
    """Shared machine-readable-output flag: ``--json`` writes the benchmark's
    results to ``BENCH_<name>.json`` at the repo root (or to an explicit
    ``--json PATH``), so successive PRs can track the perf trajectory."""
    ap.add_argument("--json", nargs="?", const="", default=None, metavar="PATH",
                    help="write machine-readable results (default path: "
                         "BENCH_<bench>.json at the repo root)")


def write_json(bench: str, payload: dict, path: str = "") -> str:
    """Emit ``payload`` (plus the bench name) as stable, sorted JSON."""
    out = path or os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", f"BENCH_{bench}.json"))
    with open(out, "w") as f:
        json.dump({"bench": bench, **payload}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {out}")
    return out


def variant_rcfg(variant: str, **kw) -> RaLMConfig:
    base = dict(max_new_tokens=48, speculation_stride=3, generation_stride=4)
    base.update(kw)
    return RaLMConfig(
        prefetch_top_k=20 if "p" in variant else 1,
        use_os3="s" in variant,
        async_verification="a" in variant,
        **base,
    )


def speedup_pair(base, agg) -> str:
    """Both timelines, each self-consistent: wall vs wall (this 1-core container)
    and modeled vs modeled (paper-hardware batched-retrieval shape, §A.1)."""
    w = base["wall"] / max(agg["wall"], 1e-9)
    m = base["analytic"] / max(agg["analytic"], 1e-9)
    return f"wall={w:.2f}x modeled={m:.2f}x"
