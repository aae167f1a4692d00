"""End-to-end RaLM serving driver (the paper's workload).

    PYTHONPATH=src python -m repro.launch.serve --retriever edr --mode both \
        --requests 5 --variant psa

Builds the synthetic Wikipedia-like corpus, the chosen retriever, a host LM
(``--arch``, reduced to 2 layers unless ``--full-width`` serves the published
config), and serves QA-style requests with RaLMSeq (baseline) and/or RaLMSpec,
printing the paper-style G/R latency decomposition and the speed-up ratio. With
``--mode both`` the run exits non-zero when the two disagree, and a request
degraded with no injected faults does too.

``--concurrency N`` (N > 1) switches the speculative path to the fleet: a
BatchedServeEngine with N slots and a FleetServer that serves requests in groups
of N, merging every slot's verification queries into one batched KB call per
round (cross-request batched verification). Outputs stay identical to the
sequential baseline; the driver checks this when --mode both.

``--scheduler continuous`` serves through ContinuousFleetServer instead of
fixed groups: requests sit on an arrival timeline and are admitted into engine
slots the moment slots free up mid-flight (continuous batching). Arrivals are
Poisson at ``--arrival-rate`` requests per modeled second (0 = everything
arrives at t=0, the saturated regime) or trace-driven via ``--arrival-trace
"0,0.5,1.2,..."``. ``--num-requests`` sets the request count (alias:
``--requests``). Example:

    PYTHONPATH=src python -m repro.launch.serve --scheduler continuous \
        --concurrency 4 --num-requests 12 --arrival-rate 2

``--async-fleet`` pipelines the fleet rounds (either scheduler): the merged
verification KB call runs on a worker thread while the fleet speculates the
next lockstep stride, with per-slot carry/invalidation — the paper's +A,
fleet-wide. A variant containing 'a' implies it.

Fault tolerance (fleet paths): ``--retry-max`` / ``--retry-backoff`` /
``--retrieval-timeout`` configure the retry-with-backoff + per-call-deadline
shell around the merged verification KB call (retried calls return
byte-identical rows — KB search is deterministic — so recovery preserves
outputs); ``--inject-faults 'p_error=0.2,p_spike=0.1,spike_s=0.05,seed=3'``
wraps the retriever's KB path in the seeded chaos harness
(repro.retrieval.faults); ``--max-queue-depth`` / ``--queue-deadline`` bound
the continuous scheduler's admission queue, shedding overflow/expired
requests with a ``shed`` status instead of queueing unboundedly:

    PYTHONPATH=src python -m repro.launch.serve --mode spec --concurrency 2 \
        --requests 4 --inject-faults p_error=0.2,seed=3 --retry-max 3

``--retriever-backend {numpy,kernel,sharded,int8,int8-kernel,int8-sharded}``
picks the dense retrievers' execution backend (`repro.retrieval.backends`):
the flat numpy scan, the Pallas blocked top-k (`kernels/dense_topk`,
compiled by Mosaic on TPU, interpret mode on CPU; KB resident on device), the
mesh-sharded scan (`retrieval/sharded.py`) where every merged verification
round is ONE collective over the KB shards — or their int8 quantized
siblings, which hold the KB as per-row symmetric int8 codes + fp32 scales
(~4x less index memory; INEXACT: a tested recall@k >= 0.95 contract instead
of byte-parity, see docs/architecture.md). EDR delegates its full scan
(``search``); ADR delegates its IVF bucket scan (``search_gathered`` —
centroid scoring stays host-side, so the merged ADR probe is still one
collective on the sharded backends, fp32 and int8 alike). SR has a single
execution strategy (see ``BACKEND_SUPPORT``). ``--mesh-shards N`` sets the
shard count, one shard per device: asking for more shards than there are
devices is an error. On a CPU host it forces an N-device host platform
(XLA_FLAGS, applied below before jax initializes), simulating the multi-chip
layout the sharded backends target:

    PYTHONPATH=src python -m repro.launch.serve --concurrency 4 \
        --retriever-backend sharded --mesh-shards 4 --requests 4

    PYTHONPATH=src python -m repro.launch.serve --retriever adr \
        --retriever-backend sharded --mesh-shards 4 --concurrency 4 --requests 4

    PYTHONPATH=src python -m repro.launch.serve --concurrency 4 \
        --retriever-backend int8-sharded --mesh-shards 4 --requests 4
"""
from __future__ import annotations

# --mesh-shards N must force the N-device host platform BEFORE jax loads;
# repro.retrieval.backends is jax-free at import time, so this is safe here
from repro.retrieval.backends import BACKENDS, bootstrap_mesh_shards

bootstrap_mesh_shards()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import RaLMConfig, get_config, reduced
from repro.core.cache import SharedRetrievalCache
from repro.core.knnlm import KNNLMSeq, KNNLMSpec
from repro.core.ralmspec import RaLMSeq, RaLMSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.retrieval.encoder import ContextEncoder
from repro.retrieval.faults import inject_faults, parse_fault_spec
from repro.retrieval.kb import DenseKB, SparseKB, build_knn_datastore
from repro.retrieval.retrievers import (BM25Retriever, ExactDenseRetriever,
                                        IVFRetriever)
from repro.serving.batched import BatchedServeEngine
from repro.serving.continuous import ContinuousFleetServer, as_requests
from repro.serving.engine import ServeEngine
from repro.serving.fleet import FleetServer
from repro.serving.workload import Workload, default_workload
from repro.training.data import make_queries, synthetic_corpus

WORKLOADS = ("ralm", "knnlm")
SCHEDULERS = ("seq", "single", "fixed", "continuous")

# The ONE capability table the CLI validation, the drivers, the benchmarks and
# the docs all mean: (workload, retriever) -> supported execution backends.
# Every listed cell runs under every scheduler in SCHEDULERS. EDR delegates
# its full scan and ADR its IVF bucket scan to `repro.retrieval.backends`
# (fp32 and int8 quantized strategies alike); SR's BM25 term scan has a
# single (numpy) execution strategy. KNN-LM has no SR cell: its datastore
# must carry per-entry next-token values, which a BM25 SparseKB does not.
CAPABILITIES = {
    ("ralm", "edr"): BACKENDS,
    ("ralm", "adr"): BACKENDS,
    ("ralm", "sr"): ("numpy",),
    ("knnlm", "edr"): BACKENDS,
    ("knnlm", "adr"): BACKENDS,
}

# per-retriever view of the table under the default (ralm) workload — kept
# because docs/tests reference backend support by retriever alone
BACKEND_SUPPORT = {r: CAPABILITIES[("ralm", r)] for r in ("edr", "adr", "sr")}


def validate_stack(workload: str, retriever: str, backend: str = "numpy",
                   scheduler: str = "fixed") -> None:
    """THE error path for serving-stack capability: every rejection —
    unknown workload/scheduler, workload x retriever, retriever x backend —
    raises ValueError here, naming the valid set. ``build_stack`` calls it
    before building anything; the CLI maps the message to ``argparse.error``."""
    if workload not in WORKLOADS:
        raise ValueError(f"unknown workload {workload!r} "
                         f"(supported: {', '.join(WORKLOADS)})")
    if scheduler not in SCHEDULERS:
        raise ValueError(f"unknown scheduler {scheduler!r} "
                         f"(supported: {', '.join(SCHEDULERS)})")
    if (workload, retriever) not in CAPABILITIES:
        sup = [r for (w, r) in CAPABILITIES if w == workload]
        raise ValueError(
            f"workload {workload!r} does not support retriever {retriever!r} "
            f"(supported: {', '.join(sup)})")
    sup = CAPABILITIES[(workload, retriever)]
    if backend not in sup:
        raise ValueError(
            f"retriever {retriever!r} does not support backend {backend!r} "
            f"(supported: {', '.join(sup)})")


@dataclasses.dataclass
class ServeStack:
    """Everything the serving drivers and benchmarks need, by name — the
    typed return of :func:`build_stack` (replacing the old positional
    6-tuple) and the one argument :func:`make_server` takes."""

    cfg: object
    model: object
    params: object
    docs: list
    encoder: ContextEncoder
    retriever: object
    rcfg: RaLMConfig
    workload: Workload
    retriever_kind: str = "edr"        # capability-table key ("edr"/"adr"/"sr")
    backend: str = "numpy"             # retrieval execution backend
    shared_cache: object = None        # optional SharedRetrievalCache tier
    stream: object = None              # KNN-LM token stream (None for ralm)
    engine: object = None              # cached by make_server; pass your own
                                       # to share one across servers


def build_stack(retriever: str, *, n_docs: int = 20000, arch: str = "ralm-gpt2-medium",
                backend: str = "numpy", mesh_shards: int = 0, seed: int = 0,
                enc_dim: int = 64, d_model: int = 256, workload: str = "ralm",
                rcfg: RaLMConfig = None, shared_cache=None,
                knn_entries: int = 20000,
                full_width: bool = False) -> ServeStack:
    """Model + corpus + retriever + workload for the serving drivers and
    benchmarks, validated against the capability table and returned as a
    :class:`ServeStack`. ``backend`` picks the dense retrievers' execution
    backend (`repro.retrieval.backends.BACKENDS`, fp32 or int8 quantized —
    EDR's full scan and ADR's IVF bucket scan alike); ``mesh_shards`` caps
    the sharded backends' shard count (0 = one shard per visible device);
    ``enc_dim``/``d_model`` let benchmarks tune the retrieval-vs-LM cost
    ratio (bench_async_fleet needs retrieval-heavy EDR). The LM is ``arch``
    reduced to 2 layers of width ``d_model``, or with ``full_width`` the
    registry config as published (float32 weights from ``seed``).

    With ``workload='knnlm'`` the KB is a (context -> next token) datastore
    over the corpus token stream (``knn_entries`` caps its size; the stream
    is returned on the stack for prompt construction) and the retriever runs
    over the datastore embeddings — same EDR/ADR/backends, different rows."""
    validate_stack(workload, retriever, backend)
    if rcfg is None:
        rcfg = RaLMConfig(knnlm=(workload == "knnlm"))
    else:
        rcfg = dataclasses.replace(rcfg, knnlm=(workload == "knnlm"))
    cfg = get_config(arch)
    if not full_width:
        cfg = reduced(cfg, layers=2, d_model=d_model)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    docs = synthetic_corpus(n_docs, cfg.vocab_size)
    stream = None
    if workload == "knnlm":
        stream = np.concatenate([np.asarray(d, np.int32) for d in docs])
        enc = ContextEncoder(cfg.vocab_size, d=enc_dim, window=16)
        kb = build_knn_datastore(stream, enc, context=16, limit=knn_entries)
    else:
        enc = ContextEncoder(cfg.vocab_size, d=enc_dim)
        kb = (SparseKB.build(docs) if retriever == "sr"
              else DenseKB.build(docs, enc))
    if retriever == "sr":
        retr = BM25Retriever(kb)
    else:
        retr = (ExactDenseRetriever(kb, backend=backend,
                                    mesh_shards=mesh_shards)
                if retriever == "edr" else
                IVFRetriever(kb, backend=backend, mesh_shards=mesh_shards))
    return ServeStack(cfg=cfg, model=model, params=params, docs=docs,
                      encoder=enc, retriever=retr, rcfg=rcfg,
                      workload=default_workload(rcfg),
                      retriever_kind=retriever, backend=backend,
                      shared_cache=shared_cache, stream=stream)


def make_server(stack: ServeStack, *, scheduler: str = "fixed",
                n_slots: int = 1, cache_window: int = 512,
                async_fleet=None, engine=None):
    """THE server factory: every driver/benchmark server comes from here.

    ``scheduler`` picks the serving shape — ``seq`` (the per-request
    sequential baseline), ``single`` (single-request speculation),
    ``fixed`` (FleetServer lockstep groups of ``n_slots``), ``continuous``
    (ContinuousFleetServer admitting mid-flight) — and the stack's workload
    picks the algorithm (RaLM or KNN-LM) within it. ``async_fleet`` is the
    fleet servers' ``async_rounds`` (None follows rcfg.async_verification).
    Engines are cached on ``stack.engine`` and reused when the type and slot
    count match, so seq/single (or repeated fleet builds at one width) share
    one set of compiled decode functions; pass ``engine=`` to override."""
    validate_stack(stack.workload.name, stack.retriever_kind, stack.backend,
                   scheduler)
    knn = stack.workload.name == "knnlm"
    if scheduler in ("seq", "single"):
        eng = engine if engine is not None else stack.engine
        if not isinstance(eng, ServeEngine):
            eng = ServeEngine(stack.model, stack.params,
                              cache_window=cache_window)
            stack.engine = eng
        if scheduler == "seq":
            cls = KNNLMSeq if knn else RaLMSeq
            return cls(eng, stack.retriever, stack.rcfg, stack.encoder)
        if knn:
            return KNNLMSpec(eng, stack.retriever, stack.rcfg, stack.encoder)
        return RaLMSpec(eng, stack.retriever, stack.rcfg, stack.encoder,
                        shared_cache=stack.shared_cache)
    beng = engine if engine is not None else stack.engine
    if not (isinstance(beng, BatchedServeEngine) and beng.n_slots == n_slots):
        beng = BatchedServeEngine(stack.model, stack.params, n_slots,
                                  cache_window=cache_window)
        stack.engine = beng
    cls = ContinuousFleetServer if scheduler == "continuous" else FleetServer
    return cls(beng, stack.retriever, stack.rcfg, stack.encoder,
               async_rounds=async_fleet, shared_cache=stack.shared_cache,
               workload=stack.workload)


def variant_config(variant: str, base: RaLMConfig) -> RaLMConfig:
    """'', 'p', 's', 'a', 'ps', 'sa', 'pa', 'psa' — paper Table 1/4 naming."""
    return dataclasses.replace(
        base,
        prefetch_top_k=20 if "p" in variant else 1,
        use_os3="s" in variant,
        async_verification="a" in variant,
    )


def make_arrivals(n: int, rate: float, trace: str = "", seed: int = 0):
    """Arrival times on the modeled clock: a trace beats a rate beats all-at-0.

    ``trace`` is comma-separated seconds, or ``@path`` naming a file with one
    arrival time per line (blank lines and ``#`` comments ignored); either
    form is cycled/truncated to n. ``rate`` > 0 draws Poisson arrivals
    (exponential inter-arrival gaps, rate req/s). Malformed traces raise
    ``ValueError`` with a one-line message — the CLI maps it to an argparse
    error instead of a traceback."""
    if trace:
        text = trace
        if trace.startswith("@"):
            path = trace[1:]
            try:
                with open(path) as fh:
                    text = ",".join(line.split("#", 1)[0] for line in fh)
            except OSError as e:
                raise ValueError(
                    f"cannot read arrival trace file {path!r}: {e}") from None
        pts = []
        for x in text.replace("\n", ",").split(","):
            x = x.strip()
            if not x:
                continue
            try:
                pts.append(float(x))
            except ValueError:
                raise ValueError(f"malformed arrival time {x!r} "
                                 "(want seconds as a float)") from None
        if not pts:
            raise ValueError("arrival trace is empty")
        if any(p < 0 for p in pts):
            raise ValueError("arrival times must be >= 0")
        return [pts[i % len(pts)] for i in range(n)]
    if rate > 0:
        gaps = np.random.default_rng(seed).exponential(1.0 / rate, size=n)
        return np.cumsum(gaps).tolist()
    return [0.0] * n


def main() -> None:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--workload", choices=list(WORKLOADS), default="ralm",
                    help="ralm: iterative RaLM (Algorithm 1, byte-parity); "
                         "knnlm: KNN-LM serving (per-token datastore "
                         "retrieval, token-match parity — paper §5.3)")
    ap.add_argument("--retriever", choices=["edr", "adr", "sr"], default="edr")
    ap.add_argument("--arch", default="ralm-gpt2-medium",
                    help="host LM from the config registry (repro.configs)")
    ap.add_argument("--full-width", action="store_true",
                    help="serve --arch at its published width and depth "
                         "(default: a 2-layer reduced variant)")
    ap.add_argument("--mode", choices=["seq", "spec", "both"], default="both")
    ap.add_argument("--variant", default="psa",
                    help="subset of 'psa': prefetch / OS3 scheduler / async")
    ap.add_argument("--requests", "--num-requests", dest="requests", type=int,
                    default=5, help="number of requests to serve")
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--n-docs", type=int, default=20000)
    ap.add_argument("--stride", type=int, default=3)
    ap.add_argument("--concurrency", type=int, default=1,
                    help=">1: serve the speculative path through the fleet "
                         "(batched engine + cross-request batched verification)")
    ap.add_argument("--scheduler", choices=["fixed", "continuous"],
                    default="fixed",
                    help="fixed: groups of --concurrency in lockstep; "
                         "continuous: admit into freed slots mid-flight")
    ap.add_argument("--async-fleet", action="store_true",
                    help="pipeline fleet rounds: overlap the merged "
                         "verification KB call with the next lockstep "
                         "speculation stride (per-slot carry, adaptive gate; "
                         "implied by a variant containing 'a')")
    ap.add_argument("--retriever-backend",
                    choices=list(BACKENDS), default="numpy",
                    help="dense scoring backend (EDR full scan / ADR bucket "
                         "scan): numpy, the Pallas top-k kernel (compiled "
                         "for the TPU; interpret mode on CPU), the "
                         "mesh-sharded scan (one collective per merged "
                         "verification round), or their int8 quantized "
                         "siblings int8/int8-kernel/int8-sharded (~4x less "
                         "index memory, recall@k contract instead of "
                         "byte-parity). SR supports numpy only")
    ap.add_argument("--mesh-shards", type=int, default=0,
                    help="shard count for the sharded backends, one per "
                         "device (0 = every visible device; more shards than "
                         "devices is an error; on CPU, N > 1 forces an "
                         "N-device host platform before jax initializes)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate, requests per modeled second "
                         "(0 = all requests arrive at t=0)")
    ap.add_argument("--arrival-trace", default="",
                    help="comma-separated arrival times in modeled seconds, "
                         "or @FILE with one arrival per line "
                         "(overrides --arrival-rate)")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed for Poisson arrivals")
    ap.add_argument("--shared-cache", action="store_true",
                    help="put a fleet-scale shared speculation cache tier in "
                         "front of the KB (exact-hit on query bytes, then "
                         "approximate-hit on embedding inner product); "
                         "speculation-only, so outputs stay byte-identical "
                         "to the baseline")
    ap.add_argument("--shared-cache-capacity", type=int, default=65536,
                    help="entries held by the shared cache tier (LRU)")
    ap.add_argument("--retry-max", type=int, default=2,
                    help="KB-call retries (after the first attempt) on the "
                         "fleet verification/seed paths; a call failing every "
                         "attempt degrades its round to speculation-only")
    ap.add_argument("--retry-backoff", type=float, default=0.0,
                    help="base exponential backoff in seconds between KB-call "
                         "retries (retry i sleeps base*2^(i-1))")
    ap.add_argument("--retrieval-timeout", type=float, default=0.0,
                    help="per-KB-call deadline in seconds (0 = none): an "
                         "overrunning call is discarded and retried — safe "
                         "because KB search is deterministic")
    ap.add_argument("--inject-faults", default="",
                    help="chaos harness: seeded fault schedule for the KB "
                         "path, e.g. 'p_error=0.2,p_spike=0.1,spike_s=0.05,"
                         "seed=3' (also error_calls/spike_calls=i;j;..., "
                         "max_faults=n; see repro.retrieval.faults). "
                         "Requires --mode spec on a fleet scheduler")
    ap.add_argument("--max-queue-depth", type=int, default=0,
                    help="continuous scheduler: arrived requests allowed to "
                         "wait for a slot before newest arrivals are shed "
                         "(0 = unbounded)")
    ap.add_argument("--queue-deadline", type=float, default=0.0,
                    help="continuous scheduler: queueing-delay deadline in "
                         "modeled seconds past which a waiting request is "
                         "shed (0 = none)")
    args = ap.parse_args()
    try:
        # fail loudly rather than silently measuring the wrong scan: the ONE
        # capability table (and its one error path) names the valid set
        validate_stack(args.workload, args.retriever, args.retriever_backend,
                       args.scheduler)
    except ValueError as e:
        ap.error(str(e))
    arrivals = None
    if args.scheduler == "continuous":
        # parse the arrival trace BEFORE building the stack: a malformed
        # trace or unreadable @file is a usage error, not a traceback
        try:
            arrivals = make_arrivals(args.requests, args.arrival_rate,
                                     args.arrival_trace, args.seed)
        except ValueError as e:
            ap.error(f"--arrival-trace: {e}")
    fault_spec = None
    if args.inject_faults:
        try:
            fault_spec = parse_fault_spec(args.inject_faults)
        except ValueError as e:
            ap.error(f"--inject-faults: {e}")
        # fault tolerance lives on the fleet serving paths: the RaLMSeq
        # baseline and the single-request RaLMSpec path have no retry /
        # degradation shell, so injecting faults there would just crash —
        # reject the combination loudly instead
        if args.mode != "spec":
            ap.error("--inject-faults requires --mode spec (the RaLMSeq "
                     "baseline has no fault-tolerance shell)")
        if args.scheduler != "continuous" and args.concurrency <= 1:
            ap.error("--inject-faults requires a fleet scheduler: use "
                     "--concurrency > 1 or --scheduler continuous (the "
                     "single-request path has no fault-tolerance shell)")

    rcfg = variant_config(args.variant.replace("-", ""),
                          RaLMConfig(max_new_tokens=args.max_new,
                                     speculation_stride=args.stride,
                                     retry_max=args.retry_max,
                                     retry_backoff_s=args.retry_backoff,
                                     retrieval_timeout_s=args.retrieval_timeout,
                                     max_queue_depth=args.max_queue_depth,
                                     queue_deadline_s=args.queue_deadline))
    shared = (SharedRetrievalCache(capacity=args.shared_cache_capacity)
              if args.shared_cache else None)
    enable_compile_cache()
    stack = build_stack(
        args.retriever, n_docs=args.n_docs, backend=args.retriever_backend,
        mesh_shards=args.mesh_shards, workload=args.workload, rcfg=rcfg,
        shared_cache=shared, arch=args.arch, full_width=args.full_width)
    docs, retr = stack.docs, stack.retriever
    if args.retriever_backend != "numpy":
        b = retr.backend
        detail = (f"{b.n_shards} shard(s), one collective per KB call"
                  if b.name.endswith("sharded") else
                  "device-resident KB" if b.name.endswith("kernel") else
                  "int8 codes + fp32 row scales, numpy scan")
        if not b.exact:
            detail += (f"; INEXACT (recall contract), index "
                       f"{b.kb_bytes / 1e6:.1f} MB int8")
        print(f"{args.retriever.upper()} backend: {b.name} ({detail})")
    inj = inject_faults(retr, fault_spec) if fault_spec is not None else None
    if args.workload == "knnlm":
        # KNN-LM prompts are prefixes of the datastore's own token stream —
        # the regime where neighbour retrieval carries signal
        prompts = [stack.stream[i * 97:i * 97 + 48].tolist()
                   for i in range(args.requests)]
    else:
        prompts = [(q * 12)[:48] for q in make_queries(docs, args.requests)]

    degraded = []                           # requests served degraded

    def run(server, label):
        tot_w = tot_g = tot_r = 0.0
        toks = []
        for p in prompts:
            r = server.serve(p)
            tot_w += r.wall_time
            tot_g += r.gen_time
            tot_r += r.retrieval_time
            toks.append(r.tokens)
        print(f"{label:14s} wall {tot_w:7.2f}s  G {tot_g:6.2f}s  R {tot_r:6.2f}s")
        return tot_w, toks

    async_rounds = True if args.async_fleet else None  # None: follow variant

    def degradation_line(res) -> None:
        """One line of fault-tolerance accounting when anything fired."""
        if not (res.kb_errors or res.kb_timeouts or res.kb_failures
                or res.degraded_rounds or res.worker_crashes
                or res.seed_failures or getattr(res, "shed", 0)):
            return
        print(f"{'fault ledger':14s} retried {res.kb_errors} errors + "
              f"{res.kb_timeouts} timeouts; {res.kb_failures} calls failed "
              f"for good -> {res.degraded_rounds} degraded rounds "
              f"({res.degraded_requests} requests), {res.worker_crashes} "
              f"worker crashes recovered, {res.seed_failures} seed calls "
              f"lost, {getattr(res, 'shed', 0)} requests shed")

    def run_fleet(label):
        tot_w = tot_an = 0.0
        toks, n_tok = [], 0
        # context manager: the async verification worker is released even if
        # a serve() raises mid-group
        with make_server(stack, scheduler="fixed",
                         n_slots=args.concurrency,
                         async_fleet=async_rounds) as fleet:
            for i in range(0, len(prompts), args.concurrency):
                fr = fleet.serve(prompts[i:i + args.concurrency])
                tot_w += fr.wall_time
                tot_an += fr.analytic_time
                n_tok += fr.total_tokens
                toks.extend(r.tokens for r in fr.results)
                degraded.extend(r for r in fr.results
                                if r.status == "degraded")
                degradation_line(fr)
        print(f"{label:14s} wall {tot_w:7.2f}s  modeled {tot_an:6.2f}s  "
              f"throughput {n_tok / max(tot_an, 1e-9):8.1f} tok/s (modeled)")
        return tot_w, toks

    def run_continuous(label):
        with make_server(stack, scheduler="continuous",
                         n_slots=args.concurrency,
                         async_fleet=async_rounds) as server:
            cr = server.serve(as_requests(prompts, arrivals))
        print(f"{label:14s} wall {cr.wall_time:7.2f}s  "
              f"modeled makespan {cr.analytic_time:6.2f}s  "
              f"throughput {cr.throughput():8.1f} tok/s (modeled)  "
              f"p50 {cr.p50:.2f}s  p99 {cr.p99:.2f}s  "
              f"peak live {cr.max_live}")
        degradation_line(cr)
        degraded.extend(r for r in cr.results if r.status == "degraded")
        return cr.wall_time, [r.tokens for r in cr.results]

    knn = args.workload == "knnlm"
    results = {}
    if args.mode in ("seq", "both"):
        results["seq"] = run(make_server(stack, scheduler="seq"),
                             "KNNLMSeq" if knn else "RaLMSeq")
    if args.mode in ("spec", "both"):
        base = "KNNLMSpec" if knn else "RaLMSpec"
        label = base + ("+" + args.variant.upper() if args.variant else "")
        if args.scheduler == "continuous":
            results["spec"] = run_continuous(f"Continuous x{args.concurrency}")
        elif args.concurrency > 1:
            results["spec"] = run_fleet(f"Fleet x{args.concurrency}")
        else:
            results["spec"] = run(make_server(stack, scheduler="single"),
                                  label)
    failures = []
    if len(results) == 2:
        same = results["seq"][1] == results["spec"][1]
        kind = ("outputs token-match" if stack.workload.equivalence ==
                "token-match" else "outputs identical")
        print(f"{kind}: {same}   "
              f"speed-up {results['seq'][0] / max(results['spec'][0], 1e-9):.2f}x")
        if not same:
            failures.append(f"{kind}: False")
    if degraded and inj is None:
        # degradation is the documented answer to INJECTED faults only; with
        # none injected, a degraded request is a failure the run must report
        failures.append(f"{len(degraded)} request(s) degraded with no "
                        "injected faults")
    if getattr(getattr(retr, "backend", None), "name", "").endswith("sharded"):
        # the merge invariant, visible: every KB call (seed or merged
        # verification round — EDR scan or ADR probe) executed as exactly one
        # sharded collective
        print(f"sharded collectives: {retr.backend.calls}  "
              f"KB calls: {retr.stats.calls}  (1 collective per call)")
    if shared is not None:
        st = shared.stats()
        print(f"shared cache: {st['hits_exact']} exact + "
              f"{st['hits_approx']} approx hits / {st['lookups']} lookups "
              f"({st['hit_rate']:.0%} hit rate), {st['size']} entries")
    if inj is not None:
        print(f"fault injection: {inj.errors} errors + {inj.spikes} spikes "
              f"over {inj.calls} KB scans (seed {inj.spec.seed}); "
              f"retried {retr.stats.errors + retr.stats.timeouts} attempts, "
              f"{retr.stats.failed_calls} calls failed after retries")
    if failures:
        sys.exit("FAILED: " + "; ".join(failures))


if __name__ == "__main__":
    main()
