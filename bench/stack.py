"""Build the system under test for one configuration and seed.

Everything the program is given is made here from the seed, on the device
and in one jitted call per array: the LM's float32 weights (in the layout of
the program's own parameter tree, so the plain reference reads the very same
arrays by name), the encoder table, the KB. The KB reaches the program as
any caller gives it: a host array handed to the kernel backend's own
constructor, which uploads it. The program then serves through its normal
entry points: ``ServeStack`` -> ``make_server(scheduler="continuous")`` ->
``ContinuousFleetServer.serve``."""
from __future__ import annotations

import dataclasses
import importlib
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, traffic

MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps",
              "qkv_bias")


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def device_key(seed: int, purpose: str):
    """A PRNG key per (seed, purpose), for any non-negative seed."""
    return jax.random.PRNGKey(int(traffic.rng(seed, purpose).integers(0, 2 ** 31)))


@dataclass
class Corpus:
    """What the benchmark made, as host arrays the program, the traffic and
    the reference read."""

    table: np.ndarray                      # encoder table (vocab, dim)
    keys: np.ndarray                       # KB keys (rows, dim)
    passages: Optional[np.ndarray] = None  # (rows, passage_tokens) payloads
    values: Optional[np.ndarray] = None    # datastore next-token values
    heldout: Optional[np.ndarray] = None   # held-out token stream
    _keys_dev: Optional[jax.Array] = None

    def keys_on_device(self) -> jax.Array:
        """The reference's own device copy of the KB, uploaded at its first
        use (after the program's state is freed)."""
        if self._keys_dev is None:
            self._keys_dev = jax.device_put(self.keys)
        return self._keys_dev


@dataclass
class System:
    cfg: dict
    mix: dict
    model_cfg: object
    params: dict
    corpus: Corpus
    stack: object
    server: object
    split: dict = field(default_factory=dict)

    @property
    def engine(self):
        return self.server.engine

    @property
    def retriever(self):
        return self.server.retriever

    def free_program(self) -> None:
        """Drop the program's serving state (engine, KV caches, server);
        the benchmark's own arrays stay for the reference."""
        self.server.close()
        self.stack.engine = None
        self.server = None
        self.stack = None


def make_corpus(cfg: dict, seed: int) -> Corpus:
    kb, vocab = cfg["kb"], cfg["vocab_size"]
    topic_key = device_key(seed, "topics")
    tm = dict(vocab=vocab, n_topics=kb["n_topics"],
              topic_words=kb["topic_words"], topical_share=kb["topical_share"])
    table_dev = data.encoder_table(device_key(seed, "table"), vocab, kb["dim"])
    rows, L = kb["rows"], kb["passage_tokens"]
    if kb["kind"] == "passages":
        docs = data.passages(topic_key, device_key(seed, "kb"), n=rows,
                             length=L, **tm)
        return Corpus(table=np.asarray(table_dev),
                      keys=np.asarray(data.passage_keys(docs, table_dev)),
                      passages=np.asarray(docs))
    if kb["kind"] == "token_datastore":
        w = kb["encoder_window"]
        need = rows + w + 1
        stream = data.passages(topic_key, device_key(seed, "kb"),
                               n=-(-need // L), length=L, **tm).reshape(-1)
        keys = data.fir_keys(stream, table_dev, kb["encoder_decay"],
                                 n_entries=rows, window=w)
        held = data.passages(topic_key, device_key(seed, "heldout"),
                             n=kb["heldout_passages"], length=L, **tm)
        s = np.asarray(stream)
        return Corpus(table=np.asarray(table_dev), keys=np.asarray(keys),
                      values=s[w:w + rows].copy(),
                      heldout=np.asarray(held).reshape(-1))
    raise ValueError(f"unknown kb kind {kb['kind']!r}")


def rcfg_of(cfg: dict, mix: dict):
    from repro.configs import RaLMConfig
    from repro.configs.base import SpeculationConfig
    from repro.launch.serve import variant_config
    srv = cfg["serving"]
    base = RaLMConfig(
        speculation=SpeculationConfig(generation_stride=srv["generation_stride"],
                                      max_stride=srv["max_stride"]),
        knnlm=cfg["workload"] == "knnlm", knn_k=srv.get("knn_k", 8),
        knn_lambda=srv.get("knn_lambda", 0.25),
        max_new_tokens=traffic.max_output(mix))
    return variant_config(srv["variant"], base)


def build(cfg: dict, mix: dict, seed: int) -> System:
    from repro.configs import get_config
    from repro.core.knnlm import knn_interpolate
    from repro.launch.serve import ServeStack, make_server
    from repro.models.model import build_model
    from repro.retrieval.backends import KernelBackend
    from repro.retrieval.encoder import ContextEncoder
    from repro.retrieval.kb import DenseKB
    from repro.retrieval.retrievers import ExactDenseRetriever
    from repro.serving.workload import default_workload

    split = {}
    t = time.perf_counter()
    mcfg = dataclasses.replace(get_config(cfg["arch"]),
                               **{k: cfg[k] for k in MODEL_KEYS if k in cfg})
    model = build_model(mcfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = reference(cfg).make_params(shapes, device_key(seed, "weights"),
                                        mcfg.d_model, cfg.get("logit_scale", 1.0))
    jax.block_until_ready(params)
    split["lm_init_s"] = time.perf_counter() - t

    t = time.perf_counter()
    corpus = make_corpus(cfg, seed)
    split["kb_build_s"] = time.perf_counter() - t

    kb = cfg["kb"]
    enc = ContextEncoder(1, d=kb["dim"], window=kb["encoder_window"],
                         decay=kb["encoder_decay"])
    enc.table = corpus.table
    dense = DenseKB(embeddings=corpus.keys,
                    docs=corpus.passages if corpus.passages is not None else [],
                    values=corpus.values)
    retr = ExactDenseRetriever(
        dense, backend=KernelBackend(
            corpus.keys, force_ref=cfg["serving"].get("force_ref", False)))
    rcfg = rcfg_of(cfg, mix)
    if cfg["workload"] == "knnlm":
        beta = knn_interpolate.__defaults__[-1]
        assert beta == cfg["serving"]["knn_beta"], \
            f"program's kNN temperature {beta} != the configuration's"
    stack = ServeStack(cfg=mcfg, model=model, params=params,
                       docs=dense.docs, encoder=enc, retriever=retr,
                       rcfg=rcfg, workload=default_workload(rcfg),
                       retriever_kind="edr", backend="kernel")
    server = make_server(stack, scheduler="continuous",
                         n_slots=mix["clients"],
                         cache_window=cfg["serving"]["cache_window"])
    return System(cfg=cfg, mix=mix, model_cfg=mcfg, params=params,
                  corpus=corpus, stack=stack, server=server, split=split)


def prefill_lengths(sysm: System) -> list:
    """Every context length the cell's traffic makes the engine prefill:
    each prompt alone at admission and, for iterative RaLM, the doc chunk +
    prompt + each multiple of the generation stride (a doc swap re-prefills
    at a stride boundary)."""
    cfg, mix = sysm.cfg, sysm.mix
    out = set()
    for p in mix["prompt"]["lengths"]:
        out.add(p)
        if cfg["workload"] == "ralm":
            g, chunk = cfg["serving"]["generation_stride"], cfg["serving"]["chunk_len"]
            out.update(chunk + p + g * i
                       for i in range(-(-traffic.max_output(mix) // g)))
    return sorted(out)


def kb_batch_widths(sysm: System) -> range:
    """Every query count a merged KB call can carry: each live slot's
    speculation steps (up to the scheduler's largest stride) plus the
    ride-along seed queries of as many queued requests as there are slots."""
    rcfg, n = sysm.server.rcfg, sysm.mix["clients"]
    per_slot = rcfg.max_stride if rcfg.use_os3 else rcfg.speculation_stride
    return range(1, n * per_slot + n + 1)


def warm(sysm: System, log=lambda msg: None) -> None:
    """Compile and run once every program the window will use: the prefill
    grid and the batched decode step (with the per-length reshape of the
    context the engine does before each prefill), a slot's snapshot and
    restore (the rollback path), then the KB call at every width."""
    eng, r = sysm.engine, sysm.retriever
    t = time.perf_counter()
    lengths = prefill_lengths(sysm)
    for i in range(0, len(lengths), 8):
        eng.warm(lengths[i:i + 8])
        log(f"prefill + decode warm: {min(i + 8, len(lengths))}/{len(lengths)}")
    for n in lengths:
        jax.block_until_ready(jnp.asarray(np.zeros((n,), np.int32))[None])
    eng.admit(0, [2] * sysm.mix["prompt"]["lengths"][0])
    eng.restore(0, eng.snapshot(0))
    jax.block_until_ready(eng._state)
    eng.retire(0)
    sysm.split["warm_lm_s"] = time.perf_counter() - t
    t = time.perf_counter()
    k = sysm.server.workload.verify_k(sysm.server.rcfg)
    d = sysm.corpus.keys.shape[1]
    widths = kb_batch_widths(sysm)
    for B in widths:
        r.retrieve(np.zeros((B, d), np.float32), k)
        if B % 32 == 0 or B == widths[-1]:
            log(f"KB call warm: {B}/{widths[-1]}")
    jax.block_until_ready(jnp.zeros(()))
    sysm.split["warm_kb_s"] = time.perf_counter() - t
