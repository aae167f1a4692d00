"""Smoke test of the RaLMSpec serving path on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(c) below
    python chip_smoke.py --chips 4   # four chips: the sharded KB only

Drives the serving entry points a user calls — ``build_stack`` ->
``make_server`` -> ``serve`` — with the paper's LMs at their published width
(random float32 weights from ``--seed``) and KBs built in memory, and holds
every phase to the system's own output-preservation contract: speculative
fleet serving must reproduce the sequential baseline token for token.

One chip:
  (a) RaLM + EDR, ``ralm-gpt2-medium``, Pallas ``kernel`` backend over a
      500k x 768 KB: 4 requests through the fixed fleet (4 slots) vs RaLMSeq,
      byte parity.
  (b) RaLM + ADR on the same LM and device-resident KB (IVF probe through
      the fused in-kernel gather), byte parity.
  (c) KNN-LM + EDR, ``knnlm-247m``, ``kernel`` backend over a 200k x 1024
      datastore vs KNNLMSeq, token match.
Four chips (``--chips 4``): RaLM + EDR on the ``sharded`` backend, a 2M x
768 KB over 4 shards (the LM stays on device 0), against the same requests
served by RaLMSeq over the single-device ``numpy`` scan: byte parity, 4
shards, one collective per KB call.

Every phase also asserts no degraded round, failed KB call or crashed
verification worker, and — for the kernel backends — that the KB call's
lowered program holds a ``tpu_custom_call`` (the kernel ran, not an
interpreter). Set-up facts (device kind, wall time, programs compiled,
compile seconds, peak device bytes) print on earlier lines; they are not
benchmark numbers. The last line of stdout is one JSON object naming the
device. With no TPU, or run outside the repository, the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RALM_DOCS = 500_000          # x 768 f32 = 1.5 GB resident KB
SHARDED_DOCS = 2_000_000     # x 768 f32 = 6.1 GB over 4 chips
KNN_ENTRIES = 200_000        # x 1024 f32 = 0.8 GB datastore
N_REQUESTS = 4
MAX_NEW = 16


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Programs compiled (or loaded from the persistent cache) and the
    seconds spent on them, from JAX's own compile events."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.n, self.secs = 0, 0.0

        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.n += 1
                self.secs += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def snapshot(self):
        return self.n, self.secs


def _prompts(stack, n: int):
    from repro.training.data import make_queries
    if stack.workload.name == "knnlm":
        # prefixes of the datastore's own token stream, as the CLI serves
        return [stack.stream[i * 97:i * 97 + 48].tolist() for i in range(n)]
    return [(q * 12)[:48] for q in make_queries(stack.docs, n)]


def serve_and_compare(stack, ref_stack, prompts, engines: dict, label: str):
    """Serve ``prompts`` through the sequential baseline on ``ref_stack``
    and through the fixed fleet on ``stack``; require identical tokens and a
    clean fault ledger. Engines are reused across phases that share an LM
    (their jitted programs with them)."""
    from repro.launch.serve import make_server
    seq = make_server(ref_stack, scheduler="seq", engine=engines.get("seq"))
    engines["seq"] = seq.engine
    want = [seq.serve(p).tokens for p in prompts]
    with make_server(stack, scheduler="fixed", n_slots=len(prompts),
                     engine=engines.get("fleet")) as fleet:
        engines["fleet"] = fleet.engine
        fr = fleet.serve(prompts)
    got = [r.tokens for r in fr.results]
    kind = stack.workload.equivalence
    first_diff = {i: next((j for j, (a, b) in enumerate(zip(g, w)) if a != b),
                          min(len(g), len(w)))
                  for i, (g, w) in enumerate(zip(got, want)) if g != w}
    check(not first_diff, f"{label}: fleet tokens differ from the sequential "
                          f"baseline ({kind} contract); first differing "
                          f"token per request {first_diff}")
    check(all(len(t) == MAX_NEW for t in got),
          f"{label}: expected {MAX_NEW} tokens per request")
    for field in ("degraded_rounds", "kb_failures", "worker_crashes",
                  "kb_errors", "kb_timeouts", "seed_failures"):
        check(getattr(fr, field) == 0,
              f"{label}: {field} = {getattr(fr, field)}")
    check(all(r.status == "ok" for r in fr.results),
          f"{label}: request status {[r.status for r in fr.results]}")
    print(f"  {label}: {len(prompts)} requests x {MAX_NEW} tokens, "
          f"{kind} parity with the sequential baseline, {fr.rounds} fleet "
          f"rounds, {fr.kb_calls} merged KB calls", flush=True)
    return fr


def check_kernel_program(backend, label: str, B: int, k: int, C=None):
    text = backend.program_text(B, k, C)
    check("tpu_custom_call" in text,
          f"{label}: the KB call did not lower to a tpu_custom_call")


def phase(name: str, meter: CompileMeter, fn) -> None:
    import jax
    n0, s0 = meter.snapshot()
    t0 = time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    n1, s1 = meter.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"phase {name}: passed in {wall:.1f} s wall; {n1 - n0} programs "
          f"compiled in {s1 - s0:.1f} s; device 0 peak_bytes_in_use {peak}",
          flush=True)


def ralm_config():
    from repro.configs import RaLMConfig
    from repro.launch.serve import variant_config
    return variant_config("psa", RaLMConfig(max_new_tokens=MAX_NEW))


def one_chip_phases(seed: int, meter: CompileMeter, *, ralm_docs=RALM_DOCS,
                    knn_entries=KNN_ENTRIES, full_width=True) -> None:
    from repro.launch.serve import build_stack
    from repro.retrieval.retrievers import IVFRetriever

    engines: dict = {}
    box = {}

    def edr():
        t0 = time.perf_counter()
        stack = build_stack("edr", n_docs=ralm_docs, enc_dim=768,
                            backend="kernel", rcfg=ralm_config(), seed=seed,
                            full_width=full_width)
        print(f"  built {stack.cfg.name} ({stack.cfg.num_layers} layers, "
              f"d_model {stack.cfg.d_model}) + {ralm_docs} x 768 KB in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        box["stack"] = stack
        prompts = _prompts(stack, N_REQUESTS)
        serve_and_compare(stack, stack, prompts, engines, "ralm-edr")
        check_kernel_program(stack.retriever.backend, "ralm-edr", 1, 1)
        box["prompts"] = prompts

    def adr():
        stack = box.pop("stack")
        t0 = time.perf_counter()
        # the IVF index shares the EDR backend: same device-resident KB
        ivf = IVFRetriever(stack.retriever.kb, backend=stack.retriever.backend)
        print(f"  built IVF index ({len(ivf.buckets)} buckets, probe width "
              f"{ivf._cand_width(1)}) in {time.perf_counter() - t0:.1f} s",
              flush=True)
        adr_stack = dataclasses.replace(stack, retriever=ivf,
                                        retriever_kind="adr", engine=None)
        serve_and_compare(adr_stack, adr_stack, box.pop("prompts"), engines,
                          "ralm-adr")
        check_kernel_program(ivf.backend, "ralm-adr", 1, 1,
                             C=ivf._cand_width(1))

    def knn():
        engines.clear()                 # a different LM: new engines
        t0 = time.perf_counter()
        # a datastore of knn_entries keys needs a token stream of about
        # that many tokens: 48-token docs
        stack = build_stack("edr", workload="knnlm", arch="knnlm-247m",
                            n_docs=knn_entries // 48 + 1, enc_dim=1024,
                            knn_entries=knn_entries, backend="kernel",
                            rcfg=ralm_config(), seed=seed,
                            full_width=full_width)
        print(f"  built {stack.cfg.name} ({stack.cfg.num_layers} layers, "
              f"d_model {stack.cfg.d_model}) + {stack.retriever.kb.size} x "
              f"1024 datastore in {time.perf_counter() - t0:.1f} s",
              flush=True)
        serve_and_compare(stack, stack, _prompts(stack, N_REQUESTS), engines,
                          "knnlm-edr")
        check_kernel_program(stack.retriever.backend, "knnlm-edr", 1,
                             stack.rcfg.knn_k)

    phase("a (RaLM EDR, kernel)", meter, edr)
    phase("b (RaLM ADR, fused gather)", meter, adr)
    phase("c (KNN-LM EDR, kernel)", meter, knn)


def four_chip_phase(seed: int, meter: CompileMeter, *,
                    n_docs=SHARDED_DOCS, full_width=True) -> None:
    from repro.launch.serve import build_stack
    from repro.retrieval.retrievers import ExactDenseRetriever

    def sharded():
        t0 = time.perf_counter()
        stack = build_stack("edr", n_docs=n_docs, enc_dim=768,
                            backend="sharded", mesh_shards=4,
                            rcfg=ralm_config(), seed=seed,
                            full_width=full_width)
        backend = stack.retriever.backend
        print(f"  built {stack.cfg.name} + {n_docs} x 768 KB over "
              f"{backend.n_shards} shards in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        check(backend.n_shards == 4, f"{backend.n_shards} KB shards, want 4")
        ref = dataclasses.replace(
            stack, retriever=ExactDenseRetriever(stack.retriever.kb),
            backend="numpy", engine=None)
        serve_and_compare(stack, ref, _prompts(stack, N_REQUESTS), {},
                          "ralm-edr-sharded")
        check(backend.calls == stack.retriever.stats.calls,
              f"{backend.calls} collectives for "
              f"{stack.retriever.stats.calls} KB calls")
        print(f"  {backend.calls} collectives for "
              f"{stack.retriever.stats.calls} KB calls", flush=True)

    phase("sharded (RaLM EDR, 4 shards vs numpy)", meter, sharded)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) on one chip; 4: the sharded-KB "
                         "phase over four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random LM weights")
    args = ap.parse_args()

    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository ({e})",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} TPU "
              "device(s) visible", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x {len(devices)}; compile cache "
          f"{cache}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phase(args.seed, meter)
        else:
            one_chip_phases(args.seed, meter)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n, secs = meter.snapshot()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s; {n} "
          f"programs compiled in {secs:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
