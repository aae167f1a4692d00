"""One run of one cell: build, warm, measure a window, check, report.

The window is one ``ContinuousFleetServer.serve`` call over a closed-loop
backlog: every request is queued at arrival 0, so each of the cell's
``clients`` slots takes the next request the moment it frees (clients that
each wait for their reply, no think time). The backlog is a whole number of
cycles through the mix's fixed pool of output lengths (each cycle the same
sizes, in the seed's order), as many as the warm-up's measured rate fills
``--seconds`` with (at least one); the window is that call's wall time. Latency is a request's wall time from
admission into a slot to retirement. Nothing is read from the program's
modeled clock."""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional, Sequence

from bench import spec, traffic

CACHE_DIR = spec.ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileMeter:
    """Programs compiled or loaded from the persistent cache, and their
    seconds, from JAX's own compile events; persistent-cache hits and
    misses from its cache events."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.n, self.secs, self.hits, self.misses = 0, 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.n += 1
                self.secs += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclass
class RunView:
    """What a metric reader may read about the run."""

    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    setup_s: float
    window_s: float
    tokens: int
    latencies: list
    result: object                 # the program's ContinuousResult
    served: list                   # (prompt, tokens) per request
    device_kind: str
    trace: Optional[object] = None  # trace.Summary of the traced run
    kb_batches: list = field(default_factory=list)
    kb_k: int = 0                  # rows per query of the merged KB call
    model_cfg: dict = field(default_factory=dict)


def _configure_jax(cfg: dict, on_chip: bool) -> None:
    """The persistent compilation cache at a fixed path. The matmul
    precision stays the program's own: its decode step sets "highest",
    everything else runs at JAX's default, as the configuration states."""
    import jax
    assert (cfg["decode_matmul_precision"], cfg["other_matmul_precision"]) \
        == ("highest", "default"), "the program's precision is not settable"
    if on_chip:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _devices(cell: dict, allow_cpu: bool):
    import jax
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if not allow_cpu and len(devs) < cell["chips"]:
        raise NoChip(f"cell needs {cell['chips']} chips, JAX found {len(devs)}")
    return devs


def _peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def prepare(cell_name: str, bench_path=None, data_dirs: Sequence = ()):
    bm = spec.load_benchmark(bench_path)
    root = Path(bench_path).resolve().parent if bench_path else spec.ROOT
    cell = spec.find_cell(bm, cell_name)
    cfg = spec.load_config(bm, cell["config"], root)
    mix = spec.load_traffic(cell["traffic"], data_dirs)
    return bm, cell, cfg, mix


def serve_window(sysm, seed: int, seconds: float, rate: float,
                 tracer=None, stream: str = "window"):
    """Serve the closed-loop backlog once; -> (result, watch, window_s,
    requests)."""
    from repro.serving.continuous import as_requests
    from bench.hooks import Watch
    pool = traffic.output_pool(sysm.mix)
    n = len(pool) * max(1, round(rate * seconds / sum(pool)))
    reqs = traffic.make_requests(sysm.mix, sysm.corpus, seed, n, stream)
    requests = as_requests([p for p, _ in reqs], max_new=[m for _, m in reqs])
    with Watch(sysm.server, traffic.rng(seed, "kb-sample"),
               tracer=tracer) as w:
        t0 = time.perf_counter()
        res = sysm.server.serve(requests)
        window_s = time.perf_counter() - t0
    return res, w, window_s, reqs


def warm_up(sysm, seed: int, log=lambda msg: None) -> float:
    """Compile every program the window uses, serve one short request per
    slot (the host-side programs of the round loop compile there), then one
    request of the mix's median length per slot; -> the last serve's output
    tokens per second, which sizes the window's backlog."""
    from repro.serving.continuous import as_requests
    from bench import stack
    stack.warm(sysm, log)
    t = time.perf_counter()
    pool = sorted(traffic.output_pool(sysm.mix))
    reqs = traffic.make_requests(sysm.mix, sysm.corpus, seed,
                                 sysm.mix["clients"], "warm-up")
    prompts = [p for p, _ in reqs]
    sysm.server.serve(as_requests(prompts, max_new=[pool[0]] * len(prompts)))
    t0 = time.perf_counter()
    res = sysm.server.serve(as_requests(
        prompts, max_new=[pool[len(pool) // 2]] * len(prompts)))
    rate = res.total_tokens / (time.perf_counter() - t0)
    sysm.split["warm_serve_s"] = time.perf_counter() - t
    log(f"warm-up serve: {rate:.1f} tokens/s")
    return rate


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, bench_path=None, data_dirs: Sequence = (),
        allow_cpu: bool = False, fault: Optional[Callable] = None,
        log=print) -> dict:
    """One run; returns the result object (the last stdout line)."""
    bm, cell, cfg, mix = prepare(cell_name, bench_path, data_dirs)
    devs = _devices(cell, allow_cpu)
    _configure_jax(cfg, on_chip=not allow_cpu)
    from bench import check, stack
    from bench import trace as trace_mod
    from bench.hooks import Tracer

    def progress(msg):
        log(f"[{time.perf_counter() - t_process:8.1f} s] {msg}")
        sys.stdout.flush()

    meter = CompileMeter()
    sysm = stack.build(cfg, mix, seed)
    progress(f"built: {sysm.split}")
    rate = warm_up(sysm, seed, progress)
    if fault is not None:
        fault(sysm)
    setup_s = time.perf_counter() - t_process
    n_setup, s_setup = meter.n, meter.secs

    tracer, log_dir = None, None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = Tracer(log_dir, start_after=min(5.0, 0.25 * seconds),
                        duration=min(4.0, 0.5 * seconds))
    res, watch, window_s, reqs = serve_window(sysm, seed, seconds, rate,
                                              tracer=tracer)
    compiles_in_window = meter.n - n_setup
    peak = _peak_bytes(devs[:cell["chips"]])
    served = [(p, list(r.tokens)) for (p, _), r in zip(reqs, res.results)]
    attempted = len(reqs)
    failed = sum(1 for (_, m), r in zip(reqs, res.results)
                 if r.status != "ok" or len(r.tokens) != m)
    tokens = sum(len(t) for _, t in served)

    summary = None
    if trace:
        summary = trace_mod.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)

    view = RunView(cell=cell, cfg=cfg, mix=mix, seconds=seconds,
                   setup_s=setup_s, window_s=window_s, tokens=tokens,
                   latencies=list(watch.latencies), result=res, served=served,
                   device_kind=devs[0].device_kind, trace=summary,
                   kb_batches=list(tracer.kb_batches) if tracer else [],
                   kb_k=sysm.server.workload.verify_k(sysm.server.rcfg),
                   model_cfg={k: getattr(sysm.model_cfg, k)
                              for k in stack.MODEL_KEYS})
    sysm.free_program()
    gc.collect()
    t = time.perf_counter()
    nums = check.numbers(sysm, served, watch.kb_records, seed)["program"]
    check_s = time.perf_counter() - t
    limits = cfg["limits"]
    correct = check.verdict(nums, limits) and failed == 0

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bm, cell["name"], section):
        v = spec.load_metric(m["name"], data_dirs).read(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    log(f"setup_s {setup_s:.3f} split "
        + " ".join(f"{k} {v:.3f}" for k, v in sysm.split.items())
        + f" programs_at_setup {n_setup} compile_s {s_setup:.3f} cache_hits {meter.hits} "
        f"cache_misses {meter.misses} warm_rate_tok_s {rate:.1f}")
    log(f"window_s {window_s:.3f} requests {attempted} retired {len(watch.latencies)} "
        f"tokens {tokens} latency_samples {len(watch.latencies)} "
        f"compiles_in_window {compiles_in_window} kb_calls {res.kb_calls} "
        f"rounds {res.rounds} max_live {res.max_live} kb_failures {res.kb_failures} "
        f"degraded_rounds {res.degraded_rounds}")
    log(f"memory_peak_bytes {peak} check_s {check_s:.3f} "
        f"kb_calls_checked {len(watch.kb_records)} of {watch.kb_seen}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = {n: {"value": nums[n], "limit": limits[n]} for n in limits}
    return out


def report(out: dict) -> None:
    """Compared numbers as the last stderr lines, the result as the last
    stdout line."""
    for n, c in out["checks"].items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
