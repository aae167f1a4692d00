"""Readings that set a cell's limits: the program's compared numbers and
the controls', over many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 8 --controls high,bfloat16

For each seed it builds the cell from that seed, warms it, serves a short
window at the cell's own load and compares, as a run does; then it puts the
plain reference in the program's place at each control precision and reads
the same numbers. One JSON line per seed; the benchmark's runs never call
this. Set-up shared by the seeds (compiled programs) is paid once."""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def calibrate(cell_name, seeds, seconds, controls, *, bench_path=None,
              data_dirs=(), allow_cpu=False, fault=None, log=print):
    bm, cell, cfg, mix = harness.prepare(cell_name, bench_path, data_dirs)
    harness._devices(cell, allow_cpu)
    harness._configure_jax(cfg, on_chip=not allow_cpu)
    from bench import check, stack
    rows = []
    for seed in seeds:
        t = time.perf_counter()
        sysm = stack.build(cfg, mix, seed)
        rate = harness.warm_up(sysm, seed)
        if fault is not None:
            fault(sysm)
        res, watch, window_s, reqs = harness.serve_window(sysm, seed,
                                                          seconds, rate)
        served = [(p, list(r.tokens)) for (p, _), r in zip(reqs, res.results)]
        sysm.free_program()
        gc.collect()
        nums = check.numbers(sysm, served, watch.kb_records, seed, controls)
        row = {"seed": seed, "requests": len(reqs),
               "tokens": sum(len(t) for _, t in served),
               "window_s": window_s, "kb_calls_checked": len(watch.kb_records),
               "seconds_this_seed": time.perf_counter() - t, **nums}
        log(json.dumps(row))
        rows.append(row)
        del sysm, res, watch, served
        gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", default="high,bfloat16")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [c for c in args.controls.split(",") if c]
    calibrate(args.workload, seeds, args.seconds, controls)
    return 0


if __name__ == "__main__":
    sys.exit(main())
