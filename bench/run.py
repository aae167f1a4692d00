"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier stdout lines say how the run went (set-up split, window, compiles
inside the window, peak device bytes); the last stdout line is the result
object; the last stderr lines are each compared number beside its limit.
Exits non-zero, with no result line, when JAX finds no TPU or fewer chips
than the cell asks for."""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
