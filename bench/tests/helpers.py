"""Shared by the CPU tests: run a tiny cell through the harness."""
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
TINY = HERE / "tiny_benchmark.json"


def run_tiny(cell, seed=7, seconds=2.0, trace=False, fault=None,
             bench_path=TINY, data_dirs=(HERE,)):
    from bench import harness
    return harness.run(cell, seed, seconds, trace, t_process=time.perf_counter(),
                       bench_path=bench_path, data_dirs=list(data_dirs),
                       allow_cpu=True, fault=fault, log=lambda *a: None)
