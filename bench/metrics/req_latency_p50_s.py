"""Median admission-to-retirement wall time over every request retired in
the window (nearest rank)."""
from bench.stats import percentile


def read(run):
    return percentile(run.latencies, 50) if run.latencies else None
