"""90th percentile (nearest rank) of admission-to-retirement wall time over
every request retired in the window."""
from bench.stats import percentile


def read(run):
    return percentile(run.latencies, 90) if run.latencies else None
