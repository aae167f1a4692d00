"""Nearest-rank percentile, copied from the program's
``serving.continuous.percentile`` so the yardstick cannot drift with it."""
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100)."""
    if not xs:
        return 0.0
    ys = sorted(xs)
    return ys[min(len(ys) - 1, max(0, round(q / 100.0 * (len(ys) - 1))))]
