"""Window arithmetic: rates and percentiles, kept with the
benchmark so every PR computes them the same way."""

from __future__ import annotations

import math
from typing import Sequence


def rate(work: float, seconds: float) -> float:
    """All the work over all the time it took."""
    if seconds <= 0:
        raise ValueError(f"a rate needs a positive time, got {seconds}")
    return work / seconds


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in [0, 100]) over every value: the
    smallest value with at least q% of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside [0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100 * len(ordered)))
    return ordered[rank - 1]
