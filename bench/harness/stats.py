"""Latency and rate arithmetic."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values))

