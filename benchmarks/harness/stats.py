"""Percentile arithmetic over samples taken inside one run."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between the
    two nearest order statistics; raises on an empty sample, because a
    latency metric without samples must never read as 0."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile: a tail
    is worth reporting with at least ten (choosing-metrics, section 1)."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median: how the driver
    measures the run-to-run spread of a metric."""
    return (percentile(values, 75.0) - percentile(values, 25.0)) / median(values)
