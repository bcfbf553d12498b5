"""Percentiles and spreads, taken over every sample.

``percentile`` is the linear-interpolation rule (numpy's default), copied
from the program's ``repro.obs.metrics.percentile`` so that the yardstick
cannot move with the program.  ``spread`` is the distance between the first
and third quartile as ``statistics.quantiles(values, n=4)`` gives them, as a
share of the median: the measure the benchmark's bounds are set from.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(xs: Sequence[float], p: float) -> float:
    """Exact percentile of all of ``xs`` with linear interpolation;
    ``p`` in [0, 100]; NaN for no samples."""
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile p must be in [0, 100], got {p}")
    xs = sorted(xs)
    if not xs:
        return float("nan")
    rank = (len(xs) - 1) * (p / 100.0)
    lo, hi = math.floor(rank), math.ceil(rank)
    if lo == hi:
        return float(xs[lo])
    frac = rank - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


def spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with ``statistics.quantiles``' default method."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
