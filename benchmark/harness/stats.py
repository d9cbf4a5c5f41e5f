"""Percentile arithmetic, kept with the benchmark."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it.  No
    interpolation, so the result is always a time that was measured."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def intervals_ms(stamps):
    """Milliseconds between consecutive clock readings."""
    return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
