"""Median and quartile arithmetic, kept with the benchmark."""

from __future__ import annotations

from typing import Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated quantile (``q`` in [0, 1]) of a non-empty sample."""
    if not values:
        raise ValueError("quantile of an empty sample")
    xs = sorted(float(v) for v in values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return quantile(values, 0.5)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the driver's measure
    of run-to-run spread."""
    m = median(values)
    if m == 0:
        raise ValueError("spread is undefined for a zero median")
    return (quantile(values, 0.75) - quantile(values, 0.25)) / abs(m)
