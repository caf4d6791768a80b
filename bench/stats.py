"""Arithmetic of the end-to-end metrics, kept with the benchmark."""

from __future__ import annotations

import math
from typing import Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile of all values, linear between order statistics
    (numpy's default method)."""
    if not values:
        raise ValueError("quantile of no values")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must lie in [0, 1], got {q}")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(work: float, seconds: float) -> float:
    """Work completed over the whole window, per second."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return work / seconds
