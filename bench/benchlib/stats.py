"""Percentiles and rates, in plain Python so that they read as stated."""
from __future__ import annotations

from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by linear interpolation between closest
    ranks (numpy's default): rank ``q/100 * (n-1)`` of the sorted values.
    None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    r = q / 100.0 * (len(xs) - 1)
    lo = int(r)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)


def rate(count: float, seconds: float) -> float:
    """Events per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds
