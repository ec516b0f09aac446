"""Metric arithmetic on plain lists. Standard library only."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (``q`` in 0..100) of a non-empty
    sample: position ``q/100 * (n - 1)`` of the sorted values."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    pos = q / 100.0 * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def supported_tail(n: int) -> Optional[int]:
    """The highest of 99/95/90 that has ten samples beyond it."""
    for q in (99, 95, 90):
        if n * (100 - q) / 100.0 >= 10:
            return q
    return None


def iqr_share(values: Sequence[float]) -> float:
    """Distance between first and third quartile as a share of the
    median — the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def mean_gap_ms(stamps: Sequence[float]) -> Optional[float]:
    """A request's mean gap between output tokens, ``(t_last - t_first)
    / (n - 1)`` in ms; tokens arrive in bursts, so single gaps are not
    the metric. ``None`` for fewer than two tokens."""
    if len(stamps) < 2:
        return None
    return (stamps[-1] - stamps[0]) * 1e3 / (len(stamps) - 1)


def summary(values: Iterable[float]) -> Dict[str, float]:
    xs: List[float] = list(values)
    if not xs:
        return {"n": 0}
    out = {"n": len(xs), "max": max(xs), "mean": sum(xs) / len(xs)}
    out.update({f"p{q}": percentile(xs, q) for q in (50, 90, 95)})
    tail = supported_tail(len(xs))
    if tail:
        out[f"p{tail}"] = percentile(xs, tail)
    return out
