"""Arithmetic from client-side records to numbers: interpolated
quantiles and inter-token gaps inside a window."""
from __future__ import annotations

from typing import Iterable, List, Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """Quantile by linear interpolation between order statistics
    (position q * (n - 1)); raises on an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("quantile of an empty sample")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q={q} outside [0, 1]")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def gaps_in_window(token_times: Iterable[Sequence[float]], w0: float,
                   w1: float) -> List[float]:
    """Inter-token gaps, all requests pooled: a gap counts when its later
    token arrived inside [w0, w1)."""
    out = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if w0 <= b < w1:
                out.append(b - a)
    return out
