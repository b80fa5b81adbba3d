"""Small statistics shared by the harness and the metric readers (stdlib)."""
from __future__ import annotations

from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolation percentile (numpy's default), or None."""
    if not xs:
        return None
    ys = sorted(xs)
    i = (len(ys) - 1) * q / 100.0
    lo = int(i)
    hi = min(lo + 1, len(ys) - 1)
    return ys[lo] + (ys[hi] - ys[lo]) * (i - lo)


def mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None
