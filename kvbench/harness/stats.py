"""Order statistics for the benchmark's own numbers."""

from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as ``numpy.percentile`` gives it; None over no
    samples, so that a reader with nothing to read returns nothing."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def mean(values: Iterable[float]) -> Optional[float]:
    xs = list(values)
    return sum(xs) / len(xs) if xs else None
