"""Order statistics of a run's step times."""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

#: percentiles a run may report besides the median, lowest first
LADDER = (75.0, 90.0, 95.0, 99.0, 99.9)
#: a percentile is reported only with this many samples beyond it
BEYOND = 10


def percentile(samples: Sequence[float], p: float) -> float:
    """The p-th percentile by linear interpolation between order
    statistics (numpy's default), in plain Python so that the rule is
    readable here."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    pos = (len(s) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)


def highest_percentile(n: int) -> Optional[float]:
    """The highest percentile of :data:`LADDER` that still has
    :data:`BEYOND` of ``n`` samples beyond it, or None when even the
    lowest has not."""
    best = None
    for p in LADDER:
        # in whole per-mille: 100 * (1 - 0.9) is not 10 in floating point
        if n * (1000 - round(10 * p)) >= 1000 * BEYOND:
            best = p
    return best


def tail(samples: Sequence[float]) -> Optional[Tuple[float, float]]:
    """``(percentile, value)`` of the highest reportable percentile."""
    p = highest_percentile(len(samples))
    return None if p is None else (p, percentile(samples, p))
