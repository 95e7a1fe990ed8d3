"""Statistics over raw samples: exact percentiles, whole-window rates."""
from __future__ import annotations

import math


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile (0-100) of every sample, by linear
    interpolation between the two nearest order statistics (numpy's
    default); never from buckets or from medians of parts."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, t0: float, t1: float) -> float:
    """``count`` over the whole window ``[t0, t1]``."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1}]")
    return count / (t1 - t0)


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
