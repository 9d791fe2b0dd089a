"""Order statistics over all samples (never medians of chunks)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of every value, interpolating
    linearly between the two nearest ranks (numpy's default method)."""

    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    r = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(r), math.ceil(r)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)
