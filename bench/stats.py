"""Statistics over raw samples (no histograms, no chunk medians)."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of all samples, linear between the
    two nearest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[hi] == xs[lo]:       # exact rank, or equal (inf) pair
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

