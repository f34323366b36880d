"""Order statistics over all samples of a run."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """Nearest-rank q-th percentile: the smallest sample whose rank is at
    least ceil(q/100 * n).  Raises on an empty sample."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]
