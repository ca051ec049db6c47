"""Order statistics of raw per-request samples."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]): a value that was
    measured, never an interpolation; ``inf`` samples rank last."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(math.ceil(q / 100 * len(v)) - 1, 0)]
