"""Order statistics used by the metric readers."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default "linear" method); None when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    if pos == lo:               # on a rank: no interpolation (inf-safe)
        return xs[lo]
    return xs[lo] + (xs[lo + 1] - xs[lo]) * (pos - lo)

