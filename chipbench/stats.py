"""Small statistics the metric readers share."""

from __future__ import annotations

import math
from typing import Iterable


def nearest_rank(values: Iterable[float], q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with at
    least ``q`` percent of the values at or below it.  A failed request
    enters as ``math.inf`` and so can be the answer; no values give None."""
    v = sorted(values)
    if not v:
        return None
    return v[max(math.ceil(q / 100 * len(v)) - 1, 0)]
