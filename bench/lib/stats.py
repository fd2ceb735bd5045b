"""Order statistics and spreads used by every metric of the benchmark."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q`` quantile as an order statistic: ``sorted[ceil(q*n) - 1]``
    (numpy's ``inverted_cdf``). A tail read this way is always a value that
    was observed. ``None`` for no values; ``inf`` counts as a value."""
    if not values:
        return None
    s = sorted(values)
    return s[max(math.ceil(q * len(s)) - 1, 0)]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None

