"""Small statistics shared by the metric readers."""
from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0 < q <= 1) by nearest rank: the smallest value
    with at least a share ``q`` of the values at or below it. Infinite
    values (requests never served) count as the largest."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def in_window(run, t: float) -> bool:
    return run.t_open < t <= run.t_close
