"""Tail arithmetic of the open-loop driver."""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``,
    which may hold ``math.inf`` for requests that never finished."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def with_misses(latencies: Iterable[float], misses: int) -> List[float]:
    """All requests of a window: the finished ones' latencies, and one
    ``inf`` for each request shed, refused or never finished."""
    return list(latencies) + [math.inf] * int(misses)


def gaps(walls_by_request: dict) -> List[float]:
    """Gaps between consecutive timestamps of the same request."""
    out = []
    for walls in walls_by_request.values():
        walls = sorted(walls)
        out += [b - a for a, b in zip(walls, walls[1:])]
    return out
