"""Order statistics, copied from the program's
``observability/profiling.py:percentile`` (nearest rank, no interpolation)
so that no PR to the program can move a tail by changing the arithmetic."""

import math


def percentile(values, q):
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it.  ``None`` for an empty sample."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


def median(values):
    if not values:
        return None
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    return ordered[mid] if n % 2 else 0.5 * (ordered[mid - 1] + ordered[mid])
