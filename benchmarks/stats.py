"""The little arithmetic the readers share."""

from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100), interpolated between the two nearest
    ranks, of a non-empty sequence."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    rank = (len(ordered) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)
