"""Percentile and window arithmetic, kept with the benchmark so that no later
PR to the program can move it. Plain Python on plain lists."""
from __future__ import annotations

import math
from typing import Iterable, List, Mapping, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0..100) by linear interpolation between the two
    closest ranks, as ``numpy.percentile`` computes it by default."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def mean_between(values: Sequence[float], q_lo: float, q_hi: float) -> float:
    """Mean of the values from the ``q_lo``-th to the ``q_hi``-th percentile,
    both ends included: a tail read from many samples, not from one rank."""
    lo, hi = percentile(values, q_lo), percentile(values, q_hi)
    inside = [v for v in values if lo <= v <= hi]
    return sum(inside) / len(inside)


def share_within(values: Sequence[float], limit: float) -> float:
    """Share of the values at or under ``limit``, 0..1."""
    if not values:
        raise ValueError("share of no values")
    return sum(1 for v in values if v <= limit) / len(values)


def times_to_first_token(due: Mapping[int, float],
                         first_token: Mapping[int, float],
                         gave_up: float) -> List[float]:
    """Time to first token of every request due: ``due`` maps a request to
    its arrival, ``first_token`` to its first token's stamp where the system
    returned one. A request without one counts as having waited until
    ``gave_up``, so a late or lost request stays in the tail."""
    return [first_token.get(rid, gave_up) - t for rid, t in due.items()]


def inter_token_gaps(token_times: Iterable[Sequence[float]]) -> List[float]:
    """All gaps between consecutive token times of one request, pooled over
    requests. A request with one token contributes none."""
    gaps: List[float] = []
    for times in token_times:
        gaps.extend(b - a for a, b in zip(times[:-1], times[1:]))
    return gaps


def count_in_window(token_times: Iterable[Sequence[float]], start: float,
                    end: float) -> int:
    """Tokens committed in [start, end)."""
    return sum(1 for times in token_times for t in times if start <= t < end)
