"""Metric arithmetic of the benchmark: percentiles and windowed counts.

Kept with the yardstick so that every PR computes the same number the same
way. Nothing here imports JAX or the program.
"""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence

#: stands in for "slower than any" when a failed or refused request enters a
#: latency sample: it sorts after every real reading and is never printed.
MISSED = math.inf


def rank_of(n: int, q: float) -> int:
    """The nearest rank of the ``q``-th percentile among ``n`` readings,
    counted from 1."""
    return max(1, math.ceil(q / 100.0 * n))


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``q`` percent
    of the sample at or below it. ``None`` for an empty sample."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[rank_of(len(ordered), q) - 1]


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def beyond_rank(n: int, q: float) -> int:
    """How many of ``n`` readings lie beyond the ``q``-th percentile's rank.
    A percentile is judged only where this is at least ``MIN_BEYOND``: with
    four beyond it (the 41st of 45) ONE request that changes sides moves the
    reading to its neighbour, 55 ms off (PERF.md section 6, PR 37)."""
    return n - rank_of(n, q)


#: the metrics guide's rule: "the highest percentile that has at least ten
#: samples beyond it"
MIN_BEYOND = 10


def tokens_in_window(arrivals: Iterable[float], start: float, end: float) -> int:
    """Tokens whose arrival time lies in ``[start, end)``."""
    return sum(1 for t in arrivals if start <= t < end)


def tapered_tokens(
    arrivals: Iterable[float], start: float, end: float, edge: float
) -> float:
    """Tokens that arrived in ``[start, end)``, each weighted by a raised
    cosine that rises from 0 to 1 over the first ``edge`` seconds and falls
    over the last: the weights integrate to ``end - start - edge``. A fused
    decode delivers its tokens in bursts (16 a row, a tick of 0.3-0.6 s
    apart), and a hard edge counts a whole burst or none of it by where it
    falls: 64 tokens of 2,476 in a 45 s window of four rows, 2.6%. Under
    the taper a burst near an edge counts for little, and the count moves
    smoothly with the bursts' phase (under 0.01% for an even stream)."""
    total = 0.0
    for t in arrivals:
        if start <= t < end:
            x = min(t - start, end - t) / edge
            total += 1.0 if x >= 1.0 else 0.5 - 0.5 * math.cos(math.pi * x)
    return total


def time_per_output_token(first_t: float, last_t: float, n_tokens: int) -> float:
    """(last token time - first token time) / (tokens - 1): the stream's own
    pace once it has started. The gap between single tokens is not used: a
    16-step fused decode delivers 16 tokens at once."""
    return (last_t - first_t) / (n_tokens - 1)

