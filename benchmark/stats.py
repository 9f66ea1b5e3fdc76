"""Percentiles, spreads and the due-time arithmetic of an open loop."""
from __future__ import annotations

import math
from statistics import median  # noqa: F401  (re-exported: one import for readers)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it.  ``math.inf`` entries (a request that never
    answered) sort last, so enough of them push the tail to infinity."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def ttfts_ms(due, first_token):
    """Time to first token of every request due in the window, from when
    it was DUE (not from when the generator got round to sending it);
    ``None`` (never answered) counts as infinitely late."""
    return [math.inf if f is None else (f - d) * 1e3
            for d, f in zip(due, first_token)]


def gaps_ms(token_times):
    """All gaps between consecutive tokens of all requests, pooled."""
    out = []
    for times in token_times:
        out.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
    return out
