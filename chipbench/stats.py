"""Arithmetic on request records: percentiles, TTFT, TPOT, token rates,
and the spread the builder's instructions define."""

from __future__ import annotations

import math
import statistics


def percentile(values: list[float], q: float) -> float | None:
    """Linear-interpolated percentile ``q`` in [0, 100] (the "inclusive"
    method: p0 is the minimum, p100 the maximum). None for no values."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tpot_ms(first: float, last: float, tokens: int) -> float | None:
    """Time per output token after the first: (last - first) / (tokens - 1),
    in ms. None for a one-token answer."""
    if tokens < 2:
        return None
    return (last - first) * 1000.0 / (tokens - 1)


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)`` (exclusive method)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def tokens_in_window(first: float, finished: float, tokens: int,
                     t0: float, t1: float) -> float:
    """Output tokens of one finished stream delivered in ``[t0, t1)``.

    Only the first and the last token of a stream are timed (see
    ``loadgen.send``). The first token arrives at ``first``; the other
    ``tokens - 1`` are taken as evenly spread from there to ``finished``,
    which is how a decode lane receives them: one megastep's worth per
    dispatch, every lane of the batch in step."""
    total = 1.0 if t0 <= first < t1 else 0.0
    if tokens > 1 and finished > first:
        overlap = max(0.0, min(finished, t1) - max(first, t0))
        total += (tokens - 1) * overlap / (finished - first)
    return total
