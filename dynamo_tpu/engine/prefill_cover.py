"""Which compiled prefill waves should carry the waiting prompt tokens.

A prefill wave pads its tokens to a rung of ``EngineConfig.prefill_buckets``
and the ladder steps by four, so a wave that just misses a rung pays for
up to four times its tokens. A wave may end mid-prompt (the next one picks
the prompt up at its cursor through the paged cache) and the program is
compiled per rung, not per cursor, so T waiting tokens can ride any
multiset of rungs whose sum is at least T. :func:`cheapest_cover` picks the one
that is predicted to take the least time, from what the engine measured:
the milliseconds one wave of each rung took on this model and device
(warm-up times each compiled program once), and the milliseconds of host
work a dispatch has cost so far, under which no wave comes out.
"""

from __future__ import annotations

import functools
import math

# Predicted times closer than this are a tie, and a tie goes to fewer waves.
_TIE_MS = 1e-6


def cheapest_cover(
    tokens: int, table: tuple[tuple[int, float], ...], floor_ms: float
) -> tuple[int, ...]:
    """The buckets, largest first, of the waves predicted to prefill
    ``tokens`` tokens soonest.

    ``table`` holds ``(bucket, measured ms of one wave)`` pairs and
    ``floor_ms`` what the host needs per dispatch: a wave costs
    ``max(ms, floor_ms)``. The search is exact: a shortest path over the
    tokens still uncovered, in units of the buckets' common divisor (a
    sum of buckets covers ``tokens`` exactly when it covers ``tokens``
    rounded up to that unit, which is the memo's key). Of covers that
    cost the same the one with the fewest waves wins, so a prompt is cut
    only where the measurements say it pays, and one wave at the smallest
    bucket that holds ``tokens`` is always among the candidates. Empty
    for an empty table or nothing to cover."""
    if not table or tokens <= 0:
        return ()
    unit = math.gcd(*(b for b, _ in table))
    return _cover_units(-(-tokens // unit), unit, table, floor_ms)


@functools.lru_cache(maxsize=4096)
def _cover_units(
    n_units: int, unit: int, table: tuple[tuple[int, float], ...], floor_ms: float
) -> tuple[int, ...]:
    rungs = sorted(
        ((b // unit, max(ms, floor_ms), b) for b, ms in table), reverse=True
    )
    # best[n]: (ms, waves, the bucket taken first) to cover n units.
    best: list[tuple[float, int, int]] = [(0.0, 0, 0)]
    for n in range(1, n_units + 1):
        pick = None
        for units, ms, bucket in rungs:
            rest_ms, rest_waves, _ = best[max(0, n - units)]
            cand = (rest_ms + ms, rest_waves + 1, bucket)
            if (
                pick is None
                or cand[0] < pick[0] - _TIE_MS
                or (abs(cand[0] - pick[0]) <= _TIE_MS and cand[1] < pick[1])
            ):
                pick = cand
        best.append(pick)
    out = []
    n = n_units
    while n > 0:
        bucket = best[n][2]
        out.append(bucket)
        n = max(0, n - bucket // unit)
    return tuple(sorted(out, reverse=True))
