"""Percentile, TPOT, spread and tokens-in-window arithmetic; the readers
that sit on them."""

import statistics
from types import SimpleNamespace

import pytest

from chipbench import stats
from chipbench.generators.common import Request
from chipbench.loadgen import Record
from chipbench.readers import request_percentile, request_share, token_rate


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([5, 1, 3], 0, 1.0),
    ([5, 1, 3], 100, 5.0),
    (list(range(1, 101)), 90, 90.1),
    ([7.0], 99, 7.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None


@pytest.mark.parametrize("first,last,tokens,want", [
    (1.0, 2.0, 11, 100.0),
    (0.0, 0.144, 9, 18.0),
    (1.0, 1.0, 1, None),
])
def test_tpot(first, last, tokens, want):
    got = stats.tpot_ms(first, last, tokens)
    assert got is None if want is None else got == pytest.approx(want)


def test_spread_is_the_exclusive_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 102.5)


def test_tokens_in_window_sums_to_the_stream():
    # first token at 0.5 s, the other 32 evenly up to 2.5 s
    assert stats.tokens_in_window(0.5, 2.5, 33, 0.0, 3.0) == pytest.approx(33)
    assert stats.tokens_in_window(0.5, 2.5, 33, 1.0, 2.0) == pytest.approx(16)
    assert stats.tokens_in_window(0.5, 2.5, 33, 0.0, 1.5) == pytest.approx(17)
    assert stats.tokens_in_window(0.5, 2.5, 33, 2.5, 3.0) == 0.0
    assert stats.tokens_in_window(0.5, 0.5, 1, 0.0, 1.0) == 1.0
    parts = [stats.tokens_in_window(0.5, 2.5, 33, a, a + 0.25) for a in
             (0.25 * i for i in range(12))]
    assert sum(parts) == pytest.approx(33)


def _rec(due, sent, chunks, tokens, ok=True):
    r = Record(Request("x" * 10, tokens, 1, due=due), due_abs=due, sent=sent)
    if ok:
        r.first, r.finished = chunks[0], chunks[-1]
        r.status, r.finish, r.done = 200, "length", chunks[-1]
        r.completion_tokens = tokens
    else:
        r.status, r.error = 503, "shed"
    return r


@pytest.fixture
def ctx():
    records = [
        _rec(0.0, 0.001, [0.1, 0.2, 0.3], 17),          # ttft 100, tpot 12.5
        _rec(1.0, 1.002, [1.3, 1.5, 1.7, 1.9], 25),     # ttft 300, tpot 25
        _rec(2.0, 2.004, [2.2, 2.6], 9),                # ttft 200, tpot 50
        _rec(3.0, 3.0, [], 9, ok=False),
    ]
    return SimpleNamespace(measured=records, records=records, t_open=0.0, t_close=4.0)


def test_request_percentile_reader(ctx):
    assert request_percentile.read(ctx, "ttft_ms", 50) == pytest.approx(200.0)
    assert request_percentile.read(ctx, "tpot_ms", 50) == pytest.approx(25.0)
    assert request_percentile.read(ctx, "late_ms", 100) == pytest.approx(4.0)


def test_request_share_counts_a_failure_as_a_miss(ctx):
    assert request_share.read(ctx, ttft_ms_max=250, tpot_ms_max=30) == pytest.approx(25.0)
    assert request_share.read(ctx, ttft_ms_max=2000, tpot_ms_max=50) == pytest.approx(75.0)


def test_token_rate_counts_every_token_delivered_in_the_window(ctx):
    assert token_rate.read(ctx) == pytest.approx((17 + 25 + 9) / 4.0)
