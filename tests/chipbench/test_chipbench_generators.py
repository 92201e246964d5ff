"""Traffic generators: low-discrepancy draws, open-loop due times, sessions."""

import random
import statistics

import pytest

from chipbench import generators
from chipbench.generators import common, open_requests, open_sessions

SEEDS = (1, 2, 3000000019)
MIXES = ["decode-batch", "chat-steady", "tiny-closed", "tiny-open", "tiny-sessions"]


@pytest.mark.parametrize("dist", [
    {"dist": "uniform", "lo": 10, "hi": 20},
    {"dist": "loguniform", "lo": 128, "hi": 2048},
    {"dist": "exponential", "mean": 4.0},
    {"dist": "exponential", "mean": 2.5, "shift": 1.5},
    {"dist": "const", "value": 7},
])
def test_quantiles_are_the_distribution(dist):
    xs = common.quantiles(dist, 2000)
    assert xs == sorted(xs)
    want = {"uniform": 15.0, "exponential": dist.get("mean", 0) + dist.get("shift", 0),
            "const": 7.0,
            "loguniform": (2048 - 128) / 2.772588722239781}[dist["dist"]]
    assert statistics.fmean(xs) == pytest.approx(want, rel=0.03)


def test_loguniform_mean_of_the_chat_mix_is_as_the_issue_says():
    mix = generators.load_traffic("chat-steady")
    assert statistics.fmean(common.quantiles(mix["prompt_tokens"], 1000)) == pytest.approx(692, abs=5)
    assert statistics.fmean(common.quantiles(mix["output_tokens"], 1000)) == pytest.approx(173, abs=3)


def test_draw_permutes_and_keeps_the_multiset():
    spec = {"dist": "loguniform", "lo": 32, "hi": 512}
    a = common.draw(spec, 100, random.Random(1))
    b = common.draw(spec, 100, random.Random(2))
    assert a != b and sorted(a) == sorted(b)


def test_draw_ints_quantum():
    xs = common.draw_ints({"dist": "uniform", "lo": 3, "hi": 600}, 300, random.Random(0),
                          quantum=8, plus=1)
    assert all(x % 8 == 1 and x >= 9 for x in xs)


def test_text_is_one_byte_per_token_and_unshared():
    rng = random.Random(5)
    a, b = common.text_of(500, rng), common.text_of(500, rng)
    assert len(a.encode()) == len(b.encode()) == 500
    assert a[:32] != b[:32]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    traffic = generators.load_traffic(mix)
    plans = [generators.generate(traffic, s, 20.0) for s in SEEDS]
    first = plans[0]
    assert generators.generate(traffic, SEEDS[0], 20.0).all_requests() == first.all_requests()
    for p in plans[1:]:
        assert p.loop == first.loop and p.ramp_seconds == first.ramp_seconds
        a, b = first.all_requests(), p.all_requests()
        if traffic["kind"] != "open_sessions":
            assert len(a) == len(b)
            assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
        if traffic["kind"] == "open_requests":
            assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in b)
        assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("mix", ["chat-steady", "tiny-open"])
@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_due_times(mix, seed):
    traffic = generators.load_traffic(mix)
    seconds = 20.0
    plan = generators.generate(traffic, seed, seconds)
    due = [r.due for r in plan.requests]
    assert due == sorted(due)
    assert min(due) >= -plan.ramp_seconds and max(due) < seconds
    inside = [d for d in due if d >= 0]
    assert len(inside) == round(traffic["rate"] * seconds)
    assert all(r.max_tokens % 8 == 1 for r in plan.requests)
    assert all(r.shared_tokens == 0 for r in plan.requests)


def test_arrivals_fill_the_span_exactly():
    ts = open_requests.arrivals({"dist": "exponential", "mean": 1.0}, 50, 10.0,
                                random.Random(3))
    assert len(ts) == 50 and 0 < ts[0] and ts[-1] < 10.0 and ts == sorted(ts)
    assert open_requests.arrivals({"dist": "exponential", "mean": 1.0}, 0, 10.0,
                                  random.Random(3)) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_session_structure_and_shared_share(seed):
    traffic = generators.load_traffic("tiny-sessions")
    seconds = 30.0
    plan = generators.generate(traffic, seed, seconds)
    by_session = {}
    for r in plan.requests:
        by_session.setdefault(r.session, []).append(r)
    full = [turns for turns in by_session.values() if len(turns) == traffic["turns"]]
    assert full, "no session ran all its turns"
    for turns in by_session.values():
        doc_len = turns[0].shared_tokens or turns[-1].shared_tokens
        assert turns[0].shared_tokens == 0            # a session's first turn is a miss
        doc = turns[0].prompt[:doc_len] if doc_len else None
        for a, b in zip(turns, turns[1:]):
            assert 0.5 <= b.due - a.due                 # think time is shifted by 0.5 s
            assert b.shared_tokens == doc_len and b.prompt.startswith(doc)
            assert 64 <= doc_len <= 128
    # two of three prompts begin with a document sent before: the shared
    # share of prompt tokens is a little under 2/3 of the document's share
    share = open_sessions.expected_shared_share(plan)
    assert 0.45 < share < 0.66
    inside = [r for r in plan.requests if 0 <= r.due < seconds]
    # one miss per session begun inside the window, for every seed
    assert sum(r.shared_tokens == 0 for r in inside) == round(traffic["session_rate"] * seconds)


def test_the_seed_moves_which_late_turns_are_cut():
    traffic = generators.load_traffic("tiny-sessions")
    counts = {len([r for r in generators.generate(traffic, s, 10.0).requests if r.due >= 0])
              for s in range(8)}
    assert len(counts) > 1


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_clients_are_staggered(seed):
    traffic = generators.load_traffic("decode-batch")
    plan = generators.generate(traffic, seed, 45.0)
    assert len(plan.clients) == 32 and all(len(c) == 8 for c in plan.clients)
    firsts = sorted(c[0].max_tokens for c in plan.clients)
    # residual lengths of the first requests spread over (0, full length)
    assert firsts[0] < 200 and firsts[-1] > 700
    assert all(r.max_tokens % 8 == 1 for c in plan.clients for r in c)
    assert all(256 <= len(r.prompt) <= 512 for c in plan.clients for r in c)
    later = [r.max_tokens for c in plan.clients for r in c[1:]]
    assert 768 <= min(later) and max(later) <= 1537
