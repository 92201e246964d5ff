"""The step clock's account of the device (ISSUE 37): a record per
dispatch from the readings the clock takes anyway: device seconds by
kind, the seconds the device had nothing queued (a lower and an upper
bound, by phase and by the kind of dispatch before them) and the
lane-seconds decode-ready lanes spent decoding, behind a wave and behind
the host. A fake ``perf_counter_ns`` and a fake readiness drive the clock
alone; the tiny engine shows that every loop closes every record."""

import time

import pytest

from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.tracing import stepclock
from dynamo_tpu.tracing.stepclock import KINDS, LANE_STATES, PHASES, StepClock

pytestmark = [pytest.mark.unit]

CFG = tiny_model()


class FakeTime:
    """Stands in for the ``time`` module inside ``stepclock``: the test
    sets the reading every boundary takes."""

    def __init__(self):
        self.now = 0

    def perf_counter_ns(self) -> int:
        return self.now

    def time(self) -> float:
        return 0.0


class Out:
    """A device output that becomes ready at ``finish`` on the fake clock."""

    def __init__(self, fake: FakeTime, finish: int):
        self.fake, self.finish, self.polls = fake, finish, 0

    def is_ready(self) -> bool:
        self.polls += 1
        return self.fake.now >= self.finish


@pytest.fixture
def fake(monkeypatch):
    fake = FakeTime()
    monkeypatch.setattr(stepclock, "time", fake)
    return fake


class Script:
    """A clock driven boundary by boundary at stated readings."""

    def __init__(self, fake: FakeTime):
        self.fake, self.clock = fake, StepClock()

    def at(self, t: int, phase: str) -> None:
        self.fake.now = t
        if phase == "step_begin":
            self.clock.step_begin()
        elif phase in ("between_steps", "no_work"):
            self.clock.step_end(phase == "between_steps")
        else:
            self.clock.mark(phase)

    def dispatch(self, no, kind, t_begin, t_enq, finish, carried=0, waiting=0) -> Out:
        self.fake.now = t_begin
        self.clock.dispatch_begin(no, kind, carried, waiting)
        out = Out(self.fake, finish)
        self.fake.now = t_enq
        self.clock.mark("plan")           # the jitted call returned
        self.clock.in_flight(no, out)
        return out

    def land(self, no, t_begin, t_fetched) -> None:
        self.at(t_begin, "land")
        self.fake.now = t_fetched
        self.clock.landed(no)

    def ns(self) -> dict:
        acc = self.clock.account()
        return {
            "device": {k: round(v * 1e9) for k, v in acc["device_seconds"].items()},
            "late": acc["late_landings"],
            "starved": {k: round(v * 1e9) for k, v in acc["starved_seconds"].items() if v},
            "lanes": {k: round(v * 1e9) for k, v in acc["lane_seconds"].items()},
        }


def bound(ns: dict, which: str) -> int:
    return sum(v for (b, _, _), v in ns["starved"].items() if b == which)


def test_a_landing_that_waited_gives_the_exact_finish(fake):
    s = Script(fake)
    s.at(0, "step_begin")
    s.at(100, "plan")
    s.dispatch(1, "megastep", 800, 1000, finish=5000, carried=3)
    s.land(1, 2000, 5000)                 # not ready at 2000: the fetch waited
    s.at(5400, "between_steps")
    s.at(6000, "step_begin")
    s.at(6100, "plan")
    s.dispatch(2, "megastep", 6800, 7000, finish=11000, carried=3)
    s.land(2, 7100, 11000)
    got = s.ns()
    # 1: enqueue 1000 -> landing 5000. 2: starts at its enqueue (the device
    # was empty since 5000), lands at 11000.
    assert got["device"]["megastep"] == 4000 + 4000
    assert got["late"] == dict.fromkeys(KINDS, 0)
    assert bound(got, "lower") == bound(got, "upper") == 2000
    assert got["lanes"] == {"decode": 3 * 8000, "behind_prefill": 0, "behind_host": 3 * 2000}


def test_a_late_landing_brackets_the_finish_between_two_polls(fake):
    s = Script(fake)
    s.at(0, "step_begin")
    s.at(100, "plan")
    out = s.dispatch(1, "prefill", 800, 1000, finish=3500, waiting=2)
    s.at(2000, "between_steps")           # polled: not ready
    s.at(3000, "step_begin")              # not ready: the last such reading
    s.at(4000, "plan")                    # seen ready: the first such reading
    polls = out.polls
    s.at(4500, "assemble")
    s.at(5000, "h2d")
    assert out.polls == polls             # seen ready: polled no more
    s.dispatch(2, "megastep", 5500, 6000, finish=9000, carried=2)
    s.land(1, 6100, 6200)                 # did not wait
    s.land(2, 6300, 9000)
    got = s.ns()
    true_starved = 6000 - 3500
    assert got["late"]["prefill"] == 1 and got["late"]["megastep"] == 0
    assert bound(got, "lower") == 6000 - 4000 <= true_starved <= bound(got, "upper") == 6000 - 3000
    # A late landing counts its device seconds to the upper end of its finish.
    assert got["device"] == {"prefill": 4000 - 1000, "megastep": 9000 - 6000,
                             "decode": 0, "mixed": 0}
    assert got["lanes"]["behind_prefill"] == 2 * 3000
    assert got["lanes"]["behind_host"] == 2 * 3000       # the upper bound
    # by phase, after a prefill: the upper bound reaches back into the gap
    assert ("upper", "between_steps", "prefill") not in got["starved"]   # it ended at 3000
    assert got["starved"]["upper", "admit", "prefill"] == 1000
    assert got["starved"]["lower", "plan", "prefill"] == 500
    assert got["starved"]["lower", "dispatch", "prefill"] == 500


def test_not_ready_at_the_successors_enqueue_starved_nothing(fake):
    s = Script(fake)
    s.at(0, "step_begin")
    s.at(100, "plan")
    s.dispatch(1, "megastep", 800, 1000, finish=6050, carried=4)
    s.at(5000, "assemble")
    s.dispatch(2, "megastep", 5800, 6000, finish=11000, carried=4)   # 1 still runs
    s.at(6100, "admit")                   # a boundary sees 1 ready before its landing
    s.land(1, 6200, 6300)                 # did not wait
    s.land(2, 6400, 11000)
    got = s.ns()
    assert got["late"]["megastep"] == 1
    assert got["starved"] == {}
    # 1: 1000 -> 6100 (the upper end); 2 begins where 1 ended
    assert got["device"]["megastep"] == 5100 + (11000 - 6100)


def test_a_starved_interval_over_two_steps_is_split_by_phase_and_named_after(fake):
    s = Script(fake)
    s.at(0, "step_begin")
    s.at(100, "plan")
    s.dispatch(1, "prefill", 800, 1000, finish=5000, waiting=1)
    s.land(1, 1100, 5000)
    s.at(5500, "between_steps")           # commit 5000-5500
    s.at(6000, "step_begin")              # admit from 6000
    s.at(6200, "plan")
    s.at(6500, "assemble")
    s.at(6800, "h2d")
    s.dispatch(2, "decode", 7000, 7400, finish=9000, carried=1)
    s.land(2, 7500, 9000)
    s.at(9100, "no_work")                 # the engine goes idle
    s.at(20000, "step_begin")
    s.at(20100, "plan")
    s.dispatch(3, "prefill", 20500, 21000, finish=22000)
    s.land(3, 21100, 22000)
    got = s.ns()
    after_wave = {phase: got["starved"].get((b, phase, "prefill"), 0)
                  for b in ("upper",) for phase in PHASES}
    assert after_wave == {"no_work": 0, "between_steps": 500, "admit": 200, "plan": 300,
                          "assemble": 300, "h2d": 200, "dispatch": 400, "land": 0,
                          "commit": 500}
    # After the decode step: commit 9000-9100, then no_work (not starvation),
    # then the next step up to its enqueue.
    after_decode = {k[1]: v for k, v in got["starved"].items()
                    if k[0] == "lower" and k[2] == "decode"}
    assert after_decode == {"commit": 100, "admit": 100, "plan": 400, "dispatch": 500}
    assert bound(got, "lower") == bound(got, "upper") == 2400 + 1100
    assert all(phase != "no_work" for _, phase, _ in s.clock.account()["starved_seconds"])


def test_the_account_partitions_the_time_the_engine_had_work(fake):
    """A few hundred scripted steps, pipelined, waited and late landings
    mixed: busy + starved (lower) never exceeds the time with work; lower
    <= upper; the lane states add up between the two bounds."""
    import random

    rng = random.Random(37)
    s = Script(fake)
    lanes, t, no, inflight = 5, 0, 0, None
    first_enq = None
    for _ in range(300):
        s.at(t, "step_begin")
        t += rng.randint(50, 400)
        s.at(t, "plan")
        t += rng.randint(100, 3000)
        kind = rng.choice(["prefill", "megastep", "megastep", "decode"])
        carried = 0 if kind == "prefill" else lanes
        no += 1
        begin = t
        t += rng.randint(50, 300)
        out = s.dispatch(no, kind, begin, t, finish=0, carried=carried,
                         waiting=lanes - carried)
        first_enq = first_enq or t
        busy_from = max(t, inflight[1].finish if inflight else 0)
        out.finish = busy_from + rng.randint(500, 4000)
        if inflight is not None:
            t += rng.randint(10, 100)
            s.land(inflight[0], t, max(t + 5, inflight[1].finish))
            t = fake.now
        inflight = (no, out)
        t += rng.randint(50, 500)
        idle = rng.random() < 0.1
        if idle:                          # drain the pipeline, then go idle
            s.at(t, "between_steps")
            t += 20
            s.at(t, "step_begin")
            t += 20
            s.land(inflight[0], t, max(t + 5, inflight[1].finish))
            t = fake.now + 30
            inflight = None
        s.at(t, "no_work" if idle else "between_steps")
        t += rng.randint(20, 200) if not idle else rng.randint(1000, 9000)
    if inflight is not None:
        s.at(t, "step_begin")
        s.land(inflight[0], t + 10, max(t + 15, inflight[1].finish))
        s.at(fake.now + 10, "no_work")
    got = s.ns()
    seconds = {k: round(v * 1e9) for k, v in s.clock.seconds().items()}
    work = sum(seconds.values()) - seconds["no_work"]
    busy = sum(got["device"].values())
    lower, upper = bound(got, "lower"), bound(got, "upper")
    assert not s.clock._open and s.clock._opening is None
    assert sum(got["late"].values()) > 20           # both kinds of landing met
    assert lower <= upper
    assert busy + lower <= work
    # ... and misses only what lies outside the chain of dispatches: before
    # the first enqueue and after the last finish.
    assert busy + upper >= work - first_enq - 200
    # lanes: every lane is in one state from the first enqueue on
    assert sum(got["lanes"].values()) == lanes * (busy + upper)
    assert got["lanes"]["decode"] + got["lanes"]["behind_prefill"] == lanes * busy


def test_with_every_landing_waited_the_bounds_are_equal_and_lanes_add_up(fake):
    s = Script(fake)
    lanes, t = 4, 0
    first = None
    for no in range(1, 41):
        s.at(t, "step_begin")
        s.at(t + 100, "plan")
        kind = "prefill" if no % 5 == 0 else "megastep"
        carried = 0 if kind == "prefill" else lanes
        s.dispatch(no, kind, t + 600, t + 800, finish=t + 3000, carried=carried,
                   waiting=lanes - carried)
        first = first or t + 800
        s.land(no, t + 900, t + 3000)     # the classic loop: commit in place
        s.at(t + 3200, "between_steps")
        t += 3300
    got = s.ns()
    assert got["late"] == dict.fromkeys(KINDS, 0)
    assert {k[1:]: v for k, v in got["starved"].items() if k[0] == "lower"} == \
           {k[1:]: v for k, v in got["starved"].items() if k[0] == "upper"}
    last_finish = t - 3300 + 3000
    assert set(got["lanes"]) == set(LANE_STATES)
    assert sum(got["lanes"].values()) == lanes * (last_finish - first)
    assert got["lanes"]["behind_prefill"] == lanes * 8 * 2200


def test_polls_between_boundaries_tighten_the_bounds_a_millisecond_apart(fake):
    s = Script(fake)
    s.at(0, "step_begin")
    out = s.dispatch(1, "prefill", 100_000, 200_000, finish=7_300_000, waiting=1)
    s.at(300_000, "plan")                 # a long phase: the planner's loop polls
    before = out.polls
    for t in range(400_000, 9_000_000, 100_000):
        fake.now = t
        s.clock.poll()
    assert 7 <= out.polls - before <= 9   # one a millisecond until seen ready
    s.dispatch(2, "megastep", 9_000_000, 9_100_000, finish=12_000_000, carried=1)
    s.land(1, 9_200_000, 9_300_000)
    s.land(2, 9_400_000, 12_000_000)
    got = s.ns()
    lower, upper = bound(got, "lower"), bound(got, "upper")
    assert lower <= 9_100_000 - 7_300_000 <= upper
    assert upper - lower <= 1_000_000     # as wide as `plan` (8.7 ms) without them
    assert got["starved"]["upper", "plan", "prefill"] > 0


def test_a_dispatch_driven_from_outside_a_step_keeps_no_record():
    clock = StepClock()
    clock.dispatch_begin(1, "decode", 1, 0)
    clock.in_flight(1, object())
    clock.landed(1)
    assert clock.phase is None and not clock._open and clock._opening is None
    assert sum(clock.account()["device_seconds"].values()) == 0


def test_the_account_costs_under_a_microsecond_a_boundary():
    """A boundary with a dispatch in flight adds one ``is_ready()`` and a
    mark kept; a dispatch adds a record, opened and closed. Best of 5."""

    class Never:
        def is_ready(self):
            return False

    def boundaries(clock, n):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n // 2):
                clock.mark("plan")
                clock.mark("assemble")
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    n = 20_000
    clock = StepClock()
    clock.step_begin()
    empty = boundaries(clock, n)
    clock.dispatch_begin(1, "megastep", 8, 0)
    clock.mark("plan")
    clock.in_flight(1, Never())
    polling = boundaries(clock, n)
    clock.mark("land")
    clock.landed(1)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for no in range(2, 2002):
            clock.dispatch_begin(no, "megastep", 8, 0)
            clock.mark("plan")
            clock.in_flight(no, None)
            clock.mark("land")
            clock.landed(no)
        best = min(best, (time.perf_counter() - t0) / 2000)
    bare = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for no in range(2000):
            clock.mark("dispatch", kind="megastep")
            clock.mark("plan")
            clock.mark("land")
            clock.mark("commit")
        bare = min(bare, (time.perf_counter() - t0) / 2000)
    clock.step_end(False)
    print(f"boundary {empty * 1e9:.0f} ns, with a poll {polling * 1e9:.0f} ns; "
          f"a dispatch's record {1e6 * (best - bare):.2f} us over its four boundaries")
    assert empty < 3e-6 and polling - empty < 1e-6, (empty, polling)
    assert best - bare < 2e-5, (best, bare)


# -- the engine's loops close every record -------------------------------------------


def _req(prompt, rid, max_tokens=8, spec_decode=None):
    return PreprocessedRequest(
        model="tiny", token_ids=prompt, request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        spec_decode=spec_decode,
    )


def _drive(core, seqs):
    for _ in range(8000):
        core.step()
        if all(s.finish is not None for s in seqs) and not core.has_work():
            return
    raise AssertionError("the tiny engine did not finish")


def _closings(core, monkeypatch) -> list:
    closed = []
    real = core.clock._close

    def close(d, now):
        closed.append(d.no)
        return real(d, now)

    monkeypatch.setattr(core.clock, "_close", close)
    return closed


@pytest.mark.parametrize("name, engine_kw, requests", [
    ("classic", dict(async_exec=False, megastep_k=8),
     [(list(range(1, 12)), 17), (list(range(3, 14)), 17)]),
    ("pipelined", dict(async_exec=True, megastep_k=8),
     [(list(range(1, 12)), 17), (list(range(3, 14)), 17)]),
    ("drain", dict(num_kv_blocks=12, max_model_len=64, async_exec=True,
                   scheduling="chunked", prefill_chunk=16, megastep_k=1),
     [(list(range(1, 17)), 24), (list(range(20, 36)), 24), (list(range(40, 80)), 8)]),
    ("merged", dict(async_exec=True, spec_decode="ngram", spec_k=4, megastep_k=1),
     [([3, 4, 5, 3, 4, 5, 3, 4], 40), (list(range(1, 30)), 24)]),
])
def test_every_loop_closes_every_record(name, engine_kw, requests, monkeypatch):
    core = EngineCore(CFG, tiny_engine(**engine_kw), seed=0)
    closed = _closings(core, monkeypatch)
    merged = []
    real_merge = core._merge_plans
    monkeypatch.setattr(core, "_merge_plans",
                        lambda parts: merged.append(len(parts)) or real_merge(parts))
    # "merged": the second request opts out of speculation, so a plan holds
    # a verify dispatch for the first and a decode chain for the second.
    seqs = [core.add_request(_req(p, f"r{i}", n, {"method": "off"}
                                  if name == "merged" and i else None))
            for i, (p, n) in enumerate(requests)]
    _drive(core, seqs)
    st = core.exec_stats
    assert closed == list(range(1, st["dispatches"] + 1)), name
    assert not core.clock._open and core.clock._opening is None
    if name == "drain":
        assert st["drains"] >= 1
    if name == "merged":
        assert max(merged) == 2           # a verify dispatch and a chain in one plan
    acc = core.device_account()
    lower = sum(v for (b, _, _), v in acc["starved_seconds"].items() if b == "lower")
    upper = sum(v for (b, _, _), v in acc["starved_seconds"].items() if b == "upper")
    seconds = core.clock.seconds()
    work = sum(seconds.values()) - seconds["no_work"]
    assert 0 < sum(acc["device_seconds"].values()) + lower <= work * 1.0001
    assert lower <= upper
    assert st["decode_tokens_committed"] == st["committed_tokens"] - len(seqs)


def test_an_engine_runs_counters_add_up_to_its_wall_time():
    """The classic loop, compiled before the window: between two reads the
    device's seconds and the seconds it starved cover the time the engine
    had work, from both sides."""
    core = EngineCore(CFG, tiny_engine(async_exec=False, megastep_k=8), seed=0)

    def run(tag):
        seqs = [core.add_request(_req(list(range(i + 1, i + 12)), f"{tag}{i}", 33))
                for i in range(3)]
        _drive(core, seqs)

    run("warm")
    a, sa = core.device_account(), core.clock.seconds()
    t0 = time.perf_counter()
    run("w")
    wall = time.perf_counter() - t0
    b, sb = core.device_account(), core.clock.seconds()

    def delta(key, pick=lambda k: True):
        return sum(v - a[key][k] for k, v in b[key].items() if pick(k))

    busy = delta("device_seconds")
    lower = delta("starved_seconds", lambda k: k[0] == "lower")
    upper = delta("starved_seconds", lambda k: k[0] == "upper")
    work = sum(sb.values()) - sum(sa.values()) - (sb["no_work"] - sa["no_work"])
    assert work <= wall * 1.001
    # (a starved interval counts when the dispatch after it lands: the one
    # that straddles the first read began a few steps before it)
    assert busy + lower <= work + 0.005
    # what follows the last landing (its commit, the step's exit) is the
    # only time with work that no interval covers
    assert busy + upper >= 0.9 * work, (busy, lower, upper, work)
    # three lanes at most, and decoding whenever a megastep ran
    assert 3 * delta("device_seconds", lambda k: k == "megastep") == pytest.approx(
        delta("lane_seconds", lambda k: k == "decode"))
    assert delta("lane_seconds") <= 3 * work * 1.0001


@pytest.mark.parametrize("async_exec", [False, True], ids=["classic", "pipelined"])
def test_lane_seconds_are_the_streams_own_first_to_last_intervals(async_exec):
    """A closed loop of four streams on the tiny engine, refilled as they
    end: between two reads the three lane states add up to what the streams
    themselves saw, the sum over streams of (last chunk - first chunk). A
    lane counted while the step in flight ends it (the planner's list still
    holds it) read 9% over on the one-step-ahead loop."""
    import random

    rng = random.Random(1)
    core = EngineCore(CFG, tiny_engine(async_exec=async_exec, megastep_k=8,
                                       num_kv_blocks=256), seed=0)
    first, last, sent = {}, {}, [0]

    def add():
        sent[0] += 1
        prompt = [rng.randrange(1, 200) for _ in range(rng.randrange(20, 60))]
        core.add_request(_req(prompt, f"r{sent[0]}", 8 * rng.randrange(4, 12) + 1))

    for _ in range(4):
        add()
    done, a, t0 = 0, None, 0.0
    while done < 60:
        outs = core.step()
        now = time.perf_counter()
        for seq, out in outs:
            first.setdefault(seq.request_id, now)
            last[seq.request_id] = now
            if out.finish_reason:
                done += 1
                add()
        if done >= 12 and a is None:        # compiles are over
            a, t0 = core.device_account()["lane_seconds"], now
    b, t1 = core.device_account()["lane_seconds"], time.perf_counter()
    lanes = sum(b.values()) - sum(a.values())
    streams = sum(max(0.0, min(last[r], t1) - max(first[r], t0)) for r in first)
    assert lanes == pytest.approx(streams, rel=0.03), (b, a)
    assert b["behind_prefill"] > a["behind_prefill"] and b["decode"] > a["decode"]


def test_the_account_is_on_metrics_typed_and_labelled():
    from chipbench.readers import prometheus
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters

    core = EngineCore(CFG, tiny_engine(megastep_k=8), seed=0)
    _drive(core, [core.add_request(_req(list(range(1, 12)), "m", 17))])
    registry = MetricsRegistry()
    registry.registry.register(_EngineCounters(
        core.step_phase_seconds, core.scheduler_stats, core.device_account))
    text = registry.render().decode()
    series = prometheus.parse(text)
    for name in ("device_seconds", "late_landings", "device_starved_seconds",
                 "lane_seconds", "decode_tokens_committed"):
        assert f"# TYPE dynamo_engine_{name}_total counter" in text, name
    kinds = {lab["kind"] for n, lab, _ in series if n == "dynamo_engine_device_seconds_total"}
    assert kinds == set(KINDS)
    starved = [lab for n, lab, _ in series
               if n == "dynamo_engine_device_starved_seconds_total"]
    # every series is there from the start, so that a window's two scrapes
    # always have a difference to take
    assert len(starved) == 2 * (len(PHASES) - 1) * len(KINDS)
    assert {lab["bound"] for lab in starved} == {"lower", "upper"}
    assert {lab["phase"] for lab in starved} == set(PHASES) - {"no_work"}
    assert {lab["after"] for lab in starved} == set(KINDS)
    states = {lab["state"]: v for n, lab, v in series
              if n == "dynamo_engine_lane_seconds_total"}
    assert set(states) == set(LANE_STATES) and states["decode"] > 0
    acc = core.device_account()
    assert prometheus.total([text], "dynamo_engine_device_seconds_total") == \
        pytest.approx(sum(acc["device_seconds"].values()))
    assert prometheus.total([text], "dynamo_engine_decode_tokens_committed_total") == 16


def test_token_account_prints_the_account_between_two_scrapes(tmp_path, capsys, monkeypatch):
    import sys

    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters
    from tools import token_account

    core = EngineCore(CFG, tiny_engine(megastep_k=8), seed=0)
    registry = MetricsRegistry()
    registry.registry.register(_EngineCounters(
        core.step_phase_seconds, core.scheduler_stats, core.device_account))
    _drive(core, [core.add_request(_req(list(range(1, 12)), "a", 17))])
    before, acc0 = registry.render().decode(), core.device_account()
    _drive(core, [core.add_request(_req(list(range(2, 40)), f"b{i}", 25)) for i in range(3)])
    after, acc1 = registry.render().decode(), core.device_account()
    got = token_account.account(before, after)
    assert got["decode_tokens"] == 3 * 24 and got["dispatches"] == 4
    lanes = {k: acc1["lane_seconds"][k] - acc0["lane_seconds"][k] for k in LANE_STATES}
    assert got["decode_ms_per_token"] == pytest.approx(1e3 * lanes["decode"] / 72)
    assert got["sum_ms_per_token"] == pytest.approx(1e3 * sum(lanes.values()) / 72)
    assert got["device_starved_share_lower"] <= got["device_starved_share_upper"] <= 100
    assert set(got["device_seconds"]) == {"prefill", "megastep"}
    assert all("/" in k for k in got["starved_upper_s"])              # phase/after
    (tmp_path / "a.txt").write_text(before)
    (tmp_path / "b.txt").write_text(after)
    monkeypatch.setattr(sys, "argv", ["token_account", str(tmp_path / "a.txt"),
                                      str(tmp_path / "b.txt")])
    assert token_account.main() == 0
    printed = capsys.readouterr().out
    assert "a token: decode" in printed and "starved seconds (upper) by phase/after" in printed
    # a worker that keeps no account (or an idle one) says so
    monkeypatch.setattr(sys, "argv", ["token_account", str(tmp_path / "a.txt"),
                                      str(tmp_path / "a.txt")])
    assert token_account.main() == 1
