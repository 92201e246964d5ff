"""The engine loop's step clock (ISSUE 24): phases that partition the
wall time, their counters on /metrics, occupancy counters, the repaired
stat-span path, and model sections that change op metadata only."""

import contextlib
import secrets
import time

import jax
import numpy as np
import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.tracing.stepclock import PHASES, StepClock

pytestmark = [pytest.mark.unit]

CFG = tiny_model()


def _req(prompt, rid, max_tokens=8):
    return PreprocessedRequest(
        model="tiny", token_ids=prompt, request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


def _run(core, n_requests=3, max_tokens=17, pause_s=0.0):
    seqs = [
        core.add_request(_req(list(range(i + 1, i + 12)), f"r{i}", max_tokens))
        for i in range(n_requests)
    ]
    finished = set()
    for _ in range(4000):
        for s, out in core.step():
            if out.finish_reason:
                finished.add(s.request_id)
        if pause_s:
            time.sleep(pause_s)
        if len(finished) == len(seqs) and not core.has_work():
            return
    raise AssertionError("the tiny engine did not finish")


@pytest.fixture(scope="module")
def ran_core():
    """One tiny engine that has served a few requests on the loop it
    chooses itself: pipelined."""
    core = EngineCore(CFG, tiny_engine(megastep_k=8), seed=0)
    assert core.pipelined
    _run(core)
    return core


# -- the phases partition the wall time -----------------------------------------


@pytest.mark.parametrize("async_exec", [True, False], ids=["pipelined", "synchronous"])
def test_phase_seconds_add_up_to_the_wall_time(async_exec):
    core = EngineCore(CFG, tiny_engine(async_exec=async_exec, megastep_k=8), seed=0)
    t0 = time.perf_counter()
    _run(core, pause_s=0.002)        # between_steps has something to count
    time.sleep(0.05)                 # and no_work, still running at the read
    seconds = core.clock.seconds()
    wall = time.perf_counter() - t0
    assert set(seconds) == set(PHASES)
    assert abs(sum(seconds.values()) - wall) < 0.02 * wall, (seconds, wall)
    # Every phase of the table was entered (land only where a fetch blocks).
    for phase in ("between_steps", "no_work", "admit", "plan", "assemble",
                  "h2d", "dispatch", "land", "commit"):
        assert seconds[phase] > 0.0, phase
    assert seconds["no_work"] >= 0.05
    assert core.clock.phase == "no_work"


def test_step_clock_boundary_is_under_three_microseconds():
    """One phase boundary: a clock read, a counter add and an annotation
    closed and opened, with no profile running. Best of 5 over 20k."""
    clock = StepClock()
    clock.step_begin()
    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n // 2):
            clock.mark("plan")
            clock.mark("assemble")
        best = min(best, (time.perf_counter() - t0) / n)
    clock.step_end(False)
    assert best < 3e-6, f"a phase boundary took {best * 1e9:.0f} ns"


def test_marks_outside_a_step_keep_no_time():
    clock = StepClock()
    clock.mark("plan")
    assert clock.phase is None and sum(clock.seconds().values()) == 0.0


# -- counters on /metrics ----------------------------------------------------------


def test_every_counter_and_label_is_on_metrics(ran_core):
    from chipbench.readers import prometheus
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import ENGINE_COUNTERS, _EngineCounters

    registry = MetricsRegistry()
    registry.registry.register(
        _EngineCounters(ran_core.step_phase_seconds, ran_core.scheduler_stats))
    text = registry.render().decode()
    assert "# TYPE dynamo_engine_step_phase_seconds_total counter" in text
    series = {(lab["phase"], lab["blocks"]): v for name, lab, v in prometheus.parse(text)
              if name == "dynamo_engine_step_phase_seconds_total"}
    assert set(series) == set(PHASES.items())
    assert all(v > 0 for v in series.values()), series
    host = prometheus.total([text], "dynamo_engine_step_phase_seconds_total",
                            {"blocks": "host"})
    assert host == pytest.approx(sum(v for (_, b), v in series.items() if b == "host"))
    stats = ran_core.exec_stats
    for key, (name, _) in ENGINE_COUNTERS.items():
        assert f"# TYPE dynamo_{name}_total counter" in text
        assert prometheus.total([text], f"dynamo_{name}_total") == stats[key], name
    assert stats["dispatches"] > 0 and stats["committed_tokens"] == 3 * 17
    # The one-step-ahead loop's own pair: every dispatch but the first
    # found a step in flight, and nothing forced the pipeline empty.
    assert "dynamo_engine_pipelined_dispatches_total" in text
    assert "dynamo_engine_pipeline_drains_total" in text
    assert 0 < stats["pipelined_dispatches"] == stats["dispatches"] - 1
    assert stats["drains"] == 0


def test_a_synchronous_engine_counts_no_pipelined_dispatch():
    core = EngineCore(CFG, tiny_engine(async_exec=False, megastep_k=8), seed=0)
    _run(core)
    assert core.exec_stats["dispatches"] > 0
    assert core.exec_stats["pipelined_dispatches"] == 0
    assert core.scheduler_stats()["async_exec"] == 0


def test_occupancy_counters_count_where_the_batch_is_built(ran_core):
    st = ran_core.exec_stats
    # 3 requests x (11-token prompt, 17 tokens): one prefill wave in one
    # bucket, then 2 megasteps of k = 8 over 3 live lanes.
    assert st["ragged_real_tokens"] == 3 * 11
    assert st["ragged_bucket_tokens"] >= st["ragged_real_tokens"]
    assert st["ragged_bucket_tokens"] in ran_core.engine.prefill_buckets
    assert st["decode_live_lanes"] == 2 * 3
    assert st["decode_padded_lanes"] == 2 * ran_core._decode_width(3)
    assert st["megastep_issued_lane_iters"] == 2 * 3 * 8
    assert st["megastep_useful_lane_iters"] == 3 * 16   # all but each prompt's first token


@pytest.mark.parametrize("engine_kw", [
    dict(scheduling="chunked", prefill_chunk=32),
    dict(spec_decode="ngram", spec_k=4),
    dict(scheduling="chunked", prefill_chunk=32, spec_decode="ngram", spec_k=4),
], ids=["chunked", "speculative", "chunked-speculative"])
def test_fused_megasteps_count_both_occupancy_pairs(engine_kw):
    """The universal megastep (prefill chunks and verify rows riding the
    scanned body) counts lanes where it builds the batch and
    lane-iterations where it commits, like the decode-only megastep."""
    core = EngineCore(CFG, tiny_engine(megastep_k=8, **engine_kw), seed=0)
    bait = [3, 4, 5, 3, 4, 5, 3, 4]                  # n-gram bait for drafts
    seqs = [core.add_request(_req(bait, "a", max_tokens=40))]
    for _ in range(3):                                # "a" is decoding when
        core.step()                                   # the long prompt arrives
    seqs.append(core.add_request(_req(list(range(1, 81)), "b", max_tokens=12)))
    for _ in range(4000):
        core.step()
        if all(s.finish is not None for s in seqs) and not core.has_work():
            break
    st = core.exec_stats
    assert st["fused_mixed_dispatches"] >= 1
    assert 0 < st["decode_live_lanes"] <= st["decode_padded_lanes"]
    assert 0 < st["megastep_useful_lane_iters"] <= st["megastep_issued_lane_iters"]
    # An iteration gives at least one token, and a verify row's may give more.
    assert st["megastep_useful_lane_iters"] <= st["committed_tokens"]
    assert st["megastep_issued_lane_iters"] <= 8 * st["decode_live_lanes"]


def test_a_window_between_two_reads_adds_up_too(ran_core):
    """What a scrape at a window's open and another at its close see
    (after the tests that count what the fixture's first run did)."""
    core = ran_core
    a, ta = core.clock.seconds(), time.perf_counter()
    _run(core, n_requests=2, max_tokens=9)
    b, tb = core.clock.seconds(), time.perf_counter()
    window = tb - ta
    assert abs(sum(b.values()) - sum(a.values()) - window) < 0.02 * window


# -- the stat-span path ---------------------------------------------------------------


def test_stat_record_mints_no_ids_and_resolves_its_histogram_once(monkeypatch):
    from dynamo_tpu.runtime import metrics as metrics_mod

    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    registry = metrics_mod.MetricsRegistry()
    collector.bind_metrics(registry)
    tracer = tracing.get_tracer("engine")
    tracer.record("engine_plan", 1.0, 1.5, stat=True)    # resolves the handle

    def no_ids(n):
        raise AssertionError("a stat span minted an id")

    lookups = []
    monkeypatch.setattr(secrets, "token_hex", no_ids)
    monkeypatch.setattr(metrics_mod.MetricsRegistry, "scoped",
                        lambda self, **kw: lookups.append(kw))
    for _ in range(10):
        tracer.record("engine_plan", 2.0, 2.25, attrs={"iteration": 1}, stat=True)
    assert lookups == []
    spans = collector.stats()
    assert len(spans) == 11 and spans[-1].attrs == {"iteration": 1}
    assert spans[-1].trace_id == "" and spans[-1].duration_s == 0.25
    assert collector.phase_totals()["engine/engine_plan"] == (11.0, pytest.approx(3.0))
    monkeypatch.undo()
    assert 'phase="engine_plan"' in registry.render().decode()
    collector.clear()


@pytest.mark.parametrize("rate, lo, hi", [(1.0, 2000, 2000), (0.25, 380, 620), (0.0, 0, 0)])
def test_stat_spans_are_sampled_at_the_root_span_rate(rate, lo, hi):
    """A stat span has no trace id to sample on, and still thins out
    with ``DYN_TRACE_SAMPLE`` as it did when it was minted one."""
    tracing.configure(enabled=True, sample=rate)
    collector = tracing.get_collector()
    collector.clear()
    tracer = tracing.get_tracer("engine")
    try:
        for _ in range(2000):
            tracer.record("engine_megastep", 1.0, 1.25, stat=True)
        kept = collector.phase_totals().get("engine/engine_megastep", (0.0, 0.0))[0]
        assert lo <= kept <= hi
    finally:
        tracing.configure(enabled=True, sample=1.0)
        collector.clear()


def test_plan_and_commit_spans_come_from_the_clocks_readings():
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    core = EngineCore(CFG, tiny_engine(async_exec=True, megastep_k=8), seed=0)
    t0 = time.time()
    _run(core)
    t1 = time.time()
    by_name = {}
    for s in collector.stats():
        by_name.setdefault(s.name, []).append(s)
    assert by_name["engine_plan"] and by_name["engine_commit"]
    for s in by_name["engine_plan"] + by_name["engine_commit"]:
        assert t0 - 0.01 <= s.start_s <= s.end_s <= t1 + 0.01   # on the wall clock
    # The per-dispatch ``host_gap`` span is gone: the device's account is
    # kept from the same readings, and lies inside the same wall time.
    assert "host_gap" not in by_name
    acc = core.device_account()
    busy = sum(acc["device_seconds"].values())
    lower = sum(v for (b, _, _), v in acc["starved_seconds"].items() if b == "lower")
    assert 0 < busy and busy + lower <= (t1 - t0) * 1.001
    # Each lies inside the step clock's own account of those phases.
    seconds = core.clock.seconds()
    planning = sum(seconds[p] for p in ("plan", "assemble", "h2d", "dispatch"))
    assert sum(s.duration_s for s in by_name["engine_plan"]) <= planning * 1.001
    committing = seconds["land"] + seconds["commit"]
    assert sum(s.duration_s for s in by_name["engine_commit"]) <= committing * 1.001
    assert not {"engine_prefill_step", "engine_decode_step"} & by_name.keys()   # read by nothing: deleted
    collector.clear()


# -- model sections ---------------------------------------------------------------------


def _lowered_forward():
    from dynamo_tpu.engine.model import decode_tokens, init_cache, init_params

    engine = tiny_engine()
    params = init_params(jax.random.PRNGKey(0), CFG)
    cache = init_cache(CFG, engine)
    B = 4
    args = (
        params, cache, np.zeros(B, np.int32),
        np.zeros((B, engine.max_blocks_per_seq), np.int32),
        np.zeros(B, np.int32), np.ones(B, bool),
    )
    return jax.jit(lambda *a: decode_tokens(*a, CFG, engine)).lower(*args)


def test_named_scopes_change_op_metadata_only(monkeypatch):
    scoped = _lowered_forward()
    names = scoped.as_text(debug_info=True)
    for section in ("embed", "qkv", "kv_write", "attn", "o_proj", "mlp", "lm_head"):
        assert f"/{section}/" in names or f"{section}/" in names, section
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _lowered_forward()
    assert "qkv/" not in plain.as_text(debug_info=True)
    # The program the compiler (and the compile cache's key) sees is the
    # same text with or without the scopes.
    assert scoped.as_text() == plain.as_text()
