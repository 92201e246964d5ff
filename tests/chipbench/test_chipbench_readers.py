"""Readers over scrapes, /health and the reduced trace."""

from types import SimpleNamespace

import pytest

from chipbench.readers import (
    health_field,
    prometheus,
    prometheus_ratio,
    tokens_per_dispatch,
    trace_reduce,
)

OPEN = """# HELP x
dynamo_trace_phase_duration_seconds_sum{phase="sched_admit",service="engine"} 1.0
dynamo_trace_phase_duration_seconds_count{phase="sched_admit",service="engine"} 10
dynamo_trace_phase_duration_seconds_sum{phase="sched_admit",service="sched"} 50.0
dynamo_kv_prefix_cache_admitted_hits_total{service="engine"} 5.0
dynamo_kv_prefix_cache_admitted_queries_total{service="engine"} 20.0
dynamo_scheduler_megastep_dispatches_total{service="engine"} 100.0
dynamo_scheduler_single_step_dispatches_total{service="engine"} 20.0
dynamo_engine_dispatches_per_token{service="engine"} 0.1
"""
CLOSE = """dynamo_trace_phase_duration_seconds_sum{phase="sched_admit",service="engine"} 4.0
dynamo_trace_phase_duration_seconds_count{phase="sched_admit",service="engine"} 40
dynamo_trace_phase_duration_seconds_sum{phase="sched_admit",service="sched"} 90.0
dynamo_kv_prefix_cache_admitted_hits_total{service="engine"} 85.0
dynamo_kv_prefix_cache_admitted_queries_total{service="engine"} 120.0
dynamo_scheduler_megastep_dispatches_total{service="engine"} 300.0
dynamo_scheduler_single_step_dispatches_total{service="engine"} 60.0
dynamo_engine_dispatches_per_token{service="engine"} 0.05
"""


@pytest.fixture
def ctx():
    return SimpleNamespace(scrape_open={"worker": [OPEN]}, scrape_close={"worker": [CLOSE]})


def test_parse_reads_labels_and_skips_comments():
    rows = prometheus.parse(OPEN)
    assert rows[0] == ("dynamo_trace_phase_duration_seconds_sum",
                       {"phase": "sched_admit", "service": "engine"}, 1.0)
    assert len(rows) == 8
    assert prometheus.total([OPEN], "nope") is None


def test_ratio_of_deltas_with_labels(ctx):
    got = prometheus_ratio.read(
        ctx, "worker", scale=1000.0,
        numerator={"name": "dynamo_trace_phase_duration_seconds_sum",
                   "labels": {"phase": "sched_admit", "service": "engine"}},
        denominator={"name": "dynamo_trace_phase_duration_seconds_count",
                     "labels": {"phase": "sched_admit", "service": "engine"}})
    assert got == pytest.approx(100.0)       # 3 s over 30 requests, in ms
    hit = prometheus_ratio.read(
        ctx, "worker", scale=100.0,
        numerator={"name": "dynamo_kv_prefix_cache_admitted_hits_total"},
        denominator={"name": "dynamo_kv_prefix_cache_admitted_queries_total"})
    assert hit == pytest.approx(80.0)


def test_ratio_with_nothing_to_read_is_nothing(ctx):
    assert prometheus_ratio.read(
        ctx, "frontend", numerator={"name": "a"}, denominator={"name": "b"}) is None


def test_tokens_per_dispatch_from_counters_and_the_cumulative_ratio(ctx):
    # open: 120 dispatches, 1200 tokens; close: 360 dispatches, 7200 tokens
    assert tokens_per_dispatch.read(ctx) == pytest.approx(6000 / 240)


def test_health_field_paths_and_ratios():
    ctx = SimpleNamespace(health_close=[
        {"startup": {"warmup_seconds": 66.0},
         "memory": [{"peak_bytes_in_use": 8, "bytes_limit": 16}]},
        {"startup": {"warmup_seconds": 70.0},
         "memory": [{"peak_bytes_in_use": 12, "bytes_limit": 16}]}])
    assert health_field.read(ctx, ["startup", "warmup_seconds"]) == 70.0
    assert health_field.read(ctx, ["memory", 0, "peak_bytes_in_use"],
                             over=["memory", 0, "bytes_limit"], scale=100.0) == 75.0
    assert health_field.read(ctx, ["startup", "nope"]) is None
    cpu = SimpleNamespace(health_close=[{"memory": [{"peak_bytes_in_use": None,
                                                     "bytes_limit": None}]}])
    assert health_field.read(cpu, ["memory", 0, "peak_bytes_in_use"],
                             over=["memory", 0, "bytes_limit"]) is None


def _trace_ctx(**harness):
    from chipbench.configs import load_config

    return SimpleNamespace(
        config=load_config("qwen2.5-7b-int8"), device_kind="TPU v5 lite",
        harness=harness, records=[], unix_minus_monotonic=0.0,
        scrape_open={}, scrape_close={},
        trace={"busy_s": 3.4, "window_s": 4.0, "devices": 1,
               "modules": {"_megastep_body": {"count": 30, "seconds": 3.36},
                           "_prefill_and_sample": {"count": 2, "seconds": 0.3}},
               "ops": [["_megastep_body/fusion_a", 2.0, 900],
                       ["_megastep_body/ragged_paged_attention_kernel_bf16", 1.0, 6720],
                       ["_prefill_and_sample/ragged_paged_attention_kernel", 0.2, 56]],
               "gaps": []})


def test_trace_stats():
    ctx = _trace_ctx(megastep_k=8)
    assert trace_reduce.read(ctx, "idle_share") == pytest.approx(15.0)
    step = trace_reduce.read(ctx, "module_ms", module="_megastep_body", per="megastep_k")
    assert step == pytest.approx(3360 / 30 / 8)                # 14 ms
    assert trace_reduce.read(ctx, "op_share", module="_megastep_body",
                             op="ragged_paged_attention") == pytest.approx(100 / 3)
    floor = trace_reduce.read(ctx, "weight_floor_share", module="_megastep_body")
    assert floor == pytest.approx(100 * 8.64 / 14.0, rel=0.01)
    assert trace_reduce.read(ctx, "module_ms", module="_no_such_program") is None
    # the worker's own megastep length, and no default where it is absent
    half = trace_reduce.read(_trace_ctx(megastep_k=4), "module_ms",
                             module="_megastep_body", per="megastep_k")
    assert half == pytest.approx(2 * step)
    with pytest.raises(KeyError):
        trace_reduce.read(_trace_ctx(), "module_ms", module="_megastep_body",
                          per="megastep_k")


def test_no_trace_no_number():
    ctx = _trace_ctx()
    ctx.trace = None
    assert trace_reduce.read(ctx, "idle_share") is None
