"""Readers over scrapes, /health and the reduced trace."""

import json
from types import SimpleNamespace

import pytest

from chipbench import manifest
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


def test_the_whole_steps_share_of_the_chips_peak():
    """``decode_step_mfu``: the operations a step needs (the architecture's
    count at the mean context in flight x the live lanes a decode dispatch
    carried) over the measured step, against the bf16 peak."""
    from chipbench import peaks
    from chipbench.configs import model_fields

    ctx = _trace_ctx(megastep_k=8, trace_started_unix=100.0, trace_seconds=4.0)
    req = SimpleNamespace(max_tokens=1025, prompt="x" * 300)
    ctx.records = [SimpleNamespace(ok=True, first=90.0, finished=110.0, completion_tokens=1025,
                                   prompt_tokens=300 + 100 * i, cached_tokens=0, req=req)
                   for i in range(4)]
    lines = lambda lanes, n: {"worker": [  # noqa: E731
        f'dynamo_engine_decode_live_lanes_total{{service="engine"}} {lanes}\n'
        f'dynamo_scheduler_megastep_dispatches_total{{service="engine"}} {n}\n'], "frontend": []}
    ctx.scrape_open, ctx.scrape_close = lines(0, 0), lines(3000, 100)
    got = trace_reduce.read(ctx, "step_mfu", module="_megastep_body")
    # the slice's middle is 12 s into streams of 1,024 steps in 20 s: 1 + 614.4 sent
    context = int(sum(300 + 100 * i + 1 + 1024 * 12 / 20 for i in range(4)) / 4)
    flops = peaks.forward_flops_per_token(model_fields(ctx.config), context) * 30
    assert got == pytest.approx(100 * flops / 0.014 / 197e12)
    assert 5 < got < 20            # decode is bandwidth-bound: far from 100, never 0
    spec = json.loads(manifest.metric_file("per_layer", "decode_step_mfu.chat").read_text())
    assert spec["reader"] == "trace_reduce" and spec["args"]["stat"] == "step_mfu"
    # nothing to read: no counters (an untraced run scrapes none), no streams, no program
    ctx.scrape_open, ctx.scrape_close = {}, {}
    assert trace_reduce.read(ctx, "step_mfu", module="_megastep_body") is None
    ctx.scrape_open, ctx.scrape_close, ctx.records = lines(0, 0), lines(3000, 100), []
    assert trace_reduce.read(ctx, "step_mfu", module="_megastep_body") is None


def test_no_trace_no_number():
    ctx = _trace_ctx()
    ctx.trace = None
    assert trace_reduce.read(ctx, "idle_share") is None
