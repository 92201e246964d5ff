"""The eleven set-up metrics that read the start-up clock (PR 52): data
files over readers that exist, each reading a number from what a real
``StartClock`` puts under ``/health`` ``startup.clock``."""

import importlib
import json
import time
from types import SimpleNamespace

import pytest

from chipbench import manifest
from chipbench.readers import read_metric

NAMES = (
    "start_to_serving_s", "worker_start_to_serving_s", "startup_process_s",
    "startup_backend_init_s", "startup_weights_s", "startup_cache_alloc_s",
    "startup_register_s", "warmup_trace_lower_s", "warmup_backend_s",
    "warmup_tiny_compile_s", "warmup_run_s",
)


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def ctx():
    """A run's context as the readers see it: the ``startup`` section of a
    clock walked through its stages on this CPU, one warm-up row with one
    event of each kind, and the harness's own number."""
    from dynamo_tpu.tracing.startclock import STAGES, StartClock

    clock = StartClock(time.perf_counter_ns() - 1_000_000, from_proc=True)
    for stage in STAGES[2:]:
        clock.mark(stage)
        if stage == "warmup":
            with clock.row("prefill T=512 sampled"):
                time.sleep(0.004)
                for kind, seconds in (("trace", 0.001), ("lower", 0.001), ("hit", 0.0),
                                      ("backend", 0.6), ("backend", 0.001)):
                    time.sleep(0.002)
                    clock.compile_event(kind, "_prefill_and_sample", seconds)
        time.sleep(0.001)
    clock.close()
    startup = {"build_seconds": 0.0, "clock": clock.snapshot()}
    return SimpleNamespace(
        health_close=[{"startup": json.loads(json.dumps(startup))}],
        harness={"setup_s": 100.0, "start_to_serving_s": 60.0})


def test_the_eleven_entries_are_sound_and_stand_last(man):
    assert manifest.problems(man) == []
    assert tuple(m["name"] for m in man["per_layer"][-11:]) == NAMES
    for m in man["per_layer"][-11:]:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "host_clock" if m["name"] == "start_to_serving_s"
                     else "program_span", "layer": "set-up", "moves": "setup_s"}


@pytest.mark.parametrize("name", NAMES)
def test_each_entry_reads_a_number_through_a_reader_that_exists(name, ctx):
    spec = json.loads(manifest.metric_file("per_layer", name).read_text())
    assert manifest.metric_file("per_layer", name).stem == name
    assert set(spec) == {"doc", "reader", "args"}
    assert spec["reader"] in ("health_field", "harness_value")
    assert callable(importlib.import_module(f"chipbench.readers.{spec['reader']}").read)
    value = read_metric("per_layer", name, ctx)
    assert isinstance(value, float) and value >= 0
    # and nothing, without raising, from a program that has no such clock
    parent = SimpleNamespace(health_close=[{"startup": {"warmup_seconds": 40.0}}], harness={})
    assert read_metric("per_layer", name, parent) is None


def test_the_entries_add_up_as_the_issue_says(ctx):
    read = {n: read_metric("per_layer", n, ctx) for n in NAMES}
    clock = ctx.health_close[0]["startup"]["clock"]
    stages = clock["stages"]
    assert read["worker_start_to_serving_s"] == pytest.approx(sum(stages.values()), abs=1e-3)
    assert read["startup_process_s"] == pytest.approx(
        stages["interpreter"] + stages["imports"], abs=2e-6)
    assert read["startup_register_s"] == pytest.approx(
        stages["runtime_connect"] + stages["register"], abs=2e-6)
    (row,) = clock["programs"]
    assert read["warmup_backend_s"] == row["backend_s"] > 0
    assert read["warmup_tiny_compile_s"] == row["tiny_s"] > 0
    assert (read["warmup_trace_lower_s"] + read["warmup_backend_s"]
            + read["warmup_tiny_compile_s"] + read["warmup_run_s"]
            == pytest.approx(row["wall_s"], abs=5e-6))
