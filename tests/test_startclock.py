"""The start-up clock (tracing/startclock.py): the stages partition the
worker's wall time, compile events are booked once and where they
happen, and what /health and /metrics show of it, during and after a real
CPU warm-up of the tiny preset (one in-process worker for the module)."""

import asyncio
import json
import threading
import time
import urllib.request

import pytest

from dynamo_tpu.tracing import startclock
from dynamo_tpu.tracing.startclock import AFTER_SERVING, STAGES, StartClock

pytestmark = [pytest.mark.unit]


def _walk(clock: StartClock, stages=("runtime_connect", "backend_init", "weights",
                                     "cache_alloc", "weights", "register")):
    for stage in stages:
        clock.mark(stage)
        time.sleep(0.002)


@pytest.mark.parametrize("closed", [False, True], ids=["running", "closed"])
def test_the_stages_partition_the_wall_time(closed):
    t0 = time.perf_counter()
    clock = StartClock()
    _walk(clock)
    if closed:
        clock.close()
        clock.mark("warmup")        # nothing once closed
    snap = clock.snapshot()
    wall = time.perf_counter() - t0
    assert tuple(snap["stages"]) == STAGES
    assert all(s >= 0 for s in snap["stages"].values())
    assert abs(sum(snap["stages"].values()) - snap["total_s"]) < 1e-3
    assert snap["total_s"] <= wall + 1e-3
    assert snap["stage_now"] == (None if closed else "register")
    assert snap["stages"]["weights"] >= 0.004          # entered twice: adds up
    assert snap["stages"]["warmup"] == 0
    assert snap["sums"]["register_s"] == pytest.approx(
        snap["stages"]["runtime_connect"] + snap["stages"]["register"], abs=2e-6)
    time.sleep(0.005)
    later = clock.snapshot()["total_s"]
    assert (later == snap["total_s"]) if closed else (later >= snap["total_s"] + 0.005)
    with pytest.raises(ValueError, match="no start-up stage"):
        clock.mark("nope")


@pytest.mark.parametrize("booked, event, fresh", [
    ([], (10, 20), [(10, 20)]),
    ([(12, 15)], (10, 20), [(10, 12), (15, 20)]),              # an outer over an inner
    ([(12, 15), (16, 18)], (10, 20), [(10, 12), (15, 16), (18, 20)]),
    ([(5, 12)], (10, 20), [(12, 20)]),                         # began inside another
    ([(0, 30)], (10, 20), []),                                 # wholly counted before
    ([(0, 5)], (10, 20), [(10, 20)]),
], ids=["alone", "nested", "two-nested", "overlap", "covered", "apart"])
def test_an_interval_is_booked_once(booked, event, fresh):
    clock = StartClock()
    for iv in booked:
        clock._book(*iv)
    assert clock._book(*event) == fresh
    ivs = clock._booked
    assert all(a[1] < b[0] for a, b in zip(ivs, ivs[1:])), ivs   # disjoint, ascending
    assert clock._book(*event) == []


def test_a_nested_trace_books_the_outer_seconds_once_and_a_row_only_its_inside():
    clock = StartClock()
    clock.mark("warmup")
    with clock.row("p"):
        time.sleep(0.03)
        clock.compile_event("trace", "inner", 0.01)
        time.sleep(0.01)
        clock.compile_event("trace", "outer", 0.035)
        clock.compile_event("hit", "", 0.0)
        clock.compile_event("backend", "outer", 10.0)   # began long before the row
        clock.compile_event("backend", "jnp_op", 0.0)
    snap = clock.snapshot()
    (row,) = snap["programs"]
    stage = snap["compile_by_stage"]["warmup"]
    assert row["name"] == "p" and row["cache"] == "hit" and row["tiny_n"] == 1
    assert row["trace_s"] == pytest.approx(0.035, abs=0.004)
    assert stage["trace_lower_sum_s"] == pytest.approx(0.045, abs=1e-6)   # the plain sum
    # the row holds what lies inside it; the stage the event's own seconds
    # less what was booked before
    assert row["trace_s"] + row["lower_s"] + row["backend_s"] + row["tiny_s"] <= row["wall_s"]
    assert row["run_s"] >= 0
    assert stage["backend_s"] == pytest.approx(10.0 - 0.035, abs=0.01)
    assert snap["sums"]["warmup_trace_lower_s"] == row["trace_s"] + row["lower_s"]


def test_events_after_the_close_land_in_after_serving():
    clock = StartClock()
    clock.mark("weights")
    clock.compile_event("backend", "init", 0.001)
    clock.close()
    time.sleep(0.003)
    clock.compile_event("lower", "reference", 0.002)
    clock.compile_event("miss", "", 0.0)
    by_stage = clock.snapshot()["compile_by_stage"]
    assert by_stage["weights"]["tiny_n"] == 1 and by_stage["weights"]["lower_s"] == 0
    assert by_stage[AFTER_SERVING]["lower_s"] == pytest.approx(0.002, abs=1e-6)
    assert by_stage[AFTER_SERVING]["cache_misses"] == 1
    assert clock.snapshot()["tiny_programs"] == [["init", 1, 0.001]]


@pytest.mark.parametrize("proc", [True, False], ids=["proc", "no-proc"])
def test_the_process_start_comes_from_proc_or_falls_back_to_the_opening(proc, monkeypatch):
    if not proc:
        def missing(*a, **kw):
            raise FileNotFoundError("/proc")
        monkeypatch.setattr("builtins.open", missing)
        assert startclock._process_age_s() is None
    first = time.perf_counter_ns() - 5_000_000
    clock = StartClock(first, from_proc=True)
    monkeypatch.undo()
    snap = clock.snapshot()
    assert snap["stage_now"] == "imports" and snap["stages"]["imports"] >= 0.005
    if proc:
        # this process has run for a while: the interpreter's stage is what
        # /proc says lay before the first line
        age = startclock._process_age_s()
        assert age is not None and age > 0.05
        assert snap["stages"]["interpreter"] == pytest.approx(age - snap["stages"]["imports"],
                                                              abs=0.1)
    else:
        assert snap["stages"]["interpreter"] == 0
    assert snap["process_start_unix"] == pytest.approx(time.time() - snap["total_s"], abs=0.05)


def test_a_boundary_is_under_three_microseconds():
    """A clock read and a dictionary add under a lock. Best of 5 over 20k."""
    clock = StartClock()
    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n // 2):
            clock.mark("weights")
            clock.mark("cache_alloc")
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 3e-6, f"a stage boundary took {best * 1e9:.0f} ns"


def test_the_context_clock_is_the_process_clock_until_it_closes():
    import contextvars

    def scenario():
        assert startclock._CURRENT.get() is None
        startclock.mark("weights")            # nothing where none is open
        clock = startclock.open_process_clock()
        assert startclock.running() is clock
        startclock.mark("weights")
        assert clock.stage_now == "weights"
        clock.close()
        fresh = startclock.running()          # an in-process worker's own
        assert fresh is not clock and fresh.snapshot()["stages"]["interpreter"] == 0
        return clock.snapshot()

    snap = contextvars.Context().run(scenario)
    assert snap["stages"]["interpreter"] >= 0 and snap["sums"]["process_s"] > 0


# -- one in-process worker, held in warm-up, then serving ----------------------


def _get(url: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:      # 503 while starting
        return e.code, e.read().decode()


@pytest.fixture(scope="module")
def started():
    """What /health and /metrics said while a tiny worker sat in warm-up
    (its first program held back) and once it served."""
    from dynamo_tpu import device
    from dynamo_tpu.backends.jax.main import run_jax_worker
    from dynamo_tpu.engine import warmup
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.status_server import SystemStatusServer
    from dynamo_tpu.runtime.store import StoreServer

    in_warmup, release = threading.Event(), threading.Event()
    real_run = warmup._run

    def held_run(*args):
        in_warmup.set()
        assert release.wait(60)
        return real_run(*args)

    async def scenario() -> dict:
        store = StoreServer()
        await store.start()
        rt = await DistributedRuntime.create(store.address)
        rt.status = SystemStatusServer(host="127.0.0.1", port=0)
        await rt.status.start()
        base = f"http://127.0.0.1:{rt.status.port}"
        compile_before = device.compile_log().snapshot()
        served = asyncio.Event()
        task = asyncio.create_task(run_jax_worker(
            rt, model_name="tinyjax", preset="tiny", seed=0, served_event=served,
            engine_overrides={"prefill_buckets": (32, 64), "decode_buckets": (4,),
                              "max_num_seqs": 4},
            warm_up=True))
        try:
            assert await asyncio.to_thread(in_warmup.wait, 60)
            during = await asyncio.to_thread(_get, f"{base}/health")
            metrics_during = await asyncio.to_thread(_get, f"{base}/metrics")
            release.set()
            await asyncio.wait_for(served.wait(), 110)
            after = await asyncio.to_thread(_get, f"{base}/health")
            metrics = await asyncio.to_thread(_get, f"{base}/metrics")
        finally:
            release.set()
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            await rt.status.stop()
            await rt.shutdown()
            await store.stop()
        return {"during": json.loads(during[1]), "after": json.loads(after[1]),
                "metrics_during": metrics_during[1], "metrics": metrics[1],
                "compile_before": compile_before}

    warmup._run = held_run
    try:
        return asyncio.run(asyncio.wait_for(scenario(), 170))
    finally:
        warmup._run = real_run


def test_health_shows_the_stage_before_warm_up_ends(started):
    during = started["during"]
    clock = during["startup"]["clock"]
    assert clock["stage_now"] == "warmup"
    assert clock["programs"] == [] and clock["stages"]["weights"] > 0
    assert during["startup"]["build_seconds"] > 0        # filled as start-up proceeds
    assert "warmup_seconds" not in during["startup"]
    assert set(during["compile"]) == {"total_seconds", "trace_lower_seconds", "programs",
                                      "cache_hits", "cache_misses"}
    # a series a stage the worker has left, none for the one it is in
    assert 'dynamo_worker_startup_seconds{service="worker",stage="weights"}' in \
        started["metrics_during"]
    assert 'stage="warmup"' not in started["metrics_during"]
    assert 'stage="register"' not in started["metrics_during"]     # not entered yet
    assert "dynamo_worker_start_to_serving_seconds" not in started["metrics_during"]


def test_the_derived_start_up_keys_keep_their_names_and_values(started):
    startup = started["after"]["startup"]
    clock = startup["clock"]
    stages = clock["stages"]
    assert clock["stage_now"] is None
    assert abs(sum(stages.values()) - clock["total_s"]) < 1e-3
    assert startup["build_seconds"] == pytest.approx(
        sum(stages[s] for s in startclock.BUILD_STAGES), abs=0.006)
    assert startup["warmup_seconds"] == pytest.approx(
        stages["warmup"] + stages["waves_timed"], abs=0.006)
    names = ["prefill T=32", "prefill T=64", "decode B=4 k=8"]     # a row a program
    assert list(startup["warmup_phases"]) == names + ["prefill waves timed"]
    assert [r["name"] for r in clock["programs"]] == names
    assert startup["warmup_phases"]["prefill waves timed"] == pytest.approx(
        stages["waves_timed"], abs=0.006)
    assert sorted(startup["prefill_bucket_ms"]) == ["32", "64"]
    # warm-up's seconds are its rows' four parts and the timed waves
    sums = clock["sums"]
    parts = (sums["warmup_trace_lower_s"] + sums["warmup_backend_s"]
             + sums["warmup_tiny_compile_s"] + sums["warmup_run_s"])
    assert startup["warmup_seconds"] == pytest.approx(parts + stages["waves_timed"], abs=0.05)
    for stage in ("runtime_connect", "backend_init", "weights", "cache_alloc", "engine_init",
                  "warmup", "waves_timed", "register"):
        assert stages[stage] > 0, stage


def test_every_row_of_a_real_warm_up_fits_in_its_wall(started):
    rows = started["after"]["startup"]["clock"]["programs"]
    assert len(rows) == 3
    for r in rows:
        assert set(r) == {"name", "wall_s", "trace_s", "lower_s", "backend_s", "cache",
                          "tiny_s", "tiny_n", "run_s"}
        assert r["trace_s"] + r["lower_s"] + r["backend_s"] + r["tiny_s"] <= r["wall_s"] + 1e-6
        assert r["run_s"] >= -1e-6 and r["cache"] in ("hit", "miss", "none")
        assert r["trace_s"] > 0 and r["lower_s"] > 0        # each row compiled a program


def test_the_compiles_by_stage_add_up_to_the_compile_logs(started):
    before, after = started["compile_before"], started["after"]["compile"]
    by_stage = started["after"]["startup"]["clock"]["compile_by_stage"]
    # (the weights' jits may have been compiled by an earlier test of this process)
    assert AFTER_SERVING in by_stage and "warmup" in by_stage
    backend = sum(c["backend_s"] + c["tiny_s"] for c in by_stage.values())
    assert backend == pytest.approx(after["total_seconds"] - before["total_seconds"], abs=0.05)
    union = sum(c["trace_s"] + c["lower_s"] for c in by_stage.values())
    plain = sum(c["trace_lower_sum_s"] for c in by_stage.values())
    assert plain == pytest.approx(
        after["trace_lower_seconds"] - before["trace_lower_seconds"], abs=0.05)
    assert 0 < union <= plain + 1e-6
    hits = sum(c["cache_hits"] for c in by_stage.values())
    assert hits == after["cache_hits"] - before["cache_hits"]


def test_metrics_carry_a_series_a_stage_once_serving(started):
    text = started["metrics"]
    clock = started["after"]["startup"]["clock"]
    for stage in STAGES:
        line = f'dynamo_worker_startup_seconds{{service="worker",stage="{stage}"}} '
        (value,) = [ln[len(line):] for ln in text.splitlines() if ln.startswith(line)]
        assert float(value) == pytest.approx(clock["stages"][stage], abs=1e-5)
    (total,) = [ln.split()[-1] for ln in text.splitlines()
                if ln.startswith("dynamo_worker_start_to_serving_seconds{")]
    assert float(total) == pytest.approx(clock["total_s"], abs=1e-5)
