"""Fleet observability plane (ISSUE 13).

Covers the three parts end to end on real runtime fixtures:

- snapshot wire + publisher/aggregator over a real store, including the
  retirement triad: drain retraction (`retired` snapshot), lease-loss
  (instance watch), and staleness — dead workers' series are REMOVED
  from the fleet /metrics, never zeroed;
- the aggregator lifecycle e2e on a 3-worker mocker fleet (one drained,
  one killed) with planner Observations fed from live workers only;
- per-tenant SLO attribution (phase scanning, frontend+worker merge,
  the tenant cardinality cap) and the embedded-frontend /fleet page;
- the flight recorder: bounded ring, redaction contract, and the
  chaos-kill / stall-deadline dumps whose step records reconstruct the
  victim's committed stream;
- the tuned trace-phase histogram buckets (satellite pin).
"""

import asyncio
import json
import time
from contextlib import suppress

import pytest

from dynamo_tpu import tracing
from dynamo_tpu.llm.mocker import MockEngineArgs, MockTpuEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.obs import flight_recorder
from dynamo_tpu.obs.aggregator import FleetAggregator
from dynamo_tpu.obs.flight_recorder import FlightRecorder
from dynamo_tpu.obs.slo import (
    FRONTEND_COMPLETE_ON,
    FRONTEND_PHASES,
    PhaseScanner,
    SloAttributor,
    SloTargets,
)
from dynamo_tpu.obs.snapshot import MetricSnapshot, SnapshotPublisher
from dynamo_tpu.runtime import DistributedRuntime, chaos
from dynamo_tpu.runtime.chaos import ChaosPlan, ChaosRule
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.store import StoreServer
from dynamo_tpu.tracing.core import _PHASE_BUCKETS, TraceCollector

pytestmark = [pytest.mark.integration, pytest.mark.pre_merge]


@pytest.fixture(autouse=True)
def _fresh_flight_state():
    """A process-wide dump flushes EVERY registered ring — engines leaked
    (but still referenced) by earlier suites in the same pytest process
    would dump alongside this module's victims, so each test starts from
    an empty registry and budget."""
    flight_recorder.reset_budget()
    flight_recorder.reset_registry()
    yield


def make_req(rid: str, max_tokens: int = 8, tenant: str = "") -> dict:
    pre = PreprocessedRequest(
        model="mock",
        token_ids=[1, 2, 3, 4],
        request_id=rid,
        sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=max_tokens),
    )
    if tenant:
        pre.tenant_id = tenant
    return pre.to_wire()


def snap(wid: int, seq: int, **kw) -> MetricSnapshot:
    return MetricSnapshot(worker_id=wid, seq=seq, t=time.time(), **kw)


def dump_for_rid(paths, rid: str) -> dict:
    """The flight artifact whose step records carry this request's lane
    cursors (a process-wide dump writes one artifact per live ring)."""
    for p in paths:
        payload = json.loads(p.read_text())
        if any(
            lane.get("rid") == rid
            for r in payload["records"]
            for lane in r.get("lanes", [])
        ):
            return payload
    raise AssertionError(f"no dump in {[str(p) for p in paths]} carries {rid!r}")


# ---------------------------------------------------------------------------
# Wire + buckets
# ---------------------------------------------------------------------------


def test_snapshot_wire_roundtrip():
    s = MetricSnapshot(
        worker_id=42,
        role="worker",
        component="backend",
        seq=7,
        t=123.5,
        families={"scheduler": {"waiting": 3.0, "running": 2.0}},
        tenants={"acme": {"depth": 1.0, "deficit": 16.0}},
        phases={"engine/prefill": (4.0, 0.25)},
        requests=[{"rid": "r1", "tenant": "acme", "phases": {"prefill": 0.1}}],
    )
    back = MetricSnapshot.from_wire(s.to_wire())
    assert back == s
    retired = MetricSnapshot(worker_id=42, retired=True)
    assert MetricSnapshot.from_wire(retired.to_wire()).retired


def test_phase_buckets_cover_measured_ranges():
    """Satellite pin: the trace-phase histogram edges resolve sub-ms
    decode iterations AND multi-second prefills — a p99 estimated off
    /metrics must interpolate inside a bucket, not saturate the top."""
    assert list(_PHASE_BUCKETS) == sorted(set(_PHASE_BUCKETS)), "monotonic"
    # Sub-ms resolution for decode iterations / host_gap stats.
    assert _PHASE_BUCKETS[0] <= 1e-4
    assert sum(1 for b in _PHASE_BUCKETS if b < 1e-3) >= 4
    # Multi-second prefill resolution: several edges between 1 s and the
    # top, and a top edge well past the longest chunked prefill.
    assert sum(1 for b in _PHASE_BUCKETS if 1.0 <= b < _PHASE_BUCKETS[-1]) >= 6
    assert _PHASE_BUCKETS[-1] >= 60.0


def test_collector_phase_totals_accumulate():
    collector = TraceCollector(capacity=8)
    tracer = tracing.Tracer("svc", collector)
    for _ in range(20):  # more spans than ring capacity: totals survive
        tracer.record("phase_x", 1.0, 1.5)
    count, total = collector.phase_totals()["svc/phase_x"]
    assert count == 20 and abs(total - 10.0) < 1e-9


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_bounded_and_redacted(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT_DIR", str(tmp_path))
    flight_recorder.reset_budget()
    rec = FlightRecorder("unit", capacity=4)
    for i in range(10):
        rec.record_step(i=i, emitted=1, token_ids=[1, 2, 3], text="secret")
    rec.record_event("shed_queue_full", rid="r9", prompt="user secret")
    records = rec.snapshot()
    assert len(records) == 4  # bounded ring
    paths = flight_recorder.dump_all("sigterm_drain", "unit-test")
    assert len(paths) == 1
    payload = json.loads(open(paths[0]).read())
    assert payload["reason"] == "sigterm_drain"
    dumped = json.dumps(payload)
    # Redaction contract: payload-bearing keys never reach the artifact.
    assert "token_ids" not in dumped
    assert "secret" not in dumped
    assert payload["records"][-1]["event"] == "shed_queue_full"
    # Budget: immediate same-reason re-dump is coalesced by the cooldown.
    assert flight_recorder.dump_all("sigterm_drain") == []


def test_flight_recorder_capacity_zero_disables():
    rec = FlightRecorder("off", capacity=0)
    rec.record_step(i=1)
    rec.record_event("x")
    assert rec.snapshot() == []


async def test_chaos_kill_dump_reconstructs_committed_stream(
    tmp_path, monkeypatch
):
    """Acceptance: a chaos kill produces a flight-recorder dump whose
    step records match the victim's committed stream — cumulative
    per-lane emitted counts equal the tokens the client received, and
    the megastep shape is reconstructable."""
    monkeypatch.setenv("DYN_FLIGHT_DIR", str(tmp_path))
    flight_recorder.reset_budget()
    engine = MockTpuEngine(
        MockEngineArgs(
            num_kv_blocks=256, block_size=8, megastep_k=4,
            speedup_ratio=200.0,
        )
    )
    engine.chaos_tag = "victim"
    chaos.install(
        ChaosPlan(
            [ChaosRule(point="engine.step", action="kill", match="victim",
                       after=6)]
        )
    )
    received = 0
    try:
        gen = engine.generate(make_req("r-kill", max_tokens=64), Context())
        with suppress(asyncio.TimeoutError):
            while True:
                # The kill parks the stream; the timeout is how the test
                # observes "worker died mid-decode".
                out = await asyncio.wait_for(gen.__anext__(), 1.0)
                received += len(out.get("token_ids") or [])
    finally:
        chaos.uninstall()
    assert engine._dead and received > 0
    dumps = sorted(tmp_path.glob("flight-*chaos_kill*.json"))
    assert dumps, "chaos kill left no flight-recorder artifact"
    payload = dump_for_rid(dumps, "r-kill")
    assert payload["reason"] == "chaos_kill"
    steps = [r for r in payload["records"] if r.get("kind") == "step"]
    assert steps, "no step records in the dump"
    emitted = sum(
        lane.get("emitted", 0)
        for r in steps
        for lane in r.get("lanes", [])
        if lane.get("rid") == "r-kill"
    )
    cursors = [
        lane["generated"]
        for r in steps
        for lane in r.get("lanes", [])
        if lane.get("rid") == "r-kill" and "generated" in lane
    ]
    # The dump reconstructs the committed stream: per-step emissions sum
    # to exactly what the client saw, and the final lane cursor agrees.
    assert emitted == received
    assert cursors and cursors[-1] == received
    # The victim's final megasteps are reconstructable (k > 1 fused).
    assert any(r.get("k", 1) > 1 for r in steps)
    assert "token_ids" not in json.dumps(payload)  # redacted


async def test_stall_deadline_dump_captures_victim_steps(
    tmp_path, monkeypatch
):
    """Acceptance: a stall-deadline fire produces a dump whose step
    records match the victim's committed stream (single-process fleet:
    the client-side stall trigger flushes the wedged engine's ring)."""
    monkeypatch.setenv("DYN_FLIGHT_DIR", str(tmp_path))
    flight_recorder.reset_budget()
    store = StoreServer()
    await store.start()
    rt = await DistributedRuntime.create(store.address)
    engine = MockTpuEngine(
        MockEngineArgs(num_kv_blocks=256, block_size=8, speedup_ratio=50.0)
    )
    engine.chaos_tag = "w-stall"
    ep = rt.namespace("obs").component("w").endpoint("generate")

    async def handler(req, ctx):
        async for out in engine.generate(req, ctx):
            yield out

    await ep.serve(handler)
    client_rt = await DistributedRuntime.create(store.address)
    client_rt.egress.policy.stall_s = 0.5
    client = await (
        client_rt.namespace("obs").component("w").endpoint("generate").client()
    )
    await client.wait_for_instances(1, timeout=10)
    chaos.install(
        ChaosPlan(
            [ChaosRule(point="engine.step", action="stall", match="w-stall",
                       after=4, stall_s=3600.0)]
        )
    )
    received = 0
    try:
        stream = await client.round_robin(make_req("r-stall", max_tokens=64))
        with suppress(ConnectionError):
            async for out in stream:
                received += len(out.get("token_ids") or [])
    finally:
        chaos.uninstall()
        await client.stop()
        await client_rt.shutdown()
        with suppress(ConnectionError, OSError):
            await rt.shutdown()
        await store.stop()
    assert received > 0
    # The dump is handed to the loop's executor (dataplane._flight_dump):
    # give that thread a moment to finish writing.
    for _ in range(100):
        dumps = sorted(tmp_path.glob("flight-*stall_deadline*.json"))
        if dumps:
            break
        await asyncio.sleep(0.05)
    assert dumps, "stall deadline left no flight-recorder artifact"
    payload = dump_for_rid(dumps, "r-stall")
    steps = [r for r in payload["records"] if r.get("kind") == "step"]
    emitted = sum(
        lane.get("emitted", 0)
        for r in steps
        for lane in r.get("lanes", [])
        if lane.get("rid") == "r-stall"
    )
    assert emitted == received


# ---------------------------------------------------------------------------
# SLO attribution
# ---------------------------------------------------------------------------


def test_phase_scanner_groups_request_spans():
    collector = TraceCollector(capacity=64)
    tracer = tracing.Tracer("engine", collector)
    scanner = PhaseScanner(collector)
    tracer.record("sched_admit", 1.0, 1.02,
                  attrs={"request_id": "r1", "tenant": "acme"})
    tracer.record("prefill", 1.0, 1.10,
                  attrs={"request_id": "r1", "tenant": "acme"})
    assert scanner.scan() == []  # decode not seen yet: still open
    tracer.record("decode", 1.10, 1.50,
                  attrs={"request_id": "r1", "tokens": 9, "tenant": "acme"})
    records = scanner.scan()
    assert len(records) == 1
    rec = records[0]
    assert rec["rid"] == "r1" and rec["tenant"] == "acme"
    assert rec["tokens"] == 9
    assert abs(rec["phases"]["prefill"] - 0.10) < 1e-9
    assert scanner.scan() == []  # already consumed


def test_slo_attributor_merges_and_caps_tenants():
    att = SloAttributor(
        targets=SloTargets(ttft_s=0.2, tpot_s=0.05), grace_s=60.0,
        max_tenants=4,
    )
    att.ingest(
        [{"rid": "r1", "tenant": "acme", "tokens": 11,
          "phases": {"sched_admit": 0.02, "prefill": 0.10, "decode": 0.50}}],
        side="worker",
    )
    att.ingest(
        [{"rid": "r1", "tenant": "acme",
          "phases": {"http": 0.70, "tokenize": 0.01, "route": 0.02}}],
        side="frontend",
    )
    s = att.summary()
    acme = s["tenants"]["acme"]
    assert acme["requests"] == 1
    # ttft = tokenize + route + prefill = 0.13 s; tpot = 0.5/10 = 50 ms.
    assert abs(acme["ttft_p50_ms"] - 130.0) < 1.0
    assert abs(acme["tpot_p50_ms"] - 50.0) < 0.5
    assert acme["ttft_attainment"] == 1.0
    assert acme["phase_mean_ms"]["queue"] == 20.0
    # Duplicate delivery (snapshot redeliver) must not double-count.
    att.ingest(
        [{"rid": "r1", "tenant": "acme", "tokens": 11,
          "phases": {"prefill": 0.10, "decode": 0.50}}],
        side="worker",
    )
    assert att.summary()["tenants"]["acme"]["requests"] == 1
    # Cardinality cap: tenants beyond max land in __other__.
    for i in range(10):
        att.ingest(
            [{"rid": f"t{i}", "tenant": f"tenant-{i}", "tokens": 2,
              "phases": {"prefill": 0.01, "decode": 0.01}}],
            side="worker",
        )
    att.sweep(time.monotonic() + 120.0)  # force worker-only finalize
    tenants = set(att.summary()["tenants"])
    assert len(tenants) <= 5  # 4 tracked + __other__
    assert "__other__" in tenants


# ---------------------------------------------------------------------------
# Aggregator: export, rollups, retirement, tenant cap
# ---------------------------------------------------------------------------


def _bound_aggregator(**kw):
    agg = FleetAggregator(store=None, namespace="dynamo", **kw)
    registry = MetricsRegistry()
    hooks: list = []
    agg.bind(registry, hooks)
    return agg, registry, hooks


def test_aggregator_exports_worker_series_and_rollups():
    agg, registry, hooks = _bound_aggregator(stale_after_s=60.0)
    agg.ingest(snap(1, 1, families={"scheduler": {"waiting": 3.0}}))
    agg.ingest(snap(2, 1, families={"scheduler": {"waiting": 7.0}}))
    hooks[0]()
    text = registry.render().decode()
    assert 'dynamo_scheduler_waiting_seqs{namespace="dynamo",service="engine",worker_id="1"} 3.0' in text
    assert 'dynamo_scheduler_waiting_seqs{namespace="dynamo",service="engine",worker_id="2"} 7.0' in text
    assert 'dynamo_fleet_scheduler_waiting_seqs{namespace="dynamo",service="engine",stat="sum"} 10.0' in text
    assert 'stat="max"} 7.0' in text
    # Retirement removes the series (not zeroed) and rollups follow.
    agg.ingest(MetricSnapshot(worker_id=2, retired=True))
    hooks[0]()
    text = registry.render().decode()
    assert 'worker_id="2"' not in text
    assert 'dynamo_fleet_scheduler_waiting_seqs{namespace="dynamo",service="engine",stat="sum"} 3.0' in text
    assert agg.workers_retired_total == 1
    # The LAST contributor retiring removes the rollups too — never
    # frozen at the dead fleet's final values (the empty family keeps
    # its HELP/TYPE header; what matters is no sample remains).
    agg.ingest(MetricSnapshot(worker_id=1, retired=True))
    hooks[0]()
    text = registry.render().decode()
    assert not [
        ln for ln in text.splitlines()
        if ln.startswith("dynamo_fleet_scheduler_waiting_seqs{")
    ]


def test_aggregator_staleness_retires_series():
    agg, registry, hooks = _bound_aggregator(stale_after_s=0.2)
    agg.ingest(snap(5, 1, families={"scheduler": {"waiting": 1.0}}))
    hooks[0]()
    assert 'worker_id="5"' in registry.render().decode()
    time.sleep(0.25)
    hooks[0]()
    assert 'worker_id="5"' not in registry.render().decode()
    assert agg.live_workers() == []


def test_aggregator_staleness_ignores_publisher_clock_skew():
    """Staleness is judged on the AGGREGATOR's arrival clock: a worker
    whose own wall clock is far behind (t stamped minutes ago) keeps
    publishing and must stay in the fleet view."""
    agg, _registry, _hooks = _bound_aggregator(stale_after_s=0.5)
    skewed = MetricSnapshot(
        worker_id=3, seq=1, t=time.time() - 3600.0,
        families={"scheduler": {"waiting": 1.0}},
    )
    agg.ingest(skewed)
    assert agg.sweep_stale() == []
    assert agg.live_workers() == [3]


def test_aggregator_accepts_restarted_publisher_epoch():
    """A publisher that restarts with the SAME worker_id starts seq over
    at 1 under a new epoch — its fresh snapshots must replace the dead
    incarnation immediately, not be dropped as out-of-order until the
    staleness sweep."""
    agg, _registry, _hooks = _bound_aggregator(stale_after_s=60.0)
    agg.ingest(snap(4, 7, epoch=100.0, families={"scheduler": {"waiting": 9.0}}))
    # Same-incarnation redelivery of an older seq: dropped.
    agg.ingest(snap(4, 6, epoch=100.0, families={"scheduler": {"waiting": 1.0}}))
    assert agg.latest[4].families["scheduler"]["waiting"] == 9.0
    # Restarted incarnation, seq reset: accepted at once.
    agg.ingest(snap(4, 1, epoch=200.0, families={"scheduler": {"waiting": 2.0}}))
    assert agg.latest[4].seq == 1
    assert agg.latest[4].families["scheduler"]["waiting"] == 2.0


def test_aggregator_tenant_cardinality_cap():
    """Satellite pin: adversarial x-tenant-id churn cannot grow the
    aggregator /metrics unboundedly — 64 series + __other__, retired
    tenants removed."""
    agg, registry, hooks = _bound_aggregator(stale_after_s=60.0)
    tenants = {
        f"tenant-{i:03d}": {"depth": float(i), "deficit": 1.0}
        for i in range(100)
    }
    agg.ingest(snap(1, 1, tenants=tenants))
    hooks[0]()
    text = registry.render().decode()
    depth_series = [
        ln for ln in text.splitlines()
        if ln.startswith("dynamo_fleet_tenant_queue_depth{")
    ]
    assert len(depth_series) == 65  # 64 + __other__
    assert any('tenant="__other__"' in ln for ln in depth_series)
    # Tenants drain away -> their series leave with them.
    agg.ingest(snap(1, 2, tenants={"tenant-099": {"depth": 1.0, "deficit": 0.0}}))
    hooks[0]()
    text = registry.render().decode()
    depth_series = [
        ln for ln in text.splitlines()
        if ln.startswith("dynamo_fleet_tenant_queue_depth{")
    ]
    assert len(depth_series) == 1 and 'tenant="tenant-099"' in depth_series[0]


def test_aggregator_observation_diffs_frontend_and_phases():
    agg, _registry, _hooks = _bound_aggregator(stale_after_s=60.0)
    agg.ingest(
        snap(9, 1, role="frontend",
             families={"frontend": {
                 "requests_total": 10.0, "isl_sum": 2560.0, "isl_count": 10.0,
                 "osl_sum": 1280.0, "osl_count": 10.0,
                 "ttft_sum": 1.0, "ttft_count": 10.0,
                 "itl_sum": 0.5, "itl_count": 50.0,
             }},
             phases={"frontend/tokenize": (10.0, 0.1)})
    )
    first = agg.observation()
    assert first.request_rate == 0.0  # priming window
    agg.ingest(
        snap(9, 2, role="frontend",
             families={"frontend": {
                 "requests_total": 20.0, "isl_sum": 5120.0, "isl_count": 20.0,
                 "osl_sum": 2560.0, "osl_count": 20.0,
                 "ttft_sum": 3.0, "ttft_count": 20.0,
                 "itl_sum": 1.5, "itl_count": 100.0,
             }},
             phases={"frontend/tokenize": (20.0, 0.3)})
    )
    obs = agg.observation()
    assert obs.request_rate > 0.0
    assert abs(obs.mean_isl - 256.0) < 1e-6
    assert abs(obs.observed_ttft_s - 0.2) < 1e-6
    assert abs(obs.observed_itl_s - 0.02) < 1e-6
    assert abs(obs.phase_means["tokenize"] - 0.02) < 1e-6


# ---------------------------------------------------------------------------
# Publisher + aggregator over a real store
# ---------------------------------------------------------------------------


async def test_snapshot_publisher_retire_over_store():
    store = StoreServer()
    await store.start()
    rt = await DistributedRuntime.create(store.address)
    agg_rt = await DistributedRuntime.create(store.address)
    agg = FleetAggregator(agg_rt.store, namespace="obs-t", stale_after_s=60.0)
    await agg.start()
    pub = SnapshotPublisher(
        rt.store, "obs-t", worker_id=77, component="backend",
        interval_s=0.03,
    )
    pub.collectors = {"scheduler": lambda: {"waiting": 4, "running": 1}}
    pub.tenant_source = lambda: {"acme": {"depth": 2.0, "deficit": 8.0}}
    try:
        await pub.start()
        for _ in range(100):
            if 77 in agg.latest:
                break
            await asyncio.sleep(0.02)
        assert agg.latest[77].families["scheduler"]["waiting"] == 4.0
        assert agg.latest[77].tenants["acme"]["depth"] == 2.0
        # Drain retraction: the retired snapshot removes the worker NOW.
        assert await pub.retire(timeout=5.0)
        for _ in range(100):
            if 77 not in agg.latest:
                break
            await asyncio.sleep(0.02)
        assert 77 not in agg.latest
    finally:
        await pub.stop()
        await agg.stop()
        await rt.shutdown()
        await agg_rt.shutdown()
        await store.stop()


async def test_snapshot_publisher_drain_survives_bad_publish():
    """A non-ConnectionError from one publish (bad payload, store-layer
    bug) must not kill the drain task: dying there strands ``_idle``
    cleared, so every later flush()/retire() would burn its full
    timeout. The failed snapshot is counted and the next one delivers."""

    class FlakyStore:
        def __init__(self):
            self.published = 0
            self.fail_next = True

        async def publish(self, subject, payload):
            if self.fail_next:
                self.fail_next = False
                raise ValueError("synthetic non-connection failure")
            self.published += 1

    store = FlakyStore()
    pub = SnapshotPublisher(store, "obs-t", worker_id=9, interval_s=60.0)
    pub.publish_nowait()
    pub.publish_nowait()
    assert await pub.flush(timeout=2.0), "drain task died on ValueError"
    assert store.published == 1
    assert pub.publish_errors_total == 1
    # The drain task is still alive and keeps delivering.
    pub.publish_nowait()
    assert await pub.flush(timeout=2.0)
    assert store.published == 2
    await pub.stop()


async def test_standalone_aggregator_service():
    """The reference `components/metrics` shape: one standalone process
    subscribing to the namespace's snapshots and serving the fleet
    /metrics + /fleet on its own status server."""
    import aiohttp

    from dynamo_tpu.obs.service import run_aggregator

    store = StoreServer()
    await store.start()
    rt = await DistributedRuntime.create(store.address)
    agg_rt = await DistributedRuntime.create(store.address)
    ready = asyncio.Event()
    statuses: list = []
    task = asyncio.create_task(
        run_aggregator(
            agg_rt, namespace="svc-t", host="127.0.0.1", port=0,
            ready_event=ready, status_out=statuses,
        )
    )
    pub = SnapshotPublisher(rt.store, "svc-t", worker_id=3, interval_s=0.03)
    pub.collectors = {"scheduler": lambda: {"waiting": 2, "running": 1}}
    try:
        await asyncio.wait_for(ready.wait(), 10)
        await pub.start()
        base = f"http://127.0.0.1:{statuses[0].port}"
        async with aiohttp.ClientSession() as s:
            text = ""
            for _ in range(100):
                async with s.get(f"{base}/metrics") as r:
                    assert r.status == 200
                    text = await r.text()
                if 'worker_id="3"' in text:
                    break
                await asyncio.sleep(0.05)
            assert 'worker_id="3"' in text
            assert "dynamo_fleet_scheduler_waiting_seqs" in text
            async with s.get(f"{base}/fleet") as r:
                assert r.status == 200
                payload = await r.json()
            assert payload["live_workers"] == [3]
            assert "slo" in payload
    finally:
        await pub.stop()
        task.cancel()
        with suppress(asyncio.CancelledError):
            await task
        await rt.shutdown()
        with suppress(ConnectionError, OSError):
            await agg_rt.shutdown()
        await store.stop()


# ---------------------------------------------------------------------------
# Fleet lifecycle e2e: 3 mocker workers, one drained, one killed
# ---------------------------------------------------------------------------


async def test_fleet_lifecycle_drain_kill_converge(tmp_path, monkeypatch):
    """Satellite e2e: 3 workers publish; one is killed (stops publishing
    — the staleness backstop retires it), one drains gracefully (the
    retired snapshot retires it immediately); the fleet view converges
    to the survivor, dead workers' series are REMOVED (not zeroed), and
    planner Observations come from live workers only."""
    from dynamo_tpu.backends.mocker.main import run_mocker

    monkeypatch.setenv("DYN_FLIGHT_DIR", str(tmp_path))
    flight_recorder.reset_budget()
    store = StoreServer()
    await store.start()
    runtimes, tasks = [], []
    for _ in range(3):
        rt = await DistributedRuntime.create(store.address)
        served = asyncio.Event()
        tasks.append(
            asyncio.create_task(
                run_mocker(
                    rt, model_name="mock",
                    engine_args=MockEngineArgs(
                        num_kv_blocks=256, block_size=8, speedup_ratio=50.0
                    ),
                    served_event=served, obs_interval_s=0.05,
                )
            )
        )
        await asyncio.wait_for(served.wait(), 20)
        runtimes.append(rt)
    wids = [rt.primary_lease_id for rt in runtimes]
    agg_rt = await DistributedRuntime.create(store.address)
    agg = FleetAggregator(agg_rt.store, namespace="dynamo", stale_after_s=0.6)
    registry = MetricsRegistry()
    hooks: list = []
    agg.bind(registry, hooks)
    await agg.start()
    client = await (
        agg_rt.namespace("dynamo").component("backend").endpoint("generate").client()
    )
    try:
        await client.wait_for_instances(3, timeout=10)
        # Traffic to every worker so phases + SLO records exist.
        for i, wid in enumerate(wids):
            stream = await client.direct(wid, make_req(f"warm-{i}"))
            async for _ in stream:
                pass
        for _ in range(200):
            if len(agg.live_workers()) == 3:
                break
            await asyncio.sleep(0.02)
        assert sorted(agg.live_workers()) == sorted(wids)
        hooks[0]()
        text = registry.render().decode()
        for wid in wids:
            assert f'worker_id="{wid}"' in text
        assert "dynamo_fleet_scheduler_running_seqs" in text

        # Graceful drain of worker 0: retired-snapshot retraction.
        await runtimes[0].drain(timeout=5.0)
        for _ in range(200):
            if wids[0] not in agg.live_workers():
                break
            await asyncio.sleep(0.02)
        assert wids[0] not in agg.live_workers()

        # Kill worker 1: cancel its serving task + drop its runtime
        # without drain — snapshots stop, staleness retires it.
        tasks[1].cancel()
        with suppress(ConnectionError, OSError):
            await runtimes[1].shutdown()
        deadline = time.monotonic() + 5.0
        while wids[1] in agg.live_workers() and time.monotonic() < deadline:
            agg.sweep_stale()
            await asyncio.sleep(0.1)
        assert agg.live_workers() == [wids[2]]

        hooks[0]()
        text = registry.render().decode()
        assert f'worker_id="{wids[0]}"' not in text  # removed, not zeroed
        assert f'worker_id="{wids[1]}"' not in text
        assert f'worker_id="{wids[2]}"' in text

        # Planner feed reflects only the live worker.
        agg.observation()  # prime the diff window
        stream = await client.direct(wids[2], make_req("post-kill"))
        async for _ in stream:
            pass
        await asyncio.sleep(0.2)  # one publish interval
        obs = agg.observation()
        assert obs.phase_means and "prefill" in obs.phase_means
        assert len(agg.latest) == 1
    finally:
        await client.stop()
        await agg.stop()
        for t in tasks:
            t.cancel()
        for rt in runtimes[2:] + [agg_rt]:
            with suppress(ConnectionError, OSError):
                await rt.shutdown()
        await store.stop()


# ---------------------------------------------------------------------------
# Embedded frontend: fleet /metrics + /fleet SLO page
# ---------------------------------------------------------------------------


async def test_frontend_embedded_fleet_and_slo(tmp_path, monkeypatch):
    import aiohttp

    from dynamo_tpu.backends.mocker.main import run_mocker
    from dynamo_tpu.frontend.main import run_frontend

    monkeypatch.setenv("DYN_FLIGHT_DIR", str(tmp_path))
    store = StoreServer()
    await store.start()
    runtimes, tasks = [], []
    for _ in range(2):
        rt = await DistributedRuntime.create(store.address)
        served = asyncio.Event()
        tasks.append(
            asyncio.create_task(
                run_mocker(
                    rt, model_name="mock",
                    engine_args=MockEngineArgs(
                        num_kv_blocks=256, block_size=8, speedup_ratio=50.0
                    ),
                    served_event=served, obs_interval_s=0.05,
                )
            )
        )
        await asyncio.wait_for(served.wait(), 20)
        runtimes.append(rt)
    front_rt = await DistributedRuntime.create(store.address)
    ready = asyncio.Event()
    services: list = []
    tasks.append(
        asyncio.create_task(
            run_frontend(
                front_rt, http_host="127.0.0.1", http_port=0,
                router_mode="round_robin", ready_event=ready,
                service_out=services, obs_interval_s=0.05,
            )
        )
    )
    await asyncio.wait_for(ready.wait(), 20)
    base = f"http://127.0.0.1:{services[0].port}"
    wids = [rt.primary_lease_id for rt in runtimes]
    try:
        async with aiohttp.ClientSession() as s:
            for _ in range(200):
                async with s.get(f"{base}/v1/models") as r:
                    if (await r.json())["data"]:
                        break
                await asyncio.sleep(0.05)
            body = {
                "model": "mock",
                "messages": [{"role": "user", "content": "hello fleet"}],
                "max_tokens": 6,
                "stream": False,
            }
            for i in range(4):  # round robin touches both workers
                async with s.post(
                    f"{base}/v1/chat/completions", json=body,
                    headers={"x-tenant-id": "acme"},
                ) as r:
                    assert r.status == 200, await r.text()
            # Fleet series with worker_id labels on the FRONTEND /metrics.
            deadline = time.monotonic() + 10.0
            text = ""
            while time.monotonic() < deadline:
                async with s.get(f"{base}/metrics") as r:
                    text = await r.text()
                if all(f'worker_id="{w}"' in text for w in wids):
                    break
                await asyncio.sleep(0.1)
            for w in wids:
                assert f'worker_id="{w}"' in text
            assert "dynamo_fleet_scheduler_running_seqs" in text
            # /fleet renders the per-tenant SLO breakdown.
            payload = {}
            while time.monotonic() < deadline:
                async with s.get(f"{base}/fleet") as r:
                    assert r.status == 200
                    payload = await r.json()
                slo = payload.get("dynamo", {}).get("slo", {})
                if slo.get("tenants", {}).get("acme", {}).get("requests"):
                    break
                await asyncio.sleep(0.1)
            fleet = payload["dynamo"]
            assert sorted(fleet["live_workers"]) == sorted(wids)
            acme = fleet["slo"]["tenants"]["acme"]
            assert acme["requests"] >= 1
            assert acme["ttft_p50_ms"] > 0
            assert "queue" in acme["phase_mean_ms"]
            # dynamo_slo_* histograms export per tenant.
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
            assert 'tenant="acme"' in text
            assert "dynamo_slo_ttft_seconds" in text
    finally:
        for t in tasks:
            t.cancel()
        for rt in runtimes + [front_rt]:
            with suppress(ConnectionError, OSError):
                await rt.shutdown()
        await store.stop()
