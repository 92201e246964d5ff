"""Closed-loop SLA autoscaling + network-aware routing on the fleet
harness (ISSUE 14).

Three layers: controller unit tests (hysteresis, cooldown, reactive
pressure, independent prefill/decode pools — RecordingConnector, no
sim), netcost unit tests (EWMA folding, cost ratios, selector shifts),
and fleet-harness e2e (the autoscaling A/B, the NetKV routing A/B, and
the drain/kill stream-identity audits — the acceptance criteria of the
issue, at test scale).
"""

import asyncio

import pytest

from dynamo_tpu.fleet.harness import (
    ChaosEvent,
    FleetHarness,
    FleetSpec,
    default_tenants,
    mocker_profile,
    run_fleet_ab,
    run_routing_ab,
)
from dynamo_tpu.fleet.workload import TenantSpec, generate_arrivals, rate_at
from dynamo_tpu.llm.kv_router.netcost import (
    MAX_COST_RATIO,
    NetCostModel,
    NetworkAwareSelector,
    best_pull_source,
)
from dynamo_tpu.llm.kv_router.protocols import RouterConfig
from dynamo_tpu.llm.kv_router.scheduler import DefaultWorkerSelector
from dynamo_tpu.llm.kv_router.sequence import ActiveSequences
from dynamo_tpu.planner.controller import ControllerConfig, PlannerController
from dynamo_tpu.planner.perf_interpolation import from_profile
from dynamo_tpu.planner.planner_core import (
    Observation,
    Planner,
    PlannerConfig,
    RecordingConnector,
    SlaTargets,
)



# -- workload generator ------------------------------------------------------


def test_workload_deterministic_and_diurnal():
    spec = TenantSpec(
        name="t", users=10_000, rps=20.0, diurnal_amplitude=0.6,
        diurnal_period_s=100.0, isl=64, osl=8, shared_prefix_tokens=32,
    )
    a1 = generate_arrivals([spec], 50.0, seed=7)
    a2 = generate_arrivals([spec], 50.0, seed=7)
    assert [(a.t, a.rid, a.token_ids) for a in a1] == [
        (a.t, a.rid, a.token_ids) for a in a2
    ], "same seed must replay identically"
    a3 = generate_arrivals([spec], 50.0, seed=8)
    assert [a.t for a in a1] != [a.t for a in a3]
    # Amplitude 0.6 -> 4x peak/trough swing of the instantaneous rate.
    peak = max(rate_at(spec, t / 10) for t in range(1000))
    trough = min(rate_at(spec, t / 10) for t in range(1000))
    assert peak / trough == pytest.approx(4.0, rel=0.01)
    # Every arrival opens with the tenant's shared prefix; a recurring
    # user recurs with the same tail (the prefix-cache population).
    prefix = a1[0].token_ids[:32]
    assert all(a.token_ids[:32] == prefix for a in a1)
    by_user = {}
    recur = 0
    for a in a1:
        tail = a.token_ids[32:]
        if a.user in by_user:
            recur += 1
            assert by_user[a.user] == tail
        else:
            by_user[a.user] = tail
    assert recur > 0, "no user ever recurred — prefix reuse untested"


def test_workload_bursts():
    spec = TenantSpec(
        name="b", users=100, rps=2.0, burst_rps=20.0,
        burst_every_s=30.0, burst_len_s=5.0,
    )
    assert rate_at(spec, 2.0) == pytest.approx(22.0)
    assert rate_at(spec, 10.0) == pytest.approx(2.0)
    assert rate_at(spec, 32.0) == pytest.approx(22.0)


# -- controller --------------------------------------------------------------

PROFILE = {
    "prefill": {"isl": [128, 512, 2048, 8192], "ttft_s": [0.02, 0.06, 0.2, 0.9]},
    "decode": {"concurrency": [1, 8, 32, 64], "itl_s": [0.01, 0.012, 0.02, 0.045]},
}


def make_controller(clock, **cfg):
    p, d = from_profile(PROFILE)
    connector = RecordingConnector()
    planner = Planner(
        p, d, connector,
        sla=SlaTargets(ttft_s=0.2, itl_s=0.02),
        config=PlannerConfig(predictor="constant", max_replicas=32),
    )
    config = ControllerConfig(
        interval_s=10.0,
        scale_up_cooldown_s=cfg.pop("up_cd", 0.0),
        scale_down_cooldown_s=cfg.pop("down_cd", 0.0),
        down_stable_cycles=cfg.pop("stable", 2),
        max_step_up=cfg.pop("step_up", 4),
        max_step_down=cfg.pop("step_down", 1),
        max_replicas=32,
        **cfg,
    )
    ctl = PlannerController(planner, connector, config=config, clock=clock)
    return ctl, connector


def obs(rate=10.0, isl=512, osl=128, **kw):
    return Observation(request_rate=rate, mean_isl=isl, mean_osl=osl, **kw)


def test_controller_scales_pools_independently():
    """Prefill-heavy vs decode-heavy demand must move DIFFERENT pools —
    the disaggregated scaling contract from the reference planner."""
    t = [0.0]
    ctl, conn = make_controller(lambda: t[0])

    async def run():
        # Prefill-heavy: long prompts, tiny completions.
        t[0] += 100
        await ctl.cycle(obs(rate=30.0, isl=4096, osl=4))
        prefill_1 = ctl.pools["prefill"].target
        decode_1 = ctl.pools["decode"].target
        # Decode-heavy: short prompts, long completions.
        for _ in range(12):
            t[0] += 100
            await ctl.cycle(obs(rate=30.0, isl=64, osl=2048))
        return prefill_1, decode_1

    p1, d1 = asyncio.run(run())
    assert p1 > 1, "prefill pool ignored prefill-heavy demand"
    assert ctl.pools["decode"].target > d1, "decode pool ignored osl demand"
    assert ctl.pools["prefill"].target < p1, (
        "prefill pool never released after demand moved to decode"
    )
    comps = {c for c, _ in conn.calls}
    assert comps == {"prefill", "decode"}


def test_controller_hysteresis_blocks_single_trough():
    """One trough observation must never shed capacity; a sustained
    trough sheds one bounded step per cycle."""
    t = [0.0]
    ctl, _ = make_controller(lambda: t[0], stable=3)

    async def run():
        t[0] += 100
        await ctl.cycle(obs(rate=40.0))            # scale up
        high = ctl.pools["decode"].target
        assert high > 1
        t[0] += 100
        await ctl.cycle(obs(rate=1.0))             # single trough blip
        assert ctl.pools["decode"].target == high
        assert ctl.pools["decode"].last_action == "hysteresis_hold"
        t[0] += 100
        await ctl.cycle(obs(rate=1.0))
        assert ctl.pools["decode"].target == high  # 2/3 cycles
        t[0] += 100
        await ctl.cycle(obs(rate=1.0))             # 3rd: down, one step
        assert ctl.pools["decode"].target == high - 1
        assert ctl.pools["decode"].last_action == "scale_down"
        # A recovery resets the streak — no delayed shed.
        t[0] += 100
        await ctl.cycle(obs(rate=40.0))
        t[0] += 100
        await ctl.cycle(obs(rate=1.0))
        assert ctl.pools["decode"].last_action == "hysteresis_hold"

    asyncio.run(run())


def test_controller_cooldowns_and_bounded_steps():
    t = [1000.0]
    ctl, _ = make_controller(
        lambda: t[0], up_cd=30.0, down_cd=60.0, stable=1, step_up=2,
    )

    async def run():
        await ctl.cycle(obs(rate=100.0, osl=2048))   # huge demand
        first = ctl.pools["decode"].target
        assert first == 1 + 2, "scale-up exceeded max_step_up"
        t[0] += 10                                    # inside up cooldown
        await ctl.cycle(obs(rate=100.0, osl=2048))
        assert ctl.pools["decode"].target == first
        assert ctl.pools["decode"].last_action == "cooldown_hold"
        t[0] += 30                                    # cooldown expired
        await ctl.cycle(obs(rate=100.0, osl=2048))
        assert ctl.pools["decode"].target == first + 2
        # Down cooldown: two sustained-trough downs need 60 s apart.
        t[0] += 100
        await ctl.cycle(obs(rate=0.1))
        down1 = ctl.pools["decode"].target
        assert down1 == first + 1
        t[0] += 10
        await ctl.cycle(obs(rate=0.1))
        assert ctl.pools["decode"].target == down1
        assert ctl.pools["decode"].last_action == "cooldown_hold"

    asyncio.run(run())


def test_controller_reactive_pressure():
    """Queue backlog, typed sheds, and SLO-attainment misses must raise
    capacity above the rate math's answer — before the predictor
    notices."""
    t = [0.0]

    async def run_one(**obs_kw):
        ctl, _ = make_controller(lambda: t[0], queue_depth_per_replica=8.0)
        t[0] += 100
        await ctl.cycle(obs(rate=1.0, **obs_kw))
        return ctl

    # Rate alone at 1 rps: hold at 1.
    ctl = asyncio.run(run_one())
    assert ctl.pools["decode"].target == 1

    # Deep backlog: proportional catch-up, bounded by max_step_up.
    ctl = asyncio.run(
        run_one(queue_depth=200.0, live_workers={"decode": 1, "prefill": 1})
    )
    assert ctl.pools["decode"].target == 5     # 1 + max_step_up(4)
    assert ctl.pools["decode"].last_reason == "queue_depth"
    assert ctl.pools["decode"].desired >= 25   # backlog / 8, uncapped desire

    # A typed shed in the window: one full step of pressure.
    ctl = asyncio.run(run_one(shed_delta=3.0))
    assert ctl.pools["decode"].target == 5
    assert ctl.pools["decode"].last_reason == "sheds"

    # TPOT attainment miss pushes decode; TTFT miss pushes prefill.
    ctl = asyncio.run(run_one(slo_attainment={"ttft": 1.0, "tpot": 0.7}))
    assert ctl.pools["decode"].target == 2
    assert ctl.pools["decode"].last_reason == "slo_attainment"
    assert ctl.pools["prefill"].target == 1
    ctl = asyncio.run(run_one(slo_attainment={"ttft": 0.7, "tpot": 1.0}))
    assert ctl.pools["prefill"].target == 2
    assert ctl.pools["decode"].target == 1


def test_controller_status_and_stats_shapes():
    t = [0.0]
    ctl, _ = make_controller(lambda: t[0])

    async def run():
        t[0] += 100
        await ctl.cycle(obs(rate=30.0))

    asyncio.run(run())
    st = ctl.stats()
    assert st["cycles"] == 1
    assert set(st["decisions"]) == {
        "scale_up", "scale_down", "hold", "cooldown_hold", "hysteresis_hold",
        "degraded_hold",
    }
    assert st["decisions"]["scale_up"] >= 1
    pay = ctl.status_payload()
    assert pay["last_plan"]["predicted_rate"] == pytest.approx(30.0)
    assert pay["pools"]["decode"]["last_action"] == "scale_up"
    assert pay["last_observation"]["request_rate"] == pytest.approx(30.0)


# -- netcost -----------------------------------------------------------------


def test_netcost_ewma_and_ratio_clamp():
    m = NetCostModel(recompute_ms_per_block=2.0)
    m.observe_pull(7, blocks=10, elapsed_ms=10.0)      # 1 ms/block
    assert m.pull_ms_per_block(7) == pytest.approx(1.0)
    assert m.cost_ratio(7) == pytest.approx(0.5)
    # A failed pull charges its whole elapsed budget as one block.
    m.observe_pull(7, blocks=0, elapsed_ms=500.0, ok=False)
    assert m.pull_ms_per_block(7) > 100.0
    assert m.cost_ratio(7) == MAX_COST_RATIO           # clamped
    # Unmeasured peers get the optimistic prior, not infinity.
    assert m.cost_ratio(99) == pytest.approx(0.5 / 2.0, abs=0.2)


def test_netcost_folds_fleet_reports():
    """Every reporter's EWMA of a source folds into one pull-count
    weighted cost — the aggregated fleet view of a peer's link."""
    from dynamo_tpu.llm.kv_router.protocols import (
        ForwardPassMetrics, KvStats, WorkerStats,
    )

    def fpm(waiting, net):
        return ForwardPassMetrics(
            worker_id=0,
            worker=WorkerStats(
                request_active_slots=0, request_total_slots=4,
                num_requests_waiting=waiting,
            ),
            kv=KvStats(
                kv_active_blocks=0, kv_total_blocks=64,
                gpu_cache_usage_perc=0.0, gpu_prefix_cache_hit_rate=0.0,
            ),
            net=net,
        )

    view = {
        1: fpm(3, {9: {"pulls": 3, "ms_per_block": 6.0}}),
        2: fpm(0, {9: {"pulls": 1, "ms_per_block": 2.0}}),
    }
    m = NetCostModel(recompute_ms_per_block=2.0, fleet_view=lambda: view,
                     cache_s=0.0)
    # (6*3 + 2*1) / 4 = 5.0
    assert m.pull_ms_per_block(9) == pytest.approx(5.0)
    assert m.queue_depth(1) == 3
    assert m.queue_depth(2) == 0
    assert m.snapshot()[9]["cost_ratio"] == pytest.approx(2.5)


def test_best_pull_source_prefers_cheap_useful_peer():
    m = NetCostModel(recompute_ms_per_block=2.0)
    m.observe_pull(1, 10, 40.0)     # 4 ms/block -> ratio 2: useless
    m.observe_pull(2, 10, 2.0)      # 0.2 ms/block -> ratio 0.1: cheap
    overlaps = {1: 12, 2: 8, 3: 2}  # peer 1 overlaps most but is slow
    src = best_pull_source(3, 2, overlaps, prompt_blocks=12, netcost=m)
    assert src is not None
    source, extra, ratio = src
    assert source == 2, "picked the expensive peer"
    assert extra == 6
    assert ratio == pytest.approx(0.1)
    # Every peer at ratio >= 1: no pull beats recomputing.
    m2 = NetCostModel(recompute_ms_per_block=2.0)
    m2.observe_pull(1, 10, 40.0)
    m2.observe_pull(2, 10, 80.0)
    assert best_pull_source(3, 0, {1: 12, 2: 8}, 12, m2) is None


def test_network_aware_selector_degrades_to_overlap_only():
    """With uniform (prior) costs, no queues, and no useful pulls the
    network-aware cost must pick exactly the overlap-only winner."""
    active = ActiveSequences(block_size=8)
    cfg = RouterConfig(temperature=0.0, block_size=8)
    overlaps = {1: 4, 2: 1, 3: 0}
    base = DefaultWorkerSelector().select_worker(
        [1, 2, 3], dict(overlaps), 64, active, cfg
    )
    m = NetCostModel(recompute_ms_per_block=2.0)
    aware = NetworkAwareSelector(m).select_worker(
        [1, 2, 3], dict(overlaps), 64, active, cfg
    )
    assert aware.worker_id == base.worker_id
    assert aware.overlap_blocks == base.overlap_blocks


def test_network_aware_selector_avoids_loaded_and_hints_cheap_source():
    from dynamo_tpu.llm.kv_router.protocols import (
        ForwardPassMetrics, KvStats, WorkerStats,
    )

    def fpm(waiting):
        return ForwardPassMetrics(
            worker_id=0,
            worker=WorkerStats(
                request_active_slots=0, request_total_slots=4,
                num_requests_waiting=waiting,
            ),
            kv=KvStats(
                kv_active_blocks=0, kv_total_blocks=64,
                gpu_cache_usage_perc=0.0, gpu_prefix_cache_hit_rate=0.0,
            ),
        )

    # Worker 1 overlaps best but carries a deep queue; worker 2 is idle
    # and can pull the difference from cheap worker 3.
    view = {1: fpm(10), 2: fpm(0), 3: fpm(0)}
    m = NetCostModel(recompute_ms_per_block=2.0, fleet_view=lambda: view,
                     cache_s=0.0)
    m.observe_pull(3, 10, 2.0)      # worker 3: 0.2 ms/block, ratio 0.1
    active = ActiveSequences(block_size=8)
    cfg = RouterConfig(temperature=0.0, block_size=8, queue_weight=2.0)
    sel = NetworkAwareSelector(m).select_worker(
        [1, 2], {1: 8, 2: 0, 3: 8}, 64, active, cfg
    )
    assert sel.worker_id == 2, "queue depth ignored"
    assert sel.pull_hint is not None
    source, blocks = sel.pull_hint
    assert source == 3 and blocks == 8


# -- fleet harness e2e -------------------------------------------------------


def _mini_tenants():
    return default_tenants(scale=0.5, users=20_000)


def test_fleet_ab_planner_beats_equal_budget_static():
    """The test-scale autoscaling A/B (one diurnal period): the closed
    loop tracks the swing, the same mean budget frozen in time misses
    it. This guards the mechanism in tier-1."""
    ab = run_fleet_ab(duration_s=240.0, keep_streams=True)
    planner, static = ab["planner"], ab["static"]
    budget = ab["static_budget_replicas"]

    assert planner.broken_streams == 0 and static.broken_streams == 0
    assert planner.requests == static.requests > 5000
    assert planner.attainment_ttft >= 0.95, planner.summary()
    assert static.attainment_ttft < 0.85, static.summary()
    assert planner.attainment_ttft > static.attainment_ttft + 0.1
    # Equal budget, honestly: within 15% of the frozen pool.
    assert planner.mean_replicas <= budget * 1.15
    # The loop actually closed — both directions actuated, drains real.
    assert planner.scale_ups >= 2 and planner.scale_downs >= 2
    assert planner.drained_retired >= 1, planner.summary()
    assert planner.decisions["scale_up"] >= 2
    # Identical completed requests stream identical bytes across
    # scenarios (completions only — static sheds under the peak).
    compared = 0
    for rid, toks in planner.streams.items():
        other = static.streams.get(rid)
        if toks and other and len(other) == len(toks):
            assert other == toks, f"stream {rid} diverged across scenarios"
            compared += 1
    assert compared >= 1, "no completed request overlapped both scenarios"


def test_fleet_routing_ab_shifts_off_slow_peer():
    """NetKV at test scale: placement AND pulls shift off the slow,
    loaded peer; cohort TTFT improves; streams byte-identical."""
    r = run_routing_ab(duration_s=30.0)
    base, aware = r["overlap_only"], r["network_aware"]
    assert aware.streams == base.streams, "routing changed a stream"
    assert base.broken_streams == aware.broken_streams == 0
    slow = 0
    assert aware.pulls_by_source.get(slow, 0) * 4 <= base.pulls_by_source.get(slow, 1)
    assert aware.placements.get(slow, 0) * 2 <= base.placements.get(slow, 1)
    assert aware.ttft_p99_ms < base.ttft_p99_ms


def test_fleet_scale_down_drains_bit_identically():
    """Scale-down during active decode: the drained worker finishes
    every accepted stream before retiring, and the cohort's bytes match
    a run that never scaled at all."""
    tenants = [TenantSpec(name="t", users=500, rps=10.0, isl=32, osl=8,
                          shared_prefix_tokens=16)]

    def spec(chaos):
        return FleetSpec(
            tenants=tenants, duration_s=40.0, seed=3, planner_on=False,
            static_replicas=3, keep_streams=True, chaos=chaos,
        )

    baseline = FleetHarness(spec([])).run()
    h = FleetHarness(spec([ChaosEvent(t=15.0, action="drain", worker=1)]))
    drained = h.run()
    assert drained.broken_streams == 0
    assert drained.drained_retired == 1
    assert drained.streams == baseline.streams, (
        "drain changed client-visible bytes"
    )
    # The drained worker really was mid-work when told to go.
    w1 = [rid for rid, rec in h.recs.items() if 1 in rec.workers]
    assert w1, "worker 1 never held work — drain untested"
    # And no placements landed on it after the drain point.
    for rec in h.recs.values():
        if rec.arrival.t > 15.0:
            assert 1 not in rec.workers


def test_fleet_kill_during_scale_down_degrades_to_migration():
    """Chaos kill of a DRAINING worker mid-decode: the drain's
    completion promise degrades to the PR 6 migration replay — streams
    still finish byte-identical to the no-fault run."""
    tenants = [TenantSpec(name="t", users=500, rps=20.0, isl=32, osl=8,
                          shared_prefix_tokens=16)]

    def spec(chaos):
        return FleetSpec(
            tenants=tenants, duration_s=40.0, seed=3, planner_on=False,
            static_replicas=3, keep_streams=True, chaos=chaos,
        )

    baseline = FleetHarness(spec([])).run()
    h = FleetHarness(spec([
        ChaosEvent(t=15.0, action="drain", worker=1),
        # 100 ms later, while the drain is mid-flight: kill the victim.
        ChaosEvent(t=15.1, action="kill", worker=-1),
    ]))
    killed = h.run()
    assert killed.migrations >= 1, "kill hit an already-empty worker"
    assert killed.broken_streams == 0
    assert killed.drained_retired == 0, "killed worker counted as drained"
    assert killed.streams == baseline.streams, (
        "kill-during-drain broke a stream"
    )


def test_fleet_partition_degrades_to_recompute():
    """A partitioned peer fails pulls (charged, measured) — requests
    recompute locally and every stream still completes identically."""
    tenants = [TenantSpec(name="t", users=300, rps=8.0, isl=64, osl=6,
                          shared_prefix_tokens=48)]

    def spec(chaos):
        return FleetSpec(
            tenants=tenants, duration_s=30.0, seed=5, planner_on=False,
            static_replicas=3, keep_streams=True, chaos=chaos,
        )

    baseline = FleetHarness(spec([])).run()
    cut = FleetHarness(spec([
        ChaosEvent(t=5.0, action="partition", worker=0, duration_s=20.0),
    ])).run()
    assert cut.failed_pulls > 0, "partition never intercepted a pull"
    assert cut.broken_streams == 0
    assert cut.streams == baseline.streams


def test_mocker_profile_matches_cost_model():
    prof = mocker_profile(20_000.0, 100.0, 5_000.0, 4)
    p, d = from_profile(prof)
    # One 128-token prefill iteration: 20 ms + 128*0.1 ms.
    assert p.ttft_at(128) == pytest.approx(0.0328)
    # One decode iteration at full batch: 20 ms + 4*5 ms.
    assert d.itl_at(4) == pytest.approx(0.040)
