"""Overload robustness (ISSUE 10): admission control, per-tenant fair
queueing, deadline shedding, and disconnect-while-queued cleanup.

The contract under test, end to end: a saturated deployment DEGRADES —
it never breaks. Admitted streams complete bit-identically to an
unloaded run; everything else exits through a typed, retryable error
(429/503 + Retry-After on HTTP, shed/deadline wire markers on the data
plane); a flooding tenant cannot starve a light one (DRR fair queues);
and nothing queued leaks blocks or router pins when it is cancelled,
shed, or expired.
"""

import asyncio
import time
from contextlib import suppress

import pytest

from dynamo_tpu.engine.fair_queue import FairQueue
from dynamo_tpu.llm.admission import (
    AdmissionConfig,
    AdmissionController,
    resolve_deadline,
)
from dynamo_tpu.llm.mocker import MockEngineArgs, MockTpuEngine
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import chaos
from dynamo_tpu.runtime.engine import (
    Context,
    DeadlineExceededError,
    EngineOverloadedError,
)

pytestmark = [pytest.mark.unit, pytest.mark.pre_merge]


class Item:
    def __init__(self, name, tenant="", cost=1, priority=0):
        self.name = name
        self.tenant_id = tenant
        self.cost = cost
        self.priority = priority

    def __repr__(self):
        return f"Item({self.name})"


def fq(**kw):
    kw.setdefault("quantum", 8)
    kw.setdefault("cost_fn", lambda it: it.cost)
    return FairQueue(**kw)


# -- FairQueue unit surface ---------------------------------------------------


def test_fair_queue_single_tenant_is_fifo():
    """One tenant (or fairness off): pop order IS arrival order — the
    structural half of the bit-identity invariant."""
    for fair in (True, False):
        q = fq(fair=fair)
        items = [Item(f"i{i}", tenant="t", cost=3 + i) for i in range(10)]
        for it in items:
            q.append(it)
        assert [q.pop() for _ in range(10)] == items
        assert len(q) == 0 and not q


def test_fair_queue_drr_interleaves_heavy_and_light():
    """A heavy tenant's backlog cannot monopolize admission: with equal
    quanta, pops alternate between tenants even when the heavy tenant
    arrived first with 10x the requests."""
    q = fq(quantum=4)
    heavy = [Item(f"h{i}", tenant="heavy", cost=4) for i in range(10)]
    light = [Item(f"l{i}", tenant="light", cost=4) for i in range(2)]
    for it in heavy:
        q.append(it)
    for it in light:
        q.append(it)
    order = [q.pop().name for _ in range(6)]
    # Both light requests admit within the first two rounds, not after
    # the entire heavy backlog.
    assert "l0" in order[:2] or "l0" in order[:3]
    assert "l1" in order[:5]
    assert set(order) != {f"h{i}" for i in range(6)}


def test_fair_queue_token_cost_weighs_admission():
    """DRR is over TOKEN cost, not request count: a tenant of huge
    prompts earns the same token bandwidth as a tenant of small ones —
    so the small-prompt tenant admits ~cost_ratio more requests."""
    q = fq(quantum=8)
    for i in range(8):
        q.append(Item(f"big{i}", tenant="big", cost=16))
    for i in range(8):
        q.append(Item(f"small{i}", tenant="small", cost=2))
    first8 = [q.pop().name for _ in range(8)]
    n_small = sum(1 for n in first8 if n.startswith("small"))
    n_big = 8 - n_small
    assert n_small > n_big  # more small admissions per token of share


def test_fair_queue_priority_orders_within_tenant_only():
    q = fq()
    q.append(Item("a", tenant="t1", priority=0))
    q.append(Item("b", tenant="t1", priority=5))
    q.append(Item("c", tenant="t1", priority=5))
    assert [q.pop().name for _ in range(3)] == ["b", "c", "a"]
    # Fairness OFF: everyone shares one queue, so a client-controlled
    # priority must NOT jump it (that would be cross-tenant queue
    # jumping, and would break the off == exact-FIFO invariant).
    q = fq(fair=False)
    q.append(Item("a", tenant="t1", priority=0))
    q.append(Item("b", tenant="t2", priority=100))
    assert [q.pop().name for _ in range(2)] == ["a", "b"]


def test_fair_queue_sweep_and_remove_any_position():
    q = fq()
    items = [Item(f"i{i}", tenant=f"t{i % 2}") for i in range(6)]
    for it in items:
        q.append(it)
    removed = q.sweep(lambda it: it.name in ("i2", "i3", "i5"))
    assert {it.name for it in removed} == {"i2", "i3", "i5"}
    assert len(q) == 3 and items[2] not in q
    assert q.remove(items[0]) and not q.remove(items[0])
    # Draining a tenant entirely drops it from rotation + stats.
    q.sweep(lambda it: True)
    assert len(q) == 0 and q.stats() == {}


def test_fair_queue_appendleft_requeues_first():
    q = fq()
    a, b, c = Item("a", "t1"), Item("b", "t2"), Item("c", "t1")
    for it in (a, b, c):
        q.append(it)
    victim = q.pop()
    q.appendleft(victim)  # preemption requeue: next admission candidate
    assert q.pop() is victim


def test_fair_queue_stats_snapshot():
    q = fq()
    q.append(Item("a", tenant="gold", cost=5))
    q.append(Item("b", tenant="", cost=2))
    st = q.stats()
    assert st["gold"]["depth"] == 1.0
    assert st["default"]["depth"] == 1.0


# -- frontend admission unit surface -----------------------------------------


def test_token_bucket_rate_limit_and_retry_after():
    clock = [0.0]
    ctl = AdmissionController(
        AdmissionConfig(tenant_rate=2.0, tenant_burst=2), clock=lambda: clock[0]
    )
    assert ctl.admit("a").admitted and ctl.admit("a").admitted
    d = ctl.admit("a")
    assert not d.admitted and d.status == 429 and d.reason == "rate_limit"
    assert 0 < d.retry_after_s <= 0.5 + 1e-6  # 2 req/s -> half-second refill
    # Another tenant has its own bucket.
    assert ctl.admit("b").admitted
    # Refill admits again.
    clock[0] += 0.6
    assert ctl.admit("a").admitted
    assert ctl.shed_total == 1


def test_inflight_ceiling_sheds_503():
    ctl = AdmissionController(AdmissionConfig(max_inflight=2))
    assert ctl.admit("x").admitted and ctl.admit("y").admitted
    d = ctl.admit("z")
    assert not d.admitted and d.status == 503 and d.reason == "queue_full"
    ctl.release()
    assert ctl.admit("z").admitted


def test_ceiling_rejection_refunds_rate_token():
    """A 503 at the ceiling must not also burn the tenant's rate token —
    the advertised retry would then 429 for capacity never used."""
    clock = [0.0]
    ctl = AdmissionController(
        AdmissionConfig(tenant_rate=1.0, tenant_burst=1, max_inflight=1),
        clock=lambda: clock[0],
    )
    assert ctl.admit("a").admitted  # fills the ceiling, spends a's token
    d = ctl.admit("b")              # fresh bucket, ceiling-shed
    assert not d.admitted and d.reason == "queue_full"
    ctl.release()
    # b's token was refunded: it admits immediately, no 429 detour.
    assert ctl.admit("b").admitted


def test_resolve_deadline_header_wins_and_validates():
    ms, epoch, err = resolve_deadline(500.0, None, now_epoch=100.0)
    assert (ms, epoch, err) == (500.0, 100.5, None)
    ms, epoch, err = resolve_deadline(500.0, "250", now_epoch=100.0)
    assert (ms, epoch) == (250.0, 100.25) and err is None
    assert resolve_deadline(None, None)[0] is None
    assert resolve_deadline(None, "nope")[2] is not None
    assert resolve_deadline(-5.0, None)[2] is not None


def test_worker_monitor_marks_saturated_queues_busy():
    from dynamo_tpu.llm.kv_router.protocols import (
        ForwardPassMetrics,
        KvStats,
        WorkerStats,
    )
    from dynamo_tpu.runtime.worker_monitor import WorkerMonitor

    mon = WorkerMonitor.__new__(WorkerMonitor)
    mon.busy_threshold = 0.95
    mon.queue_threshold = None  # auto: the worker-exported queue limit
    mon.busy = set()
    mon.on_busy_change = lambda w, b: None
    sat = ForwardPassMetrics(
        worker_id=1,
        worker=WorkerStats(num_requests_waiting=4, queue_limit=4),
        kv=KvStats(gpu_cache_usage_perc=0.1),
    )
    idle = ForwardPassMetrics(
        worker_id=2,
        worker=WorkerStats(num_requests_waiting=1, queue_limit=4),
        kv=KvStats(gpu_cache_usage_perc=0.1),
    )
    mon._on_metrics(sat)
    mon._on_metrics(idle)
    assert mon.busy == {1}
    assert mon.eligible([1, 2]) == [2]
    # Explicit threshold overrides the exported limit.
    mon.queue_threshold = 1
    mon._on_metrics(idle)
    assert mon.busy == {1, 2}
    assert mon.eligible([1, 2]) == [1, 2]  # all busy -> full set fallback


def test_fair_queue_gauges_bounded_and_removed():
    """Tenant labels are client-controlled: the export caps distinct
    series (overflow under __other__) and REMOVES drained tenants'
    series — a rotating x-tenant-id spray cannot grow /metrics forever."""
    from dynamo_tpu.runtime.status_server import (
        MAX_TENANT_GAUGES,
        SystemStatusServer,
        bind_fair_queue_gauges,
    )

    stats: dict = {}
    status = SystemStatusServer()
    bind_fair_queue_gauges(status, lambda: stats)

    def render() -> str:
        for hook in status.before_render:
            hook()
        return status.metrics.render().decode()

    stats = {
        f"t{i}": {"depth": float(i), "deficit": 0.0}
        for i in range(MAX_TENANT_GAUGES + 20)
    }
    text = render()
    assert 'tenant="__other__"' in text
    assert text.count("scheduler_tenant_queue_depth{") == MAX_TENANT_GAUGES + 1
    # Everything drains: every tenant series disappears from the output.
    stats = {}
    text = render()
    assert "scheduler_tenant_queue_depth{" not in text


def test_chaos_burst_plan_validates():
    plan = chaos.ChaosPlan.burst(slow_s=0.01, shed_p=0.25, seed=7)
    points = {r.point for r in plan.rules}
    assert points == {"engine.step", "frontend.admit"}
    with pytest.raises(ValueError, match="unknown chaos point"):
        chaos.ChaosRule(point="frontend.nope", action="drop")


# -- engine-level behavior (real EngineCore, tiny model) ----------------------


def _core(**over):
    from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model

    return EngineCore(tiny_model(), tiny_engine(**over), seed=0)


def _req(prompt, rid, max_tokens=8, temperature=0.0, seed=None, **kw):
    return PreprocessedRequest(
        model="tiny",
        token_ids=prompt,
        request_id=rid,
        sampling=SamplingOptions(temperature=temperature, seed=seed),
        stop=StopConditions(max_tokens=max_tokens),
        **kw,
    )


def _run_all(core, seqs, max_steps=2000):
    done = {s.request_id: [] for s in seqs}
    finishes = {}
    for _ in range(max_steps):
        for seq, out in core.step():
            done[seq.request_id].extend(out.token_ids)
            if out.finish_reason:
                finishes[seq.request_id] = out.finish_reason
        if len(finishes) == len(seqs):
            break
    return done, finishes


def test_single_tenant_bit_identity_fair_on_vs_off():
    """Acceptance: single-tenant, under-limit traffic is bit-identical
    with the fairness scheduler on vs off — greedy AND seeded
    temperature, waves AND chunked."""
    import numpy as np

    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 200, size=12 + 7 * i)) for i in range(5)]

    def run(fair, scheduling):
        core = _core(fair_scheduling=fair, scheduling=scheduling)
        seqs = []
        for i, p in enumerate(prompts):
            temp = 0.0 if i % 2 == 0 else 0.8
            seqs.append(
                core.add_request(
                    _req(p, f"r{i}", max_tokens=6, temperature=temp, seed=11 + i)
                )
            )
        return _run_all(core, seqs)

    for scheduling in ("waves", "chunked"):
        off = run(False, scheduling)
        on = run(True, scheduling)
        assert on == off, f"fairness changed tokens under {scheduling}"


def test_engine_deadline_expiry_typed_and_leak_free():
    """A request whose deadline passes while QUEUED gets the typed error
    frame; blocks and pins stay untouched (it was never admitted)."""
    core = _core(max_num_seqs=1)
    a = core.add_request(_req([1] * 16, "running", max_tokens=20))
    # Fill the single slot so the second request stays queued.
    core.step()
    assert a in core.running
    expired = core.add_request(
        _req([2] * 16, "expired", deadline_epoch=time.time() - 1.0)
    )
    outs = []
    for _ in range(5):
        outs.extend(core.step())
        if any(s.request_id == "expired" for s, _ in outs):
            break
    shed = [(s, o) for s, o in outs if s.request_id == "expired"]
    assert len(shed) == 1
    s, o = shed[0]
    assert o.finish_reason == "error" and o.meta["shed"] == "deadline"
    assert "expired" in o.meta["detail"]
    assert core.sched_stats["deadline_expired_total"] == 1
    assert expired not in core.waiting and expired not in core.running
    # Zero leaked blocks: every allocated block belongs to the RUNNING
    # sequence (the expired one held nothing and pinned nothing).
    assert not expired.block_ids and not expired.pinned_hashes
    assert (
        core.allocator.capacity - core.allocator.free_blocks
        == len(a.block_ids)
    )
    # An ADMITTED request past its deadline still completes (no broken
    # streams, ever).
    a.deadline_epoch = time.time() - 1.0
    _done, fin = _run_all(core, [a])
    assert fin["running"] == "length" and a.generated == 20


def test_engine_bounded_queue_sheds_typed():
    core = _core(max_waiting=2, max_num_seqs=1)
    core.add_request(_req([1] * 8, "r0", max_tokens=4))
    core.step()  # admit r0 so the queue is purely waiting depth
    core.add_request(_req([2] * 8, "r1"))
    core.add_request(_req([3] * 8, "r2"))
    with pytest.raises(EngineOverloadedError, match="queue full"):
        core.add_request(_req([4] * 8, "r3"))
    assert core.sched_stats["shed_total"] == 1
    assert core.scheduler_stats()["queue_limit"] == 2
    fpm = core.metrics()
    assert fpm.worker.queue_limit == 2
    assert fpm.worker.requests_shed_total == 1


def test_engine_cancel_while_queued_removes_mid_queue():
    """Satellite: a cancelled request leaves the waiting queue from ANY
    position — even parked behind an unadmittable head — and leaks
    nothing."""
    core = _core(max_num_seqs=1)
    a = core.add_request(_req([1] * 16, "a", max_tokens=30))
    core.step()
    b = core.add_request(_req([2] * 16, "b", max_tokens=4))
    c = core.add_request(_req([3] * 16, "c", max_tokens=4))
    core.step()
    assert b in core.waiting and c in core.waiting
    core.cancel_request(c)  # cancel BEHIND the queue head
    core.step()
    assert c not in core.waiting and b in core.waiting
    # The cancelled request held nothing; everything allocated is a's.
    assert not c.block_ids and not c.pinned_hashes
    assert (
        core.allocator.capacity - core.allocator.free_blocks
        == len(a.block_ids)
    )
    done, fin = _run_all(core, [a, b])
    assert fin == {"a": "length", "b": "length"}


async def test_tpu_engine_surfaces_deadline_as_typed_error():
    from dynamo_tpu.engine import TpuEngine

    core = _core(max_num_seqs=1)
    engine = TpuEngine(core)
    ctx = Context()

    async def consume(gen):
        return [o async for o in gen]

    blocker = asyncio.create_task(
        consume(
            engine.generate(
                _req([1] * 16, "blk", max_tokens=40).to_wire(), Context()
            )
        )
    )
    for _ in range(100):
        await asyncio.sleep(0.01)
        if core.running:
            break
    with pytest.raises(DeadlineExceededError, match="expired"):
        async for _ in engine.generate(
            _req([2] * 16, "late", deadline_epoch=time.time() - 1.0).to_wire(),
            ctx,
        ):
            pass
    await blocker


# -- mocker fairness property (virtual clock) --------------------------------


def _mock_seq(rid, prompt, max_tokens, tenant, deadline=None):
    from dynamo_tpu.llm.mocker.engine import _Seq
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    s = _Seq(
        request_id=rid,
        prompt=prompt,
        max_tokens=max_tokens,
        out=asyncio.Queue(),
        seq=TokenBlockSequence(prompt, 8),
        prompt_hashes=compute_seq_hashes(prompt, 8),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        tenant_id=tenant,
    )
    s.deadline_epoch = deadline
    return s


def _drive_mocker(fair, heavy_n, light_arrivals, max_vt=60.0):
    """Deterministic virtual-clock drive: a heavy tenant floods at t=0
    with short completions (slots turn over fast — admission order, not
    preemption, is what is under test), a light tenant arrives on a
    schedule; returns per-request first-token virtual times."""
    args = MockEngineArgs(
        num_kv_blocks=4096, block_size=8, max_num_seqs=2,
        max_num_batched_tokens=128, enable_prefix_caching=False,
        fair_scheduling=fair, fair_quantum=32,
    )
    eng = MockTpuEngine(args)
    heavy = [
        _mock_seq(f"h{i}", [1 + (i % 7)] * 32, 1, "heavy")
        for i in range(heavy_n)
    ]
    light = [
        _mock_seq(f"l{i}", [9] * 32, 4, "light")
        for i in range(len(light_arrivals))
    ]
    pending = sorted(
        zip(light_arrivals, light), key=lambda p: p[0]
    )
    for s in heavy:
        eng._waiting.append(s)
    vt = 0.0
    first: dict[str, float] = {}
    live = list(heavy)
    while vt < max_vt and (pending or any(
        s in eng._waiting or s in eng._running for s in live
    )):
        while pending and pending[0][0] <= vt:
            _, s = pending.pop(0)
            s.t_submit_vt = vt
            eng._waiting.append(s)
            live.append(s)
        eng._admit()
        p, d = eng._step()
        vt += (
            args.base_iter_us
            + p * args.prefill_us_per_token
            + d * args.decode_us_per_seq
        ) / 1e6
        for s in live:
            while not s.out.empty():
                item = s.out.get_nowait()
                if isinstance(item, dict) and item.get("token_ids"):
                    first.setdefault(s.request_id, vt)
    return {
        rid: t - getattr(
            next(s for s in live if s.request_id == rid), "t_submit_vt", 0.0
        )
        for rid, t in first.items()
    }


def test_mocker_fairness_bounds_light_tenant_ttft():
    """Acceptance: under a heavy-tenant flood, fairness on holds the
    light tenant's worst TTFT within 2x its unloaded value; FIFO does
    not. Deterministic mocker virtual clock."""
    arrivals = [0.02 * i for i in range(6)]
    unloaded = _drive_mocker(fair=False, heavy_n=0, light_arrivals=arrivals)
    fifo = _drive_mocker(fair=False, heavy_n=40, light_arrivals=arrivals)
    fair = _drive_mocker(fair=True, heavy_n=40, light_arrivals=arrivals)

    def light_worst(res):
        vals = [t for r, t in res.items() if r.startswith("l")]
        assert len(vals) == len(arrivals), f"light requests lost: {res}"
        return max(vals)

    u, f_on, f_off = light_worst(unloaded), light_worst(fair), light_worst(fifo)
    assert f_on <= 2.0 * u, (
        f"fair scheduling failed the SLO: worst light TTFT {f_on:.3f}s vs "
        f"unloaded {u:.3f}s"
    )
    assert f_off > 2.0 * u, (
        f"FIFO unexpectedly held the SLO ({f_off:.3f}s vs {u:.3f}s) — "
        "the load is not saturating; fix the test setup"
    )
    assert f_on < f_off


def test_mocker_deadline_expiry_on_virtual_clock():
    """Queued-past-deadline requests shed with the typed frame on the
    INJECTED clock; pins/partials fully released."""
    args = MockEngineArgs(
        num_kv_blocks=256, block_size=8, max_num_seqs=1,
        enable_prefix_caching=False,
    )
    eng = MockTpuEngine(args)
    clock = [1000.0]
    eng.clock = lambda: clock[0]
    running = _mock_seq("run", [1] * 16, 8, "")
    late = _mock_seq("late", [2] * 16, 8, "", deadline=1005.0)
    eng._waiting.append(running)
    eng._waiting.append(late)
    eng._admit()
    assert running in eng._running and late in eng._waiting
    clock[0] = 1010.0  # virtual deadline passes while queued
    eng._admit()
    assert late not in eng._waiting
    item = late.out.get_nowait()
    assert item["finish_reason"] == "error"
    assert item["meta"]["shed"] == "deadline"
    assert eng.sched_stats["deadline_expired_total"] == 1
    # Drain the running seq; every block returns.
    for _ in range(50):
        eng._admit()
        eng._step()
        if running not in eng._running:
            break
    assert eng.kv.free_blocks == eng.kv.capacity


async def test_mocker_generate_bounded_queue_and_deadline_raise():
    eng = MockTpuEngine(
        MockEngineArgs(
            num_kv_blocks=256, block_size=4, max_num_seqs=1, max_waiting=1,
            speedup_ratio=1000.0, decode_us_per_seq=50000.0,
        )
    )

    def wire(rid, **kw):
        return PreprocessedRequest(
            model="mock", token_ids=[1] * 12, request_id=rid,
            stop=StopConditions(max_tokens=50), **kw,
        ).to_wire()

    async def consume(gen):
        with suppress(Exception):
            async for _ in gen:
                pass

    t1 = asyncio.create_task(consume(eng.generate(wire("a"), Context())))
    for _ in range(200):
        await asyncio.sleep(0.005)
        if eng._running:
            break
    t2 = asyncio.create_task(consume(eng.generate(wire("b"), Context())))
    for _ in range(200):
        await asyncio.sleep(0.005)
        if len(eng._waiting):
            break
    with pytest.raises(EngineOverloadedError, match="queue full"):
        async for _ in eng.generate(wire("c"), Context()):
            pass
    assert eng.sched_stats["shed_total"] == 1
    t1.cancel()
    t2.cancel()
    for t in (t1, t2):
        with suppress(asyncio.CancelledError):
            await t


async def test_mocker_generate_deadline_expired_raise():
    eng = MockTpuEngine(
        MockEngineArgs(
            num_kv_blocks=256, block_size=4, max_num_seqs=1,
            speedup_ratio=1000.0, decode_us_per_seq=20000.0,
        )
    )

    async def consume(gen):
        with suppress(Exception):
            async for _ in gen:
                pass

    blocker = asyncio.create_task(
        consume(
            eng.generate(
                PreprocessedRequest(
                    model="mock", token_ids=[1] * 12, request_id="blk",
                    stop=StopConditions(max_tokens=100),
                ).to_wire(),
                Context(),
            )
        )
    )
    for _ in range(200):
        await asyncio.sleep(0.005)
        if eng._running:
            break
    with pytest.raises(DeadlineExceededError, match="expired"):
        async for _ in eng.generate(
            PreprocessedRequest(
                model="mock", token_ids=[2] * 12, request_id="late",
                stop=StopConditions(max_tokens=4),
                deadline_epoch=time.time() - 1.0,
            ).to_wire(),
            Context(),
        ):
            pass
    blocker.cancel()
    with suppress(asyncio.CancelledError):
        await blocker


# -- wire + migration behavior ------------------------------------------------


async def test_shed_worker_retries_elsewhere_stream_intact():
    """A full worker's shed is the PR 6 retry-elsewhere shape: migration
    moves the request to the other instance and the client stream is
    bit-identical to a clean run — zero broken streams."""
    from dynamo_tpu.llm.migration import Migration
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer

    store = StoreServer()
    await store.start()
    rts, engines = [], []
    try:
        for i, args in enumerate(
            (
                # Worker 0: one slot, slow, queue bounded at 1 -> sheds.
                MockEngineArgs(
                    num_kv_blocks=256, block_size=8, max_num_seqs=1,
                    max_waiting=1, decode_us_per_seq=200000.0,
                ),
                # Worker 1: healthy.
                MockEngineArgs(num_kv_blocks=256, block_size=8),
            )
        ):
            rt = await DistributedRuntime.create(store.address)
            engine = MockTpuEngine(args)
            ep = rt.namespace("ovl").component("w").endpoint("generate")

            async def handler(req, ctx, engine=engine):
                async for out in engine.generate(req, ctx):
                    yield out

            await ep.serve(handler)
            rts.append(rt)
            engines.append(engine)
        client_rt = await DistributedRuntime.create(store.address)
        client = await (
            client_rt.namespace("ovl").component("w").endpoint("generate").client()
        )
        await client.wait_for_instances(2, timeout=10)

        def req(rid, n=6):
            return PreprocessedRequest(
                model="mock", token_ids=[1, 2, 3, 4], request_id=rid,
                stop=StopConditions(max_tokens=n),
            )

        # Stuff worker 0: one running (slow), one queued (at the limit).
        ids = sorted(client.instance_ids())
        w0 = ids[0]
        s0 = await client.direct(w0, req("fill0", 400).to_wire())
        task0 = asyncio.create_task(s0.__anext__())
        for _ in range(200):
            await asyncio.sleep(0.005)
            if engines[0]._running:
                break
        s1 = await client.direct(w0, req("fill1", 4).to_wire())

        migration = Migration(
            client=client, push_router=None, mode="round_robin", limit=3
        )
        streams = []
        for i in range(4):
            toks = []
            async for out in migration.generate(req(f"m{i}", 6)):
                toks.extend(out.token_ids)
            streams.append(toks)
        expect = [97 + (i % 26) for i in range(6)]
        assert all(s == expect for s in streams), streams
        # At least one round-robin pick hit the stuffed worker and shed.
        assert engines[0].sched_stats["shed_total"] >= 1
        task0.cancel()
        with suppress(Exception):
            await task0
        with suppress(Exception):
            await s1.kill()
        await client.stop()
        await client_rt.shutdown()
    finally:
        for rt in rts:
            with suppress(ConnectionError, OSError):
                await rt.shutdown()
        await store.stop()


async def test_migration_does_not_retry_deadline_errors():
    """DeadlineExceededError is typed and final: the migration operator
    must pass it through without burning replay attempts."""
    from dynamo_tpu.llm.migration import MigrationOperator
    from dynamo_tpu.runtime.pipeline import PipelineBuilder

    calls = []

    class DeadlineBackend:
        async def generate(self, pre, ctx):
            calls.append(pre.request_id)
            raise DeadlineExceededError("deadline exceeded: test")
            yield  # pragma: no cover

    pipe = PipelineBuilder().link(MigrationOperator(limit=3)).backend(
        DeadlineBackend()
    )
    with pytest.raises(DeadlineExceededError):
        async for _ in pipe.generate(
            PreprocessedRequest(model="m", token_ids=[1], request_id="r"),
            Context(),
        ):
            pass
    assert calls == ["r"]  # exactly one attempt


async def test_disconnect_while_queued_cleans_engine_and_router():
    """Satellite e2e: cancel a request still in the scheduler queue —
    the worker drops the sequence, every block returns, and the router
    pin is freed."""
    from dynamo_tpu.llm.kv_router.protocols import RouterConfig
    from dynamo_tpu.llm.kv_router.router import KvPushRouter, KvRouter
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer

    store = StoreServer()
    await store.start()
    rt = await DistributedRuntime.create(store.address)
    client_rt = await DistributedRuntime.create(store.address)
    engine = MockTpuEngine(
        MockEngineArgs(
            num_kv_blocks=256, block_size=8, max_num_seqs=1,
            decode_us_per_seq=20000.0,
        )
    )
    try:
        ep = rt.namespace("dq").component("w").endpoint("generate")

        async def handler(req, ctx):
            async for out in engine.generate(req, ctx):
                yield out

        await ep.serve(handler)
        client = await (
            client_rt.namespace("dq").component("w").endpoint("generate").client()
        )
        await client.wait_for_instances(1, timeout=10)
        router = KvRouter(
            client_rt.store, "dq", "w", RouterConfig(use_kv_events=False, block_size=8)
        )
        push = KvPushRouter(client, router)

        async def stream(rid, max_tokens):
            payload = PreprocessedRequest(
                model="mock", token_ids=[1] * 16, request_id=rid,
                stop=StopConditions(max_tokens=max_tokens),
            ).to_wire()
            async for item in push.generate(
                payload, request_id=rid, token_ids=[1] * 16
            ):
                pass

        t1 = asyncio.create_task(stream("long", 300))
        for _ in range(200):
            await asyncio.sleep(0.005)
            if engine._running:
                break
        t2 = asyncio.create_task(stream("queued", 4))
        for _ in range(200):
            await asyncio.sleep(0.005)
            if len(engine._waiting):
                break
        assert len(engine._waiting) == 1
        assert "queued" in router.active._seqs
        t2.cancel()  # the client vanished mid-queue
        with suppress(asyncio.CancelledError):
            await t2
        for _ in range(400):
            await asyncio.sleep(0.005)
            if not len(engine._waiting):
                break
        assert not len(engine._waiting), "cancelled request stuck in queue"
        assert "queued" not in router.active._seqs, "router pin leaked"
        t1.cancel()
        with suppress(asyncio.CancelledError):
            await t1
        for _ in range(400):
            await asyncio.sleep(0.005)
            if engine.kv.free_blocks == engine.kv.capacity:
                break
        assert engine.kv.free_blocks == engine.kv.capacity, "blocks leaked"
        assert "long" not in router.active._seqs
        await client.stop()
    finally:
        with suppress(ConnectionError, OSError):
            await client_rt.shutdown()
        with suppress(ConnectionError, OSError):
            await rt.shutdown()
        await store.stop()


async def test_streaming_deadline_expiry_is_typed_503_e2e():
    """A STREAMING request that expires in the worker queue must answer
    a typed 503 — the frontend pulls the first chunk before committing
    the 200 SSE headers, so pre-first-token sheds keep the full error
    contract (status, code, Retry-After) instead of an in-band error."""
    import aiohttp

    from dynamo_tpu.backends.mocker.main import run_mocker
    from dynamo_tpu.frontend.main import run_frontend
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer

    store = StoreServer()
    await store.start()
    worker_rt = await DistributedRuntime.create(store.address)
    served = asyncio.Event()
    worker = asyncio.create_task(
        run_mocker(
            worker_rt, model_name="mock",
            engine_args=MockEngineArgs(
                num_kv_blocks=512, block_size=8, max_num_seqs=1,
                decode_us_per_seq=50000.0,
            ),
            served_event=served,
        )
    )
    await asyncio.wait_for(served.wait(), 30)
    front_rt = await DistributedRuntime.create(store.address)
    ready = asyncio.Event()
    services: list = []
    frontend = asyncio.create_task(
        run_frontend(
            front_rt, http_host="127.0.0.1", http_port=0, router_mode="kv",
            ready_event=ready, service_out=services,
        )
    )
    await asyncio.wait_for(ready.wait(), 30)
    base = f"http://127.0.0.1:{services[0].port}"
    try:
        async with aiohttp.ClientSession() as s:
            for _ in range(200):
                async with s.get(f"{base}/v1/models") as r:
                    if (await r.json())["data"]:
                        break
                await asyncio.sleep(0.05)
            url = f"{base}/v1/chat/completions"

            async def blocker():
                with suppress(Exception):
                    async with s.post(
                        url,
                        json={
                            "model": "mock", "stream": True,
                            "messages": [{"role": "user", "content": "x"}],
                            "max_tokens": 200, "temperature": 0,
                        },
                    ) as r:
                        async for _ in r.content:
                            pass

            t = asyncio.create_task(blocker())
            await asyncio.sleep(0.3)  # blocker occupies the single slot
            async with s.post(
                url,
                json={
                    "model": "mock", "stream": True,
                    "messages": [{"role": "user", "content": "late"}],
                    "max_tokens": 4, "temperature": 0,
                },
                headers={"x-request-deadline-ms": "200"},
            ) as r:
                assert r.status == 503, await r.text()
                assert "Retry-After" in r.headers
                err = (await r.json())["error"]
                assert err["type"] == "deadline_exceeded"
                assert err["code"] == "deadline" and err["retryable"] is True
            t.cancel()
            with suppress(asyncio.CancelledError):
                await t
    finally:
        frontend.cancel()
        worker.cancel()
        for task in (frontend, worker):
            with suppress(asyncio.CancelledError):
                await task
        for rt in (front_rt, worker_rt):
            with suppress(ConnectionError, OSError):
                await rt.shutdown()
        await store.stop()


# -- frontend e2e (admission + draining + chaos shed) -------------------------


async def test_frontend_overload_contract_e2e():
    """One fleet, the whole frontend contract: 429 + Retry-After on the
    tenant rate limit (per-tenant isolation), 503 at the in-flight
    ceiling, chaos-plan shed as clean 503, /health flips to draining,
    and admitted streams complete normally throughout."""
    import aiohttp

    from dynamo_tpu.backends.mocker.main import run_mocker
    from dynamo_tpu.frontend.main import run_frontend
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer

    store = StoreServer()
    await store.start()
    worker_rt = await DistributedRuntime.create(store.address)
    served = asyncio.Event()
    worker = asyncio.create_task(
        run_mocker(
            worker_rt, model_name="mock",
            engine_args=MockEngineArgs(
                num_kv_blocks=512, block_size=8, speedup_ratio=1000.0
            ),
            served_event=served,
        )
    )
    await asyncio.wait_for(served.wait(), 30)
    front_rt = await DistributedRuntime.create(store.address)
    ready = asyncio.Event()
    services: list = []
    frontend = asyncio.create_task(
        run_frontend(
            front_rt, http_host="127.0.0.1", http_port=0, router_mode="kv",
            ready_event=ready, service_out=services,
            admission=AdmissionConfig(tenant_rate=2.0, tenant_burst=2),
        )
    )
    await asyncio.wait_for(ready.wait(), 30)
    service = services[0]
    base = f"http://127.0.0.1:{service.port}"

    def body(stream=False, max_tokens=4):
        return {
            "model": "mock",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": max_tokens,
            "temperature": 0,
            "stream": stream,
        }

    try:
        async with aiohttp.ClientSession() as s:
            for _ in range(200):
                async with s.get(f"{base}/v1/models") as r:
                    if (await r.json())["data"]:
                        break
                await asyncio.sleep(0.05)

            url = f"{base}/v1/chat/completions"
            # Burst of 2 admits; the third 429s with Retry-After.
            for _ in range(2):
                async with s.post(url, json=body()) as r:
                    assert r.status == 200, await r.text()
            async with s.post(url, json=body()) as r:
                assert r.status == 429
                assert "Retry-After" in r.headers
                err = (await r.json())["error"]
                assert err["type"] == "rate_limit_error"
                assert err["code"] == "rate_limit" and err["retryable"] is True
            # Another tenant is unaffected (its own bucket).
            async with s.post(
                url, json=body(), headers={"x-tenant-id": "gold"}
            ) as r:
                assert r.status == 200, await r.text()
            # Shed counter visible on frontend /metrics.
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
            assert "frontend_requests_shed_total" in text
            assert 'reason="rate_limit"' in text

            # In-flight ceiling: retryable 503 at the cap.
            service.admission.config.max_inflight = 1
            service.admission.inflight = 1  # simulate one stuck request
            async with s.post(
                url, json=body(), headers={"x-tenant-id": "ceil"}
            ) as r:
                assert r.status == 503
                err = (await r.json())["error"]
                assert err["code"] == "queue_full" and err["retryable"] is True
            service.admission.inflight = 0

            # Malformed deadline header -> 400; valid one -> 200.
            async with s.post(
                url, json=body(),
                headers={"x-tenant-id": "d", "x-request-deadline-ms": "soon"},
            ) as r:
                assert r.status == 400
            async with s.post(
                url, json=body(),
                headers={"x-tenant-id": "d", "x-request-deadline-ms": "30000"},
            ) as r:
                assert r.status == 200, await r.text()

            # Chaos shed at frontend.admit: clean 503, never a 500.
            chaos.install(
                chaos.ChaosPlan(
                    rules=[
                        chaos.ChaosRule(
                            point="frontend.admit", action="drop", count=1
                        )
                    ]
                )
            )
            try:
                async with s.post(
                    url, json=body(), headers={"x-tenant-id": "cx"}
                ) as r:
                    assert r.status == 503
                    assert (await r.json())["error"]["retryable"] is True
                    assert "Retry-After" in r.headers
            finally:
                chaos.uninstall()

            # Draining: health goes dark and new requests shed.
            front_rt._draining = True
            async with s.get(f"{base}/health") as r:
                assert r.status == 503
                assert (await r.json())["status"] == "draining"
            async with s.post(
                url, json=body(), headers={"x-tenant-id": "dr"}
            ) as r:
                assert r.status == 503
                assert (await r.json())["error"]["code"] == "draining"
            front_rt._draining = False
            async with s.get(f"{base}/health") as r:
                assert r.status == 200
                assert (await r.json())["status"] == "healthy"
    finally:
        frontend.cancel()
        worker.cancel()
        for t in (frontend, worker):
            with suppress(asyncio.CancelledError):
                await t
        with suppress(ConnectionError, OSError):
            await front_rt.shutdown()
        with suppress(ConnectionError, OSError):
            await worker_rt.shutdown()
        await store.stop()
