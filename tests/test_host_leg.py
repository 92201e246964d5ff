"""The host's leg between a landing and the next megastep's enqueue (ISSUE
40): a megastep's lane inputs cross as one packed array, a step's KV events
cross to the loop in one hop (that a lane is itself, and that a plan at 128
lanes compares none: tests/test_laguna.py). Nothing a program computes
changes: the streams are the parent's, to the token."""

from __future__ import annotations

import hashlib
import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.backends.jax.main import StepKvEvents
from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine import core as core_mod
from dynamo_tpu.engine.config import tiny_laguna, tiny_lfm2
from dynamo_tpu.engine.programs import (
    LANE_COLS,
    MEGASTEP_WATCH_W,
    pack_lanes,
    unpack_lanes,
)
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.parallel.sharding import make_mesh

RECORDED = Path(__file__).parent / "fixtures" / "host_leg" / "streams_128.json"
NAMES = ("tokens", "positions", "active", "seeds", "counters", "temperature",
         "top_k", "top_p", "watch", "budgets", "min_left")


def _request(i: int, prompt: list[int], max_tokens: int, **sampling) -> PreprocessedRequest:
    stop_ids = sampling.pop("stop_token_ids", [])
    return PreprocessedRequest(
        model="tiny", token_ids=prompt, request_id=f"r{i}",
        sampling=SamplingOptions(seed=1000 + i, **sampling),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True,
                            stop_token_ids=stop_ids))


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


# -- one packed buffer of lane inputs ----------------------------------------------


def _unpack(lanes, feed):
    return jax.jit(unpack_lanes)(lanes, feed)


@pytest.mark.parametrize("fed", [False, True], ids=["host-tokens", "fed"])
def test_the_packed_array_unpacks_to_the_twelve_bit_for_bit(fed):
    """Every bit pattern a column may carry: negative ints, a seed at the
    int32 edge, floats that are no number, a negative zero, a denormal."""
    B, W = 16, MEGASTEP_WATCH_W
    rs = np.random.RandomState(3)
    ints = lambda lo, hi: rs.randint(lo, hi, size=B).astype(np.int32)  # noqa: E731
    temperature = rs.rand(B).astype(np.float32)
    temperature[:5] = [np.nan, -0.0, np.inf, 1e-45, np.float32(0.1)]
    top_p = np.linspace(0.0, 1.0, B).astype(np.float32)
    top_p[-1] = np.nextafter(np.float32(1.0), np.float32(0.0))
    seeds = ints(-2**31, 2**31 - 1)
    seeds[:2] = [-2**31, 2**31 - 1]
    feed_idx = np.where(rs.rand(B) < 0.5, ints(0, 40), -1).astype(np.int32) if fed else None
    host = dict(
        tokens=ints(0, 50_000), positions=ints(0, 10_000), active=rs.rand(B) < 0.8,
        seeds=seeds, counters=ints(0, 4096), temperature=temperature,
        top_k=ints(0, 64), top_p=top_p, watch=rs.randint(-1, 300, size=(B, W)).astype(np.int32),
        budgets=ints(1, 4096), min_left=ints(0, 9))
    lanes = pack_lanes(host["tokens"], feed_idx, *[host[n] for n in NAMES[1:]])
    assert lanes.shape == (B, LANE_COLS) and lanes.dtype == np.int32
    feed = np.arange(1000, 1040, dtype=np.int32)
    got = dict(zip(NAMES, _unpack(jnp.asarray(lanes), jnp.asarray(feed))))
    want = dict(host)
    if fed:
        want["tokens"] = np.where(feed_idx >= 0, feed[np.clip(feed_idx, 0, 39)], host["tokens"])
    for name in NAMES:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]), err_msg=name)


def _capture(core: EngineCore, prompts, max_tokens=20):
    """Serve ``prompts``; every megastep's twelve host arrays as the
    dispatch assembled them, and what its program was handed."""
    packs, calls = [], []
    pack = core_mod.pack_lanes

    def packing(*arrays):
        packs.append([None if a is None else np.array(a) for a in arrays])
        return pack(*arrays)

    program = core._decode

    def decode(params, cache, lanes, tables, feed, **static):
        calls.append((lanes, tables, feed))
        return program(params, cache, lanes, tables, feed, **static)

    core._decode = decode
    core_mod.pack_lanes = packing
    try:
        seqs = [core.add_request(_request(
            i, p, max_tokens, temperature=0.0 if i % 2 else 0.9,
            top_k=8 if i % 3 == 0 else 0, top_p=0.9 if i % 4 == 0 else 1.0,
            stop_token_ids=[7, 11] if i % 2 else []))
            for i, p in enumerate(prompts)]
        streams = {s.request_id: [] for s in seqs}
        for _ in range(400):
            for seq, out in core.step():
                streams[seq.request_id].extend(out.token_ids)
            if all(s.finish for s in seqs):
                break
    finally:
        core_mod.pack_lanes = pack
    assert all(s.finish for s in seqs)
    return packs, calls, streams


def _engine_for(kind: str, pipelined: bool):
    eng = dict(async_exec=pipelined, max_num_seqs=8, decode_buckets=(4, 8))
    if kind == "window":
        return tiny_laguna(), tiny_engine(block_size=4, num_kv_blocks=128, **eng), None
    if kind == "hybrid":
        return tiny_lfm2(), tiny_engine(**eng), None
    if kind == "dense-dp":
        return tiny_model(), tiny_engine(**eng), make_mesh(dp=2, tp=2)
    return tiny_model(), tiny_engine(**eng), None


@pytest.mark.parametrize("pipelined", [False, True], ids=["no-feed", "fed"])
@pytest.mark.parametrize("kind", ["dense", "window", "hybrid", "dense-dp"])
def test_a_served_megastep_is_handed_its_twelve_arrays_in_one(kind, pipelined):
    """Dense, window (a table of three parts) and hybrid models, with a
    step in flight to feed from and without, and over a dp x tp mesh: what
    the program unpacks is what the dispatch assembled, bit for bit; the
    tables ride beside it as they did; the streams are the same with and
    without the feed."""
    cfg, eng, mesh = _engine_for(kind, pipelined)
    core = EngineCore(cfg, eng, seed=5, mesh=mesh)
    assert core.pipelined == pipelined
    rs = np.random.RandomState(11)
    prompts = [[int(t) for t in rs.randint(1, 380, size=9 + 3 * i)] for i in range(5)]
    packs, calls, streams = _capture(core, prompts)
    assert len(packs) == len(calls) >= 3
    assert any(p[1] is not None for p in packs) == pipelined   # a feed index
    width = core.engine.max_blocks_per_seq
    if core.window_allocator is not None:
        width += 1 + core.engine.window_table_blocks(cfg.sliding_window)
    for arrays, (lanes, tables, feed) in zip(packs, calls):
        tokens, feed_idx, *rest = arrays
        assert lanes.shape == (len(tokens), LANE_COLS) and tables.shape == (len(tokens), width)
        assert feed.shape == (core._feed_width,)
        got = dict(zip(NAMES, _unpack(lanes, feed)))
        want = dict(zip(NAMES, [tokens, *rest]))
        if feed_idx is not None:
            flat = np.asarray(feed)
            want["tokens"] = np.where(feed_idx >= 0, flat[np.clip(feed_idx, 0, None)], tokens)
        for name in NAMES:
            np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]), err_msg=name)
    if mesh is not None:
        assert "dp" in str(calls[0][0].sharding.spec)   # the lanes split over dp
    other = EngineCore(cfg, _engine_for(kind, not pipelined)[1], seed=5, mesh=mesh)
    assert _capture(other, prompts)[2] == streams


def test_a_megastep_makes_two_transfers_and_compiles_once_fed_or_not():
    """Thirteen before (twelve arrays and the feed index); at most three
    now. A megastep that nothing feeds is handed zeros placed as a step's
    output is, and runs the program the fed one compiled."""
    core = EngineCore(tiny_model(), tiny_engine(decode_buckets=(5,), max_num_seqs=5), seed=0)
    per_megastep, inside = [], [False]
    for name in ("_put_batch", "_to_device", "_fed"):
        def counting(*args, _fn=getattr(core, name), **kw):
            if inside[0]:
                per_megastep[-1] += 1
            return _fn(*args, **kw)
        setattr(core, name, counting)
    dispatch = core._dispatch_megastep

    def megastep(*args, **kw):
        per_megastep.append(0)
        inside[0] = True
        try:
            return dispatch(*args, **kw)
        finally:
            inside[0] = False   # a wave's transfers are not a megastep's

    core._dispatch_megastep = megastep
    programs = core._decode._cache_size()
    prompts = [list(range(1 + i, 12 + i)) for i in range(5)]
    for pipelined in (True, False):
        core.pipelined = pipelined
        seqs = [core.add_request(_request(10 * pipelined + i, p, 17, temperature=0.5))
                for i, p in enumerate(prompts)]
        while any(s.finish is None for s in seqs):
            core.step()
    assert len(per_megastep) >= 4 and set(per_megastep) == {2}
    assert core._decode._cache_size() - programs == 1


# -- the streams are the parent's ---------------------------------------------------


def closed_run(core: EngineCore, clients: int = 128, requests: int = 176) -> dict[str, list[int]]:
    """``clients`` closed-loop clients over ``requests`` requests: one that
    ends is followed by the next, so waves refill the lanes between the
    megasteps. Greedy and sampled lanes, masks, stop ids, budgets that are
    and are not 8m + 1."""
    rs = np.random.RandomState(2024)

    def request(i: int) -> PreprocessedRequest:
        prompt = [int(t) for t in rs.randint(1, 380, size=int(rs.randint(6, 30)))]
        return _request(
            i, prompt, int(rs.choice([9, 17, 25, 12, 30])),
            temperature=float(rs.choice([0.0, 0.7, 1.0])),
            top_k=int(rs.choice([0, 0, 20])), top_p=float(rs.choice([1.0, 1.0, 0.9])),
            stop_token_ids=[int(t) for t in rs.randint(1, 380, size=int(rs.randint(0, 3)))])

    todo = [request(i) for i in range(requests)]
    live = [core.add_request(todo.pop(0)) for _ in range(clients)]
    streams = {s.request_id: [] for s in live}
    for _ in range(5000):
        for seq, out in core.step():
            streams[seq.request_id].extend(out.token_ids)
            if out.finish_reason and todo:
                nxt = core.add_request(todo.pop(0))
                streams[nxt.request_id] = []
                live.append(nxt)
        if not todo and all(s.finish for s in live):
            break
    assert len(streams) == requests and all(s.finish for s in live)
    return streams


def closed_run_core(pipelined: bool = True) -> EngineCore:
    return EngineCore(
        tiny_model(),
        tiny_engine(max_num_seqs=128, decode_buckets=(32, 128), max_model_len=64,
                    num_kv_blocks=128 * 8 + 16, async_exec=pipelined),
        seed=0)


def digest(streams: dict[str, list[int]]) -> str:
    return hashlib.sha256(json.dumps(streams, sort_keys=True).encode()).hexdigest()


def test_a_128_lane_closed_run_streams_what_the_parent_recorded():
    """``fixtures/host_leg/streams_128.json``: the same run on the commit
    before the packed array (3bdec35, PR 39), recorded once on the CPU
    (:func:`record`). The loop
    that feeds nothing streams the same."""
    recorded = json.loads(RECORDED.read_text())
    streams = closed_run(closed_run_core())
    assert sum(map(len, streams.values())) == recorded["tokens"] > 2000
    assert closed_run(closed_run_core(pipelined=False)) == streams
    differ = [r for r in streams if streams[r] != recorded["streams"][r]]
    assert not differ and digest(streams) == recorded["sha256"], differ[:5]


# -- one hop of KV events a step ----------------------------------------------------


class _Loop:
    def __init__(self):
        self.hops = []

    def call_soon_threadsafe(self, fn, *args):
        self.hops.append((fn, args))

    def run(self):
        hops, self.hops = self.hops, []
        for fn, args in hops:
            fn(*args)
        return len(hops)


class _Publisher:
    def __init__(self):
        self.got = []

    def stored_nowait(self, hashes, parent, tier="device"):
        self.got.append(("stored", tuple(hashes), parent))

    def removed_nowait(self, hashes, tier="device"):
        self.got.append(("removed", tuple(hashes)))


def test_a_steps_kv_events_cross_in_one_hop_in_the_order_raised():
    loop, pub = _Loop(), _Publisher()
    events = StepKvEvents(loop, pub)
    # Few blocks: the second batch of prompts evicts the first's, so a
    # step raises ``removed`` between its ``stored``.
    core = EngineCore(
        tiny_model(), tiny_engine(num_kv_blocks=24, max_num_seqs=4, decode_buckets=(4,)),
        seed=0, on_stored=events.stored, on_removed=events.removed)
    core.step_scope = events.step
    raised = []
    alloc = core.allocator
    stored, removed = alloc.on_stored, alloc.on_removed
    alloc.on_stored = lambda h, p: (raised.append(("stored", tuple(h), p)), stored(h, p))
    alloc.on_removed = lambda h: (raised.append(("removed", tuple(h))), removed(h))
    most = 0
    for batch in range(3):
        seqs = [core.add_request(_request(
            10 * batch + i, [1 + (13 * (10 * batch + i) + j) % 300 for j in range(30)], 12))
            for i in range(4)]
        while any(s.finish is None for s in seqs):
            seen = len(raised)
            core.step()
            assert len(loop.hops) == (1 if len(raised) > seen else 0)
            most = max(most, len(raised) - seen)
            loop.run()
            assert pub.got == raised
    assert most >= 4 and {e[0] for e in raised} == {"stored", "removed"}
    # Outside a step an event crosses at once (a cache clear holds the
    # step lock: never beside a step) ...
    core.clear_kv_cache()
    assert len(loop.hops) >= 1 and loop.run() and pub.got == raised
    # ... and so does another thread's while a step runs (the offload
    # thread's evictions): only the engine thread's are kept.
    with events.step():
        events.stored([1], None)
        t = threading.Thread(target=events.removed, args=([2],))
        t.start()
        t.join()
        assert [fn.__name__ for fn, _ in loop.hops] == ["removed_nowait"]
    assert len(loop.hops) == 2 and loop.run() == 2
    assert pub.got[-2:] == [("removed", (2,)), ("stored", (1,), None)]


def record() -> None:
    """Print a run's record, for ``fixtures/host_leg/``:
    ``python -c "import tests.conftest, tests.test_host_leg as t; t.record()"``
    (the conftest first: the flags the tests compile under)."""
    run = closed_run(closed_run_core())
    print(json.dumps({"tokens": sum(map(len, run.values())), "sha256": digest(run),
                      "streams": run}, sort_keys=True))
