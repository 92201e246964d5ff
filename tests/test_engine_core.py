"""EngineCore scheduler: admission, prefix reuse, stops, preemption, async."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import EngineCore, TpuEngine, tiny_engine, tiny_model
from dynamo_tpu.engine.block_allocator import DeviceBlockAllocator
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context

CFG = tiny_model()


def make_core(**eng_overrides) -> EngineCore:
    return EngineCore(CFG, tiny_engine(**eng_overrides), seed=0)


def run_to_completion(core, seqs, max_steps=500):
    done: dict[str, list[int]] = {s.request_id: [] for s in seqs}
    finishes: dict[str, str] = {}
    for _ in range(max_steps):
        for seq, out in core.step():
            done[seq.request_id].extend(out.token_ids)
            if out.finish_reason:
                finishes[seq.request_id] = out.finish_reason
        if len(finishes) == len(seqs):
            break
    return done, finishes


def _req(prompt, rid, max_tokens=8, temperature=0.0, **stop_kw):
    return PreprocessedRequest(
        model="tiny",
        token_ids=prompt,
        request_id=rid,
        sampling=SamplingOptions(temperature=temperature),
        stop=StopConditions(max_tokens=max_tokens, **stop_kw),
    )


def test_single_request_generates_to_length():
    core = make_core()
    seq = core.add_request(_req(list(range(1, 20)), "a", max_tokens=6))
    done, finishes = run_to_completion(core, [seq])
    assert len(done["a"]) == 6
    assert finishes["a"] == "length"
    # All blocks released after finish.
    assert core.allocator.used_blocks == len(core.allocator._inactive)


def test_64bit_seed_does_not_crash_step():
    # OpenAI clients send 64-bit seeds; int32 device arrays must not
    # overflow (the old failure poisoned the engine loop permanently).
    core = make_core()
    pre = PreprocessedRequest(
        model="tiny",
        token_ids=list(range(1, 20)),
        request_id="big-seed",
        sampling=SamplingOptions(temperature=0.8, seed=2**40 + 17),
        stop=StopConditions(max_tokens=4),
    )
    seq = core.add_request(pre)
    done, finishes = run_to_completion(core, [seq])
    assert len(done["big-seed"]) == 4
    assert finishes["big-seed"] == "length"


def test_greedy_determinism_and_prefix_cache_hit():
    core = make_core()
    prompt = list(range(3, 60))  # several full blocks
    s1 = core.add_request(_req(prompt, "r1", max_tokens=5))
    d1, _ = run_to_completion(core, [s1])
    assert s1.num_cached_tokens == 0

    s2 = core.add_request(_req(prompt, "r2", max_tokens=5))
    d2, _ = run_to_completion(core, [s2])
    # Same prompt, greedy: same tokens; prefix cache served full blocks.
    assert d1["r1"] == d2["r2"]
    assert s2.num_cached_tokens >= 48  # 56 prompt tokens -> 6 blocks cached (cap 55//8)


def test_concurrent_requests_interleave():
    core = make_core()
    seqs = [
        core.add_request(_req([i + 1, i + 2, i + 3, i + 4], f"c{i}", max_tokens=4))
        for i in range(5)
    ]
    done, finishes = run_to_completion(core, seqs)
    for i in range(5):
        assert len(done[f"c{i}"]) == 4
        assert finishes[f"c{i}"] == "length"


def test_stop_token_id():
    core = make_core()
    # Greedy tiny model is deterministic: find its 2nd token, then make it a stop.
    probe = core.add_request(_req([5, 6, 7], "probe", max_tokens=4))
    d, _ = run_to_completion(core, [probe])
    target = d["probe"][1]
    first_hit = d["probe"].index(target)
    core2 = make_core()
    seq = core2.add_request(
        _req([5, 6, 7], "s", max_tokens=16, stop_token_ids=[target])
    )
    d2, fin = run_to_completion(core2, [seq])
    # Stream stops at the first occurrence of the stop token (inclusive).
    assert d2["s"] == d["probe"][: first_hit + 1]
    assert fin["s"] == "stop"


def test_eos_token():
    core = make_core()
    probe = core.add_request(_req([9, 9, 9], "p", max_tokens=3))
    d, _ = run_to_completion(core, [probe])
    eos = d["p"][2]
    core2 = EngineCore(CFG, tiny_engine(), seed=0, eos_token_ids=(eos,))
    s = core2.add_request(_req([9, 9, 9], "e", max_tokens=16))
    d2, fin = run_to_completion(core2, [s])
    assert fin["e"] == "eos"
    assert len(d2["e"]) == 3


def test_long_prompt_chunked_prefill():
    core = make_core()
    prompt = list(np.random.RandomState(0).randint(1, 200, size=200))
    # largest tiny bucket is 128 < 200 -> must chunk
    seq = core.add_request(_req(prompt, "long", max_tokens=3))
    done, fin = run_to_completion(core, [seq])
    assert len(done["long"]) == 3
    assert fin["long"] == "length"


def test_context_overflow_rejected():
    core = make_core()
    with pytest.raises(ValueError):
        core.add_request(_req(list(range(1, 300)), "big", max_tokens=3))


def test_preemption_under_block_pressure():
    # Tiny pool: force decode growth to preempt a neighbor and still finish.
    core = make_core(num_kv_blocks=12, max_model_len=64)
    prompts = [list(range(1, 17)), list(range(20, 36)), list(range(40, 56))]
    seqs = [core.add_request(_req(p, f"p{i}", max_tokens=24)) for i, p in enumerate(prompts)]
    done, fin = run_to_completion(core, seqs, max_steps=2000)
    for i in range(3):
        assert len(done[f"p{i}"]) == 24, f"p{i}: {len(done[f'p{i}'])}"
        assert fin[f"p{i}"] == "length"


def test_preempted_greedy_stream_is_consistent():
    """A preempted+replayed greedy stream must equal the unpressured one."""
    base = make_core()
    s = base.add_request(_req(list(range(1, 17)), "ref", max_tokens=24))
    ref, _ = run_to_completion(base, [s])

    core = make_core(num_kv_blocks=12, max_model_len=64)
    seqs = [
        core.add_request(_req(list(range(1, 17)), "a", max_tokens=24)),
        core.add_request(_req(list(range(20, 36)), "b", max_tokens=24)),
        core.add_request(_req(list(range(40, 56)), "c", max_tokens=24)),
    ]
    done, _ = run_to_completion(core, seqs, max_steps=2000)
    assert done["a"] == ref["ref"]


def test_kv_events_emitted():
    stored, removed = [], []
    core = EngineCore(
        CFG,
        tiny_engine(),
        seed=0,
        on_stored=lambda hs, parent: stored.extend(hs),
        on_removed=lambda hs: removed.extend(hs),
    )
    seq = core.add_request(_req(list(range(1, 30)), "ev", max_tokens=12))
    run_to_completion(core, [seq])
    # 29 prompt tokens = 3 full blocks; decode crosses more boundaries.
    assert len(stored) >= 3


async def test_async_engine_streams():
    core = make_core()
    eng = TpuEngine(core)
    ctx = Context("async1")
    got = []
    async for out in eng.generate(
        _req([1, 2, 3, 4, 5], "async1", max_tokens=5).to_wire(), ctx
    ):
        got.extend(out.get("token_ids", []))
    assert len(got) == 5


async def test_async_engine_concurrent():
    core = make_core()
    eng = TpuEngine(core)

    async def one(i):
        toks = []
        async for out in eng.generate(
            _req([i, i + 1, i + 2], f"cc{i}", max_tokens=4).to_wire(), Context(f"cc{i}")
        ):
            toks.extend(out.get("token_ids", []))
        return toks

    results = await asyncio.gather(*[one(i + 1) for i in range(6)])
    for toks in results:
        assert len(toks) == 4


def test_allocator_dedup_and_eviction():
    events = {"stored": 0, "removed": 0}
    alloc = DeviceBlockAllocator(
        4, 8,
        on_stored=lambda h, p: events.__setitem__("stored", events["stored"] + len(h)),
        on_removed=lambda h: events.__setitem__("removed", events["removed"] + len(h)),
    )
    b1 = alloc.alloc()
    got = alloc.commit(b1, 111, None)
    assert got == b1 and events["stored"] == 1
    # Duplicate content: second physical copy freed, canonical returned.
    b2 = alloc.alloc()
    got2 = alloc.commit(b2, 111, None)
    assert got2 == b1 and events["stored"] == 1
    alloc.release([111]); alloc.release([111])
    # Now inactive; filling the pool evicts it.
    ids = alloc.alloc_many(4)
    assert events["removed"] == 1
    assert len(set(ids)) == 4


def test_logprobs_greedy_consistency():
    """Greedy decode with logprobs: the chosen token must be the top-1
    alternative with a matching logprob, on both the prefill-sampled first
    token and chained decode tokens (reference perf/logprobs.rs path)."""
    from dynamo_tpu.llm.protocols.common import OutputOptions

    core = make_core()
    pre = _req(list(range(1, 20)), "lp", max_tokens=6)
    pre.output = OutputOptions(logprobs=3)
    seq = core.add_request(pre)

    entries: list[dict] = []
    for _ in range(200):
        for s, out in core.step():
            assert out.logprobs is not None and len(out.logprobs) == len(out.token_ids)
            entries.extend(out.logprobs)
            if out.finish_reason:
                break
        if seq.finish:
            break
    assert len(entries) == 6
    for e in entries:
        assert len(e["top"]) == 3
        top = e["top"]
        # Greedy: chosen == argmax == first alternative; logprobs agree.
        assert e["token_id"] == top[0][0]
        assert abs(e["logprob"] - top[0][1]) < 1e-5
        assert e["logprob"] <= 0.0 + 1e-6
        # Alternatives sorted descending.
        lps = [v for _, v in top]
        assert lps == sorted(lps, reverse=True)


def test_logprobs_mixed_batch_only_requested_lanes():
    """A batch mixing logprob and plain requests: only the requesting
    sequence gets logprob records."""
    from dynamo_tpu.llm.protocols.common import OutputOptions

    core = make_core()
    p1 = _req([1, 2, 3, 4, 5], "with", max_tokens=4)
    p1.output = OutputOptions(logprobs=1)
    p2 = _req([6, 7, 8, 9, 10], "without", max_tokens=4)
    s1 = core.add_request(p1)
    s2 = core.add_request(p2)
    got = {"with": [], "without": []}
    done, _ = run_to_completion(core, [s1, s2])
    # re-run: collect logprobs per request
    core2 = make_core()
    s1 = core2.add_request(p1)
    s2 = core2.add_request(p2)
    for _ in range(200):
        for s, out in core2.step():
            if out.logprobs:
                got[s.request_id].extend(out.logprobs)
        if s1.finish and s2.finish:
            break
    assert len(got["with"]) == 4
    assert got["without"] == []


def test_chain_length_respects_generation_budgets():
    """Short-budget batches must not run full decode chains (tool-call
    workloads: max_tokens=2 with megastep_k=32 used to burn 30 wasted
    fused steps per chain)."""
    # Synchronous loop: the budgets are read between two counted steps.
    core = make_core(megastep_k=32, max_model_len=256, async_exec=False)
    s1 = core.add_request(_req([1, 2, 3], "a", max_tokens=2))
    s2 = core.add_request(_req([4, 5, 6], "b", max_tokens=3))
    core.step()  # prefill: each seq now has 1 generated token
    n = core._chain_length([s1, s2])
    # Largest remaining budget is 2 -> chain of 2, not 32.
    assert n == 2
    # The manual prefill step above already emitted token 1 of each.
    done, fin = run_to_completion(core, [s1, s2])
    assert len(done["a"]) == 1 and len(done["b"]) == 2
    assert fin["a"] == fin["b"] == "length"


def test_chain_length_unbounded_budget_keeps_full_chain():
    core = make_core(megastep_k=8, max_model_len=256)
    s = core.add_request(_req([1, 2, 3], "a", max_tokens=200, ignore_eos=True))
    core.step()
    assert core._chain_length([s]) == 8


def test_expired_held_blocks_are_released():
    """A remote-decode prefill whose decode side never pulls (timeout,
    crash) must not pin its blocks forever: the hold expires after
    held_block_ttl_s and the next step releases it (advisor r4)."""
    import time

    core = EngineCore(CFG, tiny_engine(held_block_ttl_s=0.15), seed=0)
    pre = _req(list(range(1, 20)), "held", max_tokens=1)
    pre.kv_transfer_params = {"do_remote_decode": True}
    seq = core.add_request(pre)
    run_to_completion(core, [seq])
    assert "held" in core._held
    held_blocks = core.allocator.used_blocks
    assert held_blocks > 0

    # Within the TTL the hold survives steps, and a transfer touch
    # refreshes the deadline.
    core.step()
    assert "held" in core._held
    core.export_descriptors("held")

    time.sleep(0.2)
    core.step()  # sweep runs at the top of the step
    assert "held" not in core._held
    assert core._held_deadline == {}
    # Blocks are back in the reusable pool (inactive cached content).
    assert core.allocator.used_blocks == len(core.allocator._inactive)


def _held_prefill(core, prompt, rid):
    pre = _req(prompt, rid, max_tokens=1, ignore_eos=True)
    pre.kv_transfer_params = {"do_remote_decode": True}
    seq = core.add_request(pre)
    done, _ = run_to_completion(core, [seq])
    return done[rid]


def test_import_blocks_direct_matches_aggregated():
    """Device-direct cache->cache transfer (the within-slice ICI analogue
    of NIXL GPU->GPU): decode continuation over directly-imported blocks
    must match the aggregated output exactly."""
    prompt = list(range(1, 41))  # 5 complete 8-token blocks
    agg = make_core()
    want, _ = run_to_completion(agg, [agg.add_request(_req(prompt, "agg", max_tokens=6))])

    p_core = make_core()
    d_core = EngineCore(CFG, tiny_engine(), seed=0, params=p_core.params)
    tok1 = _held_prefill(p_core, prompt, "pf")
    n = d_core.import_blocks_direct(p_core, "pf").imported
    p_core.release_held("pf")
    assert n == 5  # all five complete prompt blocks committed and moved
    seq = d_core.add_request(_req(prompt + tok1, "dec", max_tokens=5))
    got, _ = run_to_completion(d_core, [seq])
    assert tok1 + got["dec"] == want["agg"]
    # The continuation rode the imported prefix (cached tokens > 0).
    assert seq.num_cached_tokens > 0
    assert d_core.transfer_stats["imported_blocks"] == n
    assert d_core.transfer_stats["dropped_blocks"] == 0


def test_import_blocks_direct_skips_cached_and_accounts():
    """Re-importing the same prefix skips already-cached hashes and the
    accounting distinguishes imported vs skipped vs dropped."""
    prompt = list(range(1, 41))
    p_core = make_core()
    d_core = EngineCore(CFG, tiny_engine(), seed=0, params=p_core.params)
    _held_prefill(p_core, prompt, "a")
    n1 = d_core.import_blocks_direct(p_core, "a").imported
    p_core.release_held("a")
    _held_prefill(p_core, prompt, "b")
    n2 = d_core.import_blocks_direct(p_core, "b").imported
    p_core.release_held("b")
    assert n1 > 0 and n2 == 0
    st = d_core.transfer_stats
    assert st["transfers"] == 2
    assert st["imported_blocks"] == n1
    assert st["skipped_cached_blocks"] == n1
    assert st["dropped_blocks"] == 0 and st["partial_transfers"] == 0


def test_import_blocks_partial_drop_is_accounted():
    """Allocator exhaustion mid-import drops the tail blocks and the
    stats record it ('transfer worked' vs 'transfer
    half-dropped' must be distinguishable)."""
    prompt = list(range(1, 41))
    p_core = make_core()
    descs = None
    _held_prefill(p_core, prompt, "a")
    descs = p_core.export_descriptors("a")
    pages = p_core.read_held_pages("a", 0, len(descs))
    blocks = [dict(d, kv=kv) for d, kv in zip(descs, pages)]
    p_core.release_held("a")

    # Destination with too few blocks: every block pinned by a running
    # sequence, so alloc_for_import starves partway through.
    d_core = EngineCore(CFG, tiny_engine(num_kv_blocks=6), seed=0, params=p_core.params)
    pin = d_core.add_request(_req(list(range(50, 70)), "pin", max_tokens=64, ignore_eos=True))
    d_core.step()  # prefill: pins 3 blocks, leaves 3 free
    res = d_core.import_blocks(blocks)
    st = d_core.transfer_stats
    assert res.imported < len(blocks)
    assert res.dropped == st["dropped_blocks"] == len(blocks) - res.imported
    assert st["partial_transfers"] == 1
    del pin
