"""Async pipelined execution loop (ISSUE 5): the one-step-ahead engine.

The tentpole contract: on the pipelined loop (the engine's own choice
since ISSUE 25; ``async_exec`` pins one) the engine plans and
enqueues step N+1 while step N executes on device (device-resident token
feedback, optimistic cursor overlays, double-buffered host fetch) and the
token stream stays BIT-IDENTICAL to the synchronous loop — greedy AND
seeded temperature, waves + chunked mixed steps + spec-decode verify rows,
including stops that land one step late and roll back via the
``num_computed_tokens`` cursor.
"""

import math

import numpy as np
import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

pytestmark = [pytest.mark.unit]

CFG = tiny_model()


def _req(prompt, rid, max_tokens=8, temperature=0.0, seed=None, top_k=0,
         top_p=1.0, logprobs=None, **stop_kw):
    pre = PreprocessedRequest(
        model="tiny",
        token_ids=prompt,
        request_id=rid,
        sampling=SamplingOptions(
            temperature=temperature, seed=seed, top_k=top_k, top_p=top_p
        ),
        stop=StopConditions(max_tokens=max_tokens, **stop_kw),
    )
    if logprobs is not None:
        pre.output.logprobs = logprobs
    return pre


def drive(core, seqs, max_steps=4000):
    """Run to completion, draining the pipeline tail (an in-flight step
    holds a stream's final tokens until the next step() call)."""
    done = {s.request_id: [] for s in seqs}
    fins: dict[str, str] = {}
    lps = {s.request_id: [] for s in seqs}
    for _ in range(max_steps):
        for s, out in core.step():
            done[s.request_id].extend(out.token_ids)
            if out.logprobs:
                lps[s.request_id].extend(out.logprobs)
            if out.finish_reason:
                fins[s.request_id] = out.finish_reason
        if len(fins) == len(seqs) and not core.has_work():
            break
    return done, fins, lps


def _every_landing_precedes_the_next_dispatch(log) -> bool:
    """The synchronous loop's signature in the ``_exec_log`` hook."""
    disp_pos = {n: i for i, (k, n) in enumerate(log) if k == "dispatch"}
    land_pos = {n: i for i, (k, n) in enumerate(log) if k == "land"}
    return bool(land_pos) and all(
        land_pos[n] < disp_pos[n + 1] for n in land_pos if n + 1 in disp_pos
    )


def _mixed_workload(core):
    rng = np.random.RandomState(0)
    long_prompt = list(rng.randint(1, 200, size=200))
    seqs = [
        core.add_request(_req(list(range(i + 1, i + 9)), f"s{i}", max_tokens=12))
        for i in range(4)
    ]
    seqs.append(core.add_request(_req(long_prompt, "long", max_tokens=6)))
    return seqs


# -- config validation --------------------------------------------------------


def test_async_constructs_on_pp_mesh():
    # The async x pp rejection is LIFTED (ISSUE 20): fused pp megasteps
    # compose with async execution. Stream parity for that combination
    # is pinned by tests/test_pp_megastep.py::test_parity_pp_async_composition;
    # here we pin that construction succeeds and reports its stages.
    from dynamo_tpu.parallel.pipeline import make_pp_mesh

    core = EngineCore(
        CFG, tiny_engine(async_exec=True), seed=0, pp_mesh=make_pp_mesh(2)
    )
    assert core.scheduler_stats()["pp_stages"] == 2


# -- the loop the engine chooses ----------------------------------------------


@pytest.mark.parametrize("overrides,pipelined", [
    ({}, True),
    ({"spec_decode": "ngram", "spec_device_draft": True, "megastep_k": 8}, True),
    # Drafts from host history would be one step stale under the
    # pipelined loop (no verify row forms): such engines stay synchronous.
    ({"spec_decode": "ngram"}, False),
    # The explicit override pins a loop whatever the engine would choose.
    ({"async_exec": False}, False),
    ({"spec_decode": "ngram", "async_exec": True}, True),
])
def test_loop_is_chosen_from_what_the_engine_was_built_with(overrides, pipelined):
    core = EngineCore(CFG, tiny_engine(**overrides), seed=0)
    assert core.pipelined is pipelined
    assert core.scheduler_stats()["async_exec"] == int(pipelined)


def test_sp_engine_builds_and_keeps_the_synchronous_loop():
    from dynamo_tpu.ops.ring_attention import sequence_parallel_mesh

    core = EngineCore(
        CFG, tiny_engine(ring_prefill_threshold=64), seed=0,
        sp_mesh=sequence_parallel_mesh(8),
    )
    assert core.pipelined is False
    core._exec_log = []
    seq = core.add_request(_req(list(range(1, 101)), "r", max_tokens=6, ignore_eos=True))
    done, fins, _ = drive(core, [seq])
    assert len(done["r"]) == 6 and fins["r"] == "length"
    assert core._ring_prefills == 1
    assert _every_landing_precedes_the_next_dispatch(core._exec_log)
    assert core.exec_stats["pipelined_dispatches"] == 0
    # Asking an sp engine for the pipelined loop outright is still refused.
    with pytest.raises(ValueError, match="sp mesh"):
        EngineCore(
            CFG, tiny_engine(async_exec=True), seed=0,
            sp_mesh=sequence_parallel_mesh(8),
        )


def test_host_drafted_engine_still_forms_verify_rows():
    core = EngineCore(
        CFG, tiny_engine(spec_decode="ngram", spec_k=4, megastep_k=1), seed=0
    )
    seq = core.add_request(
        _req([3, 4, 5] * 6, "v", max_tokens=24, ignore_eos=True)
    )
    drive(core, [seq])
    assert core.pipelined is False
    assert core.spec_stats.verify_rows > 0


# -- bit-identical parity -----------------------------------------------------


@pytest.mark.parametrize("scheduling", ["waves", "chunked"])
def test_greedy_parity_async_on_vs_off(scheduling):
    """Same seeds/prompts, same tokens, same finish reasons — async
    changes WHEN work happens (one step late), never what is emitted."""

    def run(async_exec):
        core = EngineCore(
            CFG,
            tiny_engine(
                async_exec=async_exec, scheduling=scheduling, prefill_chunk=32
            ),
            seed=0,
        )
        return drive(core, _mixed_workload(core))[:2]

    assert run(False) == run(True)


@pytest.mark.parametrize("scheduling", ["waves", "chunked"])
def test_seeded_temperature_parity_async_on_vs_off(scheduling):
    """Seeded sampling lanes (plain temperature, top-k, top-p mixed in
    one batch) replay the same (seed, counter) keys through the overlay,
    so the sampled ids match bit for bit; logprob payloads too."""

    def run(async_exec):
        core = EngineCore(
            CFG,
            tiny_engine(
                async_exec=async_exec, scheduling=scheduling, prefill_chunk=32
            ),
            seed=0,
        )
        seqs = [
            core.add_request(_req(
                [3, 5, 7, 9], "t", max_tokens=10, temperature=0.8, seed=11,
                ignore_eos=True,
            )),
            core.add_request(_req(
                [4, 6, 8], "k", max_tokens=10, temperature=0.7, seed=12,
                top_k=8, ignore_eos=True,
            )),
            core.add_request(_req(
                [2, 4, 6, 8, 10], "p", max_tokens=10, temperature=0.9,
                seed=13, top_p=0.8, logprobs=3, ignore_eos=True,
            )),
        ]
        return drive(core, seqs)

    d0, f0, l0 = run(False)
    d1, f1, l1 = run(True)
    assert d0 == d1
    assert f0 == f1
    assert l0 == l1


@pytest.mark.parametrize("scheduling", ["waves", "chunked"])
def test_spec_decode_parity_async_on_vs_off(scheduling):
    """Speculating lanes: drafts propose from (possibly lagged) host
    history and the verify row consumes the device-resident pending
    token; verification replays the target's own counter-keyed choices,
    so the stream is identical regardless of WHAT was drafted."""

    def run(async_exec):
        core = EngineCore(
            CFG,
            tiny_engine(
                async_exec=async_exec, scheduling=scheduling,
                prefill_chunk=32, spec_decode="ngram", spec_k=4,
            ),
            seed=0,
        )
        repeat = [3, 4, 5, 3, 4, 5, 3, 4]  # n-gram bait
        seqs = [
            core.add_request(_req(repeat, "sp", max_tokens=16, ignore_eos=True)),
            core.add_request(_req(
                [7] * 40, "q", max_tokens=10, temperature=0.7, seed=5,
                ignore_eos=True,
            )),
        ]
        return drive(core, seqs)[:2]

    assert run(False) == run(True)


def test_prefix_cache_replay_parity_async():
    """A prefix-cache-served replay must emit identical tokens under
    async execution (the admission path runs at plan time)."""
    prompt = list(range(3, 63))

    def run(async_exec):
        core = EngineCore(
            CFG,
            tiny_engine(
                async_exec=async_exec, scheduling="chunked", prefill_chunk=32
            ),
            seed=0,
        )
        s1 = core.add_request(_req(prompt, "warm", max_tokens=5))
        d1, _, _ = drive(core, [s1])
        s2 = core.add_request(_req(prompt, "hit", max_tokens=5))
        d2, _, _ = drive(core, [s2])
        assert s2.num_cached_tokens >= 48
        return d1["warm"], d2["hit"]

    assert run(False) == run(True)


# -- late-stop rollback -------------------------------------------------------


def test_late_stop_rolls_back_optimistic_step():
    """With 1-step chains, a stop token commits one step AFTER the next
    step was already dispatched optimistically: the zombie lane's
    in-flight tokens are discarded (its K/V writes sit past the cursor,
    never attended) and the stream matches the synchronous loop."""
    ref = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = ref.add_request(_req([9, 9, 9], "r", max_tokens=12, ignore_eos=True))
    d, _, _ = drive(ref, [s])
    stop_tok = d["r"][5]  # mid-stream stop: 5 tokens then the stop

    def run(async_exec):
        core = EngineCore(
            CFG, tiny_engine(async_exec=async_exec, megastep_k=1), seed=0
        )
        seq = core.add_request(_req(
            [9, 9, 9], "x", max_tokens=12, stop_token_ids=[stop_tok],
            ignore_eos=True,
        ))
        out = drive(core, [seq])
        return out, core

    (d0, f0, _), sync_core = run(False)
    (d1, f1, _), async_core = run(True)
    assert d0 == d1
    assert f0 == f1 == {"x": "stop"}
    # The rollback actually happened: the async engine dispatched at
    # least one optimistic step past the stop and discarded it.
    assert (
        async_core.exec_stats["dispatches"]
        > sync_core.exec_stats["dispatches"]
    )


def test_late_eos_rollback_async():
    """Same rollback through the EOS path (engine-level eos_token_ids)."""
    probe = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = probe.add_request(_req([1, 2, 3], "p", max_tokens=10, ignore_eos=True))
    d, _, _ = drive(probe, [s])
    eos = d["p"][4]
    if eos in d["p"][:4]:
        pytest.skip("greedy stream repeats before position 4; stop-token "
                    "rollback is covered by test_late_stop_rolls_back")

    def run(async_exec):
        core = EngineCore(
            CFG, tiny_engine(async_exec=async_exec, megastep_k=1),
            seed=0, eos_token_ids=(eos,),
        )
        seq = core.add_request(_req([1, 2, 3], "e", max_tokens=10))
        return drive(core, [seq])[:2]

    assert run(False) == run(True)


# -- the pipelining contract --------------------------------------------------


def test_steady_decode_dispatch_precedes_landing():
    """The acceptance invariant: in steady-state decode, dispatch N+1 is
    enqueued BEFORE step N's outputs land — the host never syncs on the
    device between consecutive dispatches, so the device queue is never
    empty when the host blocks (asserted via the dispatch/land event
    hook)."""
    # Built with defaults: the engine chooses the pipelined loop itself.
    core = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    assert core.pipelined and core.scheduler_stats()["async_exec"] == 1
    core._exec_log = []
    seqs = [
        core.add_request(_req([1, 2, 3, 4], "a", max_tokens=20, ignore_eos=True)),
        core.add_request(_req([5, 6, 7, 8], "b", max_tokens=20, ignore_eos=True)),
    ]
    drive(core, seqs)
    log = core._exec_log
    disp_pos = {n: i for i, (k, n) in enumerate(log) if k == "dispatch"}
    land_pos = {n: i for i, (k, n) in enumerate(log) if k == "land"}
    assert len(disp_pos) >= 20  # 1-step chains: a real steady state
    # Every landing of step n happens after dispatch n+1 (the final
    # step's drain, with nothing left to dispatch, is the one exception).
    max_d = max(disp_pos)
    violations = [
        n for n in land_pos
        if n < max_d and disp_pos.get(n + 1, 10 ** 9) > land_pos[n]
    ]
    assert violations == [], (violations, log[:12])
    # Every dispatch but the first found a step in flight.
    assert core.exec_stats["pipelined_dispatches"] == core.exec_stats["dispatches"] - 1
    assert core.exec_stats["drains"] == 0


def test_sync_loop_lands_before_next_dispatch():
    """The synchronous twin of the hook test: async off, every landing
    precedes the next dispatch (plan+commit per call)."""
    core = EngineCore(CFG, tiny_engine(async_exec=False, megastep_k=1), seed=0)
    core._exec_log = []
    seq = core.add_request(_req([1, 2, 3], "a", max_tokens=8, ignore_eos=True))
    drive(core, [seq])
    assert _every_landing_precedes_the_next_dispatch(core._exec_log)


def test_block_pressure_drains_pipeline_and_recovers():
    """Out-of-blocks mid-plan with a step in flight: the engine commits
    the in-flight step (a drain), re-plans settled, preempts normally,
    and the replayed stream still matches the synchronous loop."""

    def run(async_exec):
        core = EngineCore(
            CFG,
            tiny_engine(
                num_kv_blocks=12, max_model_len=64, async_exec=async_exec,
                scheduling="chunked", prefill_chunk=16, megastep_k=1,
            ),
            seed=0,
        )
        seqs = [
            core.add_request(_req(list(range(1, 17)), "a", max_tokens=24)),
            core.add_request(_req(list(range(20, 36)), "b", max_tokens=24)),
            core.add_request(_req(list(range(40, 80)), "c", max_tokens=8)),
        ]
        done, fins, _ = drive(core, seqs, max_steps=8000)
        assert core.allocator._partials == 0
        return done, fins, core

    d0, f0, _ = run(False)
    d1, f1, core1 = run(True)
    assert d0 == d1
    assert f0 == f1
    # The pressure path actually ran (deterministic at this config):
    # growth failed mid-plan with a step in flight (drain), and the
    # settled re-plan preempted a victim.
    assert core1.exec_stats["drains"] >= 1
    assert core1.sched_stats["preemptions"] >= 1


def test_cancel_mid_flight_discards_in_flight_tokens():
    core = EngineCore(CFG, tiny_engine(async_exec=True, megastep_k=1), seed=0)
    seq = core.add_request(_req([1, 2, 3], "c", max_tokens=50, ignore_eos=True))
    core.step()  # dispatch prefill
    core.step()  # dispatch decode 1, commit prefill
    core.cancel_request(seq)
    for _ in range(5):
        core.step()
    assert not core.has_work()
    assert seq not in core.running
    assert core.allocator._partials == 0


# -- observability ------------------------------------------------------------


def test_plan_commit_spans_and_the_device_account_recorded():
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    core = EngineCore(CFG, tiny_engine(async_exec=True, megastep_k=1), seed=0)
    seq = core.add_request(_req([1, 2, 3], "t", max_tokens=8, ignore_eos=True))
    drive(core, [seq])
    names = {s.name for s in collector.stats()}
    assert "engine_plan" in names
    assert "engine_commit" in names
    # What the engine's ``host_gap`` stat bounded is measured now: the step
    # clock keeps a record per dispatch and counts the device's seconds,
    # the seconds it had nothing queued and the lanes' waiting.
    assert "host_gap" not in names and "last_host_gap_ms" not in core.exec_stats
    acc = core.device_account()
    n = core.exec_stats["dispatches"]
    assert n == 8 and not core.clock._open          # every record closed
    assert acc["device_seconds"]["prefill"] > 0 and acc["device_seconds"]["decode"] > 0
    assert sum(acc["late_landings"].values()) <= n
    starved = {"lower": 0.0, "upper": 0.0}
    for (bound, phase, after), sec in acc["starved_seconds"].items():
        assert phase != "no_work" and sec >= 0
        starved[bound] += sec
    assert starved["lower"] <= starved["upper"]
    # One lane, carried by every decode step: its decode seconds are the
    # decode steps' device seconds, and nothing waited behind a wave.
    assert acc["lane_seconds"]["decode"] == pytest.approx(acc["device_seconds"]["decode"])
    assert acc["lane_seconds"]["behind_prefill"] == 0
    assert core.exec_stats["decode_tokens_committed"] == 7   # of 8: the first is the wave's
    st = core.scheduler_stats()
    assert st["async_exec"] == 1
    assert st["dispatches"] == core.exec_stats["dispatches"]


def test_kv_cache_stats_surface():
    core = EngineCore(CFG, tiny_engine(), seed=0)
    st = core.kv_cache_stats()
    # Counter/usage series start at zero; the static layout facts
    # (kv_dtype, bytes_per_block, capacity_blocks) are nonzero by design.
    static = {"kv_dtype", "kv_dtype_int8", "bytes_per_block", "capacity_blocks"}
    assert all(v == 0 for k, v in st.items() if k not in static)
    prompt = list(range(3, 63))
    s1 = core.add_request(_req(prompt, "w", max_tokens=3))
    drive(core, [s1])
    s2 = core.add_request(_req(prompt, "h", max_tokens=3))
    drive(core, [s2])
    st = core.kv_cache_stats()
    # Admission series: warm miss + replay hit.
    assert st["admitted_queries"] == 2
    assert st["admitted_hits"] == 1
    assert st["admitted_hit_rate"] == 0.5
    # Probe series stays untouched by admissions (match_prefix only) —
    # the two definitions must never double-count each other.
    assert st["prefix_queries"] == 0
    core.cached_prefix_tokens(prompt)
    st = core.kv_cache_stats()
    assert st["prefix_queries"] == 1
    assert st["prefix_hits"] == 1
    assert st["admitted_queries"] == 2  # probes don't touch admissions


# -- mocker virtual-clock overlap A/B ----------------------------------------


def _mock_decode_sim(async_exec, B=16, osl=64):
    """Decode-heavy workload on the mocker's virtual clock: per-iteration
    cost from iter_time_s (deterministic, no sleeping)."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    args = MockEngineArgs(
        num_kv_blocks=8192, block_size=32, max_num_seqs=B,
        max_num_batched_tokens=2048, enable_prefix_caching=False,
        async_exec=async_exec,
    )
    eng = MockTpuEngine(args)
    seqs = []
    for j in range(B):
        prompt = [1 + (j % 7)] * 128
        s = _Seq(
            request_id=f"s{j}", prompt=prompt, max_tokens=osl,
            out=asyncio.Queue(),
            seq=TokenBlockSequence(prompt, args.block_size),
            prompt_hashes=compute_seq_hashes(prompt, args.block_size),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        )
        seqs.append(s)
        eng._waiting.append(s)
    vt = 0.0
    first, prev = {}, {}
    gaps = []
    streams = {s.request_id: [] for s in seqs}
    while any(s in eng._running or s in eng._waiting for s in seqs):
        eng._admit()
        p, d = eng._step()
        vt += eng.iter_time_s(p, d)
        for s in seqs:
            while not s.out.empty():
                item = s.out.get_nowait()
                if not isinstance(item, dict):
                    continue
                toks = item.get("token_ids", [])
                if not toks:
                    continue
                streams[s.request_id].extend(toks)
                rid = s.request_id
                if rid in first:
                    gaps.append(vt - prev[rid])
                first.setdefault(rid, vt)
                prev[rid] = vt
    gaps.sort()
    return {
        "tpot_p50": gaps[len(gaps) // 2],
        "streams": streams,
    }


def test_mocker_async_ab_improves_tpot_when_overhead_dominates():
    """The acceptance A/B on the mocker's virtual clock: at B=16 decode
    the fixed per-dispatch host overhead (base_iter_us=500) dominates the
    device term (16 * 100us / ... ), and the one-step-ahead overlap model
    must cut decode TPOT p50 — with a BIT-IDENTICAL stream."""
    off = _mock_decode_sim(False)
    on = _mock_decode_sim(True)
    assert on["streams"] == off["streams"], "async changed token values"
    assert on["tpot_p50"] < off["tpot_p50"], (on["tpot_p50"], off["tpot_p50"])
    # max(host, device) vs host + device at these shapes: >= 20% better.
    assert on["tpot_p50"] < off["tpot_p50"] * 0.8


def test_mocker_host_gap_stat_shrinks_with_async():
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine

    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    for async_exec in (False, True):
        collector.clear()
        eng = MockTpuEngine(MockEngineArgs(async_exec=async_exec))
        t = eng.iter_time_s(0, 32)  # decode-heavy: device 3.2ms > host 0.5ms
        gaps = [s for s in collector.stats() if s.name == "host_gap"]
        assert len(gaps) == 1
        if async_exec:
            assert gaps[0].duration_s == 0.0  # fully hidden
            assert math.isclose(t, 32 * 100e-6, rel_tol=1e-6)
        else:
            assert gaps[0].duration_s > 0.0
            assert math.isclose(t, 500e-6 + 32 * 100e-6, rel_tol=1e-6)
