"""Device-side multi-step decode — the MEGASTEP (ISSUE 7) and its
UNIVERSAL extension (ISSUE 12).

The tentpole contract: with ``megastep_k = k`` the engine fuses k decode
iterations into ONE device dispatch — an on-device scan over the ragged
program with device-resident sampling, per-lane on-device stop flags
(EOS / stop ids / max-tokens; lanes that stop early run masked no-op
iterations), and the host draining outputs every k steps through the
double-buffered fetch — and the token stream stays BIT-IDENTICAL to
k=1: greedy AND seeded temperature (+ top-k/top-p + logprobs), waves AND
chunked scheduling, async execution on AND off. Stops only the host can
see (stop ids truncated off the device watch, stop strings, cancels)
roll back via the ``num_computed_tokens`` cursor; block headroom for all
k tokens per lane is reserved at plan time, so mid-megastep block
exhaustion is impossible by construction (pressure surfaces as
drain→preempt BEFORE the dispatch).

ISSUE 12 lifts the first cut's k=1 carve-outs: chunked mixed steps and
spec verify rows now ride the same scanned body — verify rows resolve
accept/reject ON DEVICE (rejected drafts roll back inside the dispatch
via the lane's position cursor) and prefill chunks that complete their
prompt continue as decode rows in the remaining inner iterations. The
only forced-k=1 path left is a stop watch wider than the device's
MEGASTEP_WATCH_W slots, surfaced on the megastep_forced_single gauge.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine.core import MEGASTEP_WATCH_W
from dynamo_tpu.engine.sampler import stop_flags
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


def _workload(core):
    """Greedy + seeded-temperature + top-k + top-p + logprobs lanes with
    staggered budgets, plus one long prompt (exercises prefill waves /
    chunks between megasteps)."""
    rng = np.random.RandomState(0)
    seqs = [
        core.add_request(_req(
            list(range(i + 1, i + 9)), f"g{i}", max_tokens=10 + i,
            ignore_eos=True,
        ))
        for i in range(3)
    ]
    seqs.append(core.add_request(_req(
        [3, 5, 7, 9], "t", max_tokens=13, temperature=0.8, seed=11,
        ignore_eos=True,
    )))
    seqs.append(core.add_request(_req(
        [4, 6, 8], "k", max_tokens=9, temperature=0.7, seed=12, top_k=8,
        ignore_eos=True,
    )))
    seqs.append(core.add_request(_req(
        [2, 4, 6, 8, 10], "p", max_tokens=11, temperature=0.9, seed=13,
        top_p=0.8, logprobs=3, ignore_eos=True,
    )))
    seqs.append(core.add_request(_req(
        list(rng.randint(1, 200, size=120)), "long", max_tokens=6,
        ignore_eos=True,
    )))
    return seqs


# -- config resolution --------------------------------------------------------


def test_megastep_resolution_and_validation():
    assert tiny_engine().megastep == 8
    assert tiny_engine(megastep_k=1).megastep == 1
    assert tiny_engine(megastep_k=16).megastep == 16
    with pytest.raises(ValueError, match="megastep_k"):
        EngineCore(CFG, tiny_engine(megastep_k=0), seed=0)


# -- bit-identical parity -----------------------------------------------------


@pytest.mark.parametrize("scheduling", ["waves", "chunked"])
@pytest.mark.parametrize(
    "k", [pytest.param(2, marks=pytest.mark.slow), 8]
)  # k=2 rides the slow tier; k=8 keeps both scheduling modes in tier-1
def test_parity_megastep_vs_single_step(scheduling, k):
    """The acceptance invariant: --megastep-k k vs 1, same tokens, same
    finish reasons, same logprob payloads — greedy and seeded lanes in
    one batch, under both schedulers."""

    def run(kk):
        core = EngineCore(
            CFG,
            tiny_engine(
                megastep_k=kk, scheduling=scheduling, prefill_chunk=32
            ),
            seed=0,
        )
        return drive(core, _workload(core))

    assert run(1) == run(k)


@pytest.mark.parametrize(
    "async_exec", [pytest.param(False, marks=pytest.mark.slow), True]
)  # async OFF re-runs the plain matrix above; tier-1 keeps the ON cell
def test_parity_megastep_async_composition(async_exec):
    """Megastep x async-exec compose: one k-iteration dispatch in flight
    while the next is planned against the optimistic overlay; stream
    identical to the synchronous single-step loop."""

    def run(kk, ae):
        core = EngineCore(
            CFG, tiny_engine(megastep_k=kk, async_exec=ae), seed=0
        )
        return drive(core, _workload(core))

    assert run(1, False) == run(8, async_exec)


def test_async_megastep_dispatch_precedes_landing():
    """The pipelining contract survives k > 1: in steady decode, the
    NEXT megastep is dispatched before the previous one's outputs land."""
    core = EngineCore(CFG, tiny_engine(megastep_k=8, async_exec=True), seed=0)
    core._exec_log = []
    seq = core.add_request(_req([1, 2, 3], "s", max_tokens=40, ignore_eos=True))
    drive(core, [seq])
    events = core._exec_log
    overlapped = any(
        ("dispatch", n + 1) in events
        and events.index(("dispatch", n + 1)) < events.index(("land", n))
        for kind, n in events
        if kind == "dispatch" and ("land", n) in events
    )
    assert overlapped, events
    assert core.exec_stats["megastep_dispatches"] >= 2


# -- on-device stop flags -----------------------------------------------------


def test_stop_flags_device_logic():
    """The pure stop-flag predicate: watch hits gate on the min-tokens
    floor, budgets fire exactly at the remaining-token edge, and the -1
    padding can never match a real token id."""
    watch = jnp.asarray([[5, -1], [7, 9], [-1, -1], [2, -1]], jnp.int32)
    budgets = jnp.asarray([10, 10, 3, 10], jnp.int32)
    min_left = jnp.asarray([0, 4, 0, 0], jnp.int32)
    sampled = jnp.asarray([5, 9, 0, 3], jnp.int32)
    # i=0 -> gen=1: lane0 watch-hits; lane1 watch-hits but sits under its
    # min-tokens floor (gen 1 < 4); lane2 budget 3 not yet; lane3 clean.
    f0 = np.asarray(stop_flags(sampled, watch, budgets, min_left, jnp.int32(0)))
    assert f0.tolist() == [True, False, False, False]
    # i=3 -> gen=4: lane1's floor passes; lane2 exhausted its budget at
    # gen=3 already (flag recomputed per-iteration — still True at 4).
    f3 = np.asarray(stop_flags(sampled, watch, budgets, min_left, jnp.int32(3)))
    assert f3.tolist() == [True, True, True, False]
    # -1 padding never fires even if a lane "samples" garbage id 0.
    pad_only = jnp.full((4, 2), -1, jnp.int32)
    f = np.asarray(stop_flags(
        jnp.zeros(4, jnp.int32), pad_only,
        jnp.full(4, 99, jnp.int32), jnp.zeros(4, jnp.int32), jnp.int32(0),
    ))
    assert not f.any()


def test_eos_inside_megastep():
    """A lane that samples EOS at an inner iteration of a k=8 megastep
    finishes with reason 'eos' and emits exactly the same stream as the
    single-step engine; its surviving batch neighbors are untouched.
    Seeded temperature (the tiny model's greedy stream is a fixed point,
    so a fresh mid-stream EOS only exists on a sampled lane — which also
    pins the on-device stop flag against the seeded replay path)."""
    probe = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = probe.add_request(_req(
        [1, 2, 3], "p", max_tokens=12, temperature=0.9, seed=42,
        ignore_eos=True,
    ))
    d, _, _ = drive(probe, [s])
    eos = d["p"][4]  # mid-stream token -> EOS lands INSIDE a k=8 megastep
    if eos in d["p"][:4]:
        pytest.skip("seeded stream repeats before position 4")

    def run(k):
        core = EngineCore(
            CFG, tiny_engine(megastep_k=k), seed=0, eos_token_ids=(eos,)
        )
        seqs = [
            core.add_request(_req(
                [1, 2, 3], "e", max_tokens=12, temperature=0.9, seed=42,
            )),
            core.add_request(_req([9, 9, 9], "n", max_tokens=12,
                                  ignore_eos=True)),
        ]
        return drive(core, seqs)[:2]

    d1, f1 = run(1)
    d8, f8 = run(8)
    assert d1 == d8
    assert f1 == f8
    assert f8["e"] == "eos"
    assert d8["e"] == d["p"][:5]  # stopped mid-megastep, not at a boundary


def test_host_only_stop_rolls_back_at_megastep_boundary():
    """A stop id truncated OFF the device watch (the lane carries more
    stop ids than MEGASTEP_WATCH_W) is invisible to the on-device flags:
    the megastep runs past it, and the host stop-scan rolls the cursor
    back — the late-stop/stop-string rollback story. Stream and finish
    reason still match k=1 exactly."""
    probe = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = probe.add_request(_req([9, 9, 9], "p", max_tokens=20, ignore_eos=True))
    d, _, _ = drive(probe, [s])
    stop_tok = d["p"][5]
    # Decoys (never sampled by this greedy stream) fill the device watch;
    # the REAL stop id is last and falls off the [B, W] array.
    decoys = [t for t in range(300, 300 + MEGASTEP_WATCH_W)]
    stop_ids = decoys + [stop_tok]

    def run(k, async_exec=False):
        core = EngineCore(
            CFG, tiny_engine(megastep_k=k, async_exec=async_exec), seed=0
        )
        seq = core.add_request(_req(
            [9, 9, 9], "x", max_tokens=20, stop_token_ids=stop_ids,
            ignore_eos=True,
        ))
        out = drive(core, [seq])[:2]
        assert core.allocator._partials == 0
        return out

    d1, f1 = run(1)
    d8, f8 = run(8)
    assert d1 == d8 == {"x": d["p"][:6]}
    assert f1 == f8 == {"x": "stop"}
    # And one megastep later under async: the stop lands a whole
    # in-flight megastep late and the zombie lane's k tokens discard.
    assert run(8, async_exec=True) == (d1, f1)


def test_watch_overflow_forces_single_step():
    """ISSUE 8 satellite: a request watching MORE stop ids than the
    device's MEGASTEP_WATCH_W slots must not silently truncate the
    watch — its megasteps run at k=1, where the host stop-scan (which
    checks the FULL list) sees every token before the next dispatch.
    9 stop ids inside a configured k=8 megastep: correct stream, correct
    finish, and ZERO fused dispatches."""
    probe = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = probe.add_request(_req([9, 9, 9], "p", max_tokens=20, ignore_eos=True))
    d, _, _ = drive(probe, [s])
    stop_tok = d["p"][5]
    # W decoys + the real stop id = W+1 watch entries: one over the slots.
    stop_ids = list(range(300, 300 + MEGASTEP_WATCH_W)) + [stop_tok]
    assert len(stop_ids) == MEGASTEP_WATCH_W + 1

    core = EngineCore(CFG, tiny_engine(megastep_k=8), seed=0)
    seq = core.add_request(_req(
        [9, 9, 9], "x", max_tokens=20, stop_token_ids=stop_ids,
        ignore_eos=True,
    ))
    done, fins, _ = drive(core, [seq])
    assert done == {"x": d["p"][:6]}
    assert fins == {"x": "stop"}
    # The overflow forced every decode dispatch to k=1 — no fused
    # megasteps ran, so the truncated device watch never decided anything.
    assert core.exec_stats["megastep_dispatches"] == 0
    assert core.exec_stats["single_step_dispatches"] > 0

    # Control: the same stream with a watch that FITS stays fused.
    core8 = EngineCore(CFG, tiny_engine(megastep_k=8), seed=0)
    seq8 = core8.add_request(_req(
        [9, 9, 9], "y", max_tokens=20,
        stop_token_ids=stop_ids[1:],  # exactly W ids, real stop included
        ignore_eos=True,
    ))
    done8, fins8, _ = drive(core8, [seq8])
    assert done8 == {"y": d["p"][:6]} and fins8 == {"y": "stop"}
    assert core8.exec_stats["megastep_dispatches"] >= 1


def test_cancel_mid_megastep_discards_in_flight_tokens():
    """Host-side aborts (client disconnect, detokenizer stop-string
    match) cancel between steps: the in-flight megastep's tokens for
    that lane are discarded at commit and its blocks release exactly
    once."""
    core = EngineCore(CFG, tiny_engine(megastep_k=8, async_exec=True), seed=0)
    seq = core.add_request(_req([1, 2, 3], "c", max_tokens=50, ignore_eos=True))
    core.step()  # dispatch prefill
    core.step()  # dispatch megastep 1, commit prefill
    core.cancel_request(seq)
    for _ in range(5):
        core.step()
    assert not core.has_work()
    assert seq not in core.running
    assert core.allocator._partials == 0


# -- block headroom (reserved at plan time) -----------------------------------


@pytest.mark.parametrize("async_exec", [False, True])
def test_block_headroom_under_pressure(async_exec):
    """k tokens of per-lane block headroom are grown BEFORE the dispatch
    is enqueued, so pressure surfaces as preemption (sync) or
    drain-then-preempt (async) at plan time — never as mid-megastep
    exhaustion — and the replayed stream still matches an unpressured
    single-step run."""

    def run(blocks, k, ae):
        core = EngineCore(
            CFG,
            tiny_engine(
                num_kv_blocks=blocks, max_model_len=64, megastep_k=k,
                async_exec=ae,
            ),
            seed=0,
        )
        seqs = [
            core.add_request(_req(list(range(1, 17)), "a", max_tokens=24,
                                  ignore_eos=True)),
            core.add_request(_req(list(range(20, 36)), "b", max_tokens=24,
                                  ignore_eos=True)),
        ]
        done, fins, _ = drive(core, seqs, max_steps=8000)
        assert core.allocator._partials == 0
        return done, fins, core

    ref = run(64, 1, False)[:2]  # plentiful blocks, single-step
    d, f, core = run(7, 8, async_exec)
    assert (d, f) == ref
    assert core.sched_stats["preemptions"] >= 1
    if async_exec:
        assert core.exec_stats["drains"] >= 1


# -- observability ------------------------------------------------------------


def test_megastep_span_and_dispatch_gauges():
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    core = EngineCore(CFG, tiny_engine(megastep_k=8), seed=0)
    seq = core.add_request(_req([1, 2, 3], "m", max_tokens=20, ignore_eos=True))
    drive(core, [seq])
    spans = [s for s in collector.stats() if s.name == "engine_megastep"]
    assert spans, "engine_megastep span missing"
    assert all(s.attrs["inner_steps"] > 1 for s in spans)
    assert sum(s.attrs["tokens"] for s in spans) <= 20
    st = core.scheduler_stats()
    assert st["megastep_k"] == 8
    assert st["megastep_dispatches"] == len(spans)
    assert st["single_step_dispatches"] >= 1  # the prefill wave
    assert st["committed_tokens"] == 20
    # The amortization gauge: fewer dispatches than tokens.
    assert 0 < st["dispatches_per_token"] < 1.0


def test_single_step_engine_reports_no_megasteps():
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    core = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    seq = core.add_request(_req([1, 2, 3], "s", max_tokens=8, ignore_eos=True))
    drive(core, [seq])
    assert not [s for s in collector.stats() if s.name == "engine_megastep"]
    st = core.scheduler_stats()
    assert st["megastep_dispatches"] == 0
    assert st["dispatches_per_token"] >= 1.0  # one dispatch per token + prefill


def test_spec_verify_rows_fuse_on_device():
    """ISSUE 12: speculating lanes RIDE the megastep — verify rows
    resolve accept/reject inside the scanned dispatch (rejected drafts
    roll back on device) and the stream still matches the unfused,
    unspeculated engine bit for bit."""

    def run(**kw):
        core = EngineCore(CFG, tiny_engine(**kw), seed=0)
        repeat = [3, 4, 5, 3, 4, 5, 3, 4]  # n-gram bait
        seq = core.add_request(_req(repeat, "sp", max_tokens=16,
                                    ignore_eos=True))
        out = drive(core, [seq])[:2]
        return out, core

    ref, _ = run(megastep_k=1)
    got, core = run(megastep_k=8, spec_decode="ngram", spec_k=4)
    assert got == ref
    assert core.exec_stats["fused_mixed_dispatches"] >= 1
    assert core.exec_stats["megastep_dispatches"] >= 1
    assert core.exec_stats["megastep_forced_single"] == 0
    assert core.spec_stats.verify_rows > 0


# -- universal megastep (ISSUE 12): fused mixed + spec-verify steps ----------


def _spec_workload(core):
    """Speculation-heavy mixed traffic: repetitive prompts (n-gram bait)
    across greedy, seeded-temperature, and top-p + logprobs lanes, one
    incompressible decode lane (drafts rarely), and one long prompt so
    chunked scheduling interleaves prefill chunks with fused verify
    rows."""
    rng = np.random.RandomState(7)
    return [
        core.add_request(_req([3, 4, 5] * 4, "sg", max_tokens=18,
                              ignore_eos=True)),
        core.add_request(_req([7, 8] * 6, "st", max_tokens=15,
                              temperature=0.8, seed=21, ignore_eos=True)),
        core.add_request(_req([2, 4, 6, 2, 4, 6, 2, 4], "sl", max_tokens=12,
                              temperature=0.9, seed=22, top_p=0.85,
                              logprobs=3, ignore_eos=True)),
        core.add_request(_req(list(range(1, 9)), "pd", max_tokens=14,
                              ignore_eos=True)),
        core.add_request(_req(list(rng.randint(1, 200, size=120)), "long",
                              max_tokens=6, ignore_eos=True)),
    ]


@pytest.mark.parametrize("scheduling", ["waves", "chunked"])
@pytest.mark.parametrize(
    "k", [pytest.param(2, marks=pytest.mark.slow), 8]
)  # k=2 rides the slow tier; k=8 keeps both scheduling modes in tier-1
def test_parity_fused_mixed_spec(scheduling, k):
    """The ISSUE 12 acceptance invariant: with spec decode ON and mixed
    traffic, --megastep-k k fuses verify rows (accept/reject resolved on
    device) and prefill chunks into scanned dispatches, and the stream —
    tokens, finish reasons, logprob payloads — is bit-identical to the
    single-step engine AND to the unspeculated single-step engine."""

    def run(kk, spec):
        core = EngineCore(
            CFG,
            tiny_engine(
                megastep_k=kk, scheduling=scheduling, prefill_chunk=32,
                **(dict(spec_decode="ngram", spec_k=4) if spec else {}),
            ),
            seed=0,
        )
        return drive(core, _spec_workload(core)), core

    base, _ = run(1, spec=False)
    ref, _ = run(1, spec=True)
    got, core = run(k, spec=True)
    assert base == ref == got
    assert core.exec_stats["fused_mixed_dispatches"] >= 1
    assert core.exec_stats["megastep_forced_single"] == 0
    assert core.spec_stats.verify_rows > 0


@pytest.mark.parametrize(
    "async_exec", [pytest.param(False, marks=pytest.mark.slow), True]
)  # async OFF re-runs the plain matrix above; tier-1 keeps the ON cell
def test_parity_fused_async_composition(async_exec):
    """Universal megastep x async-exec: fused steps carrying live drafts
    are a pipeline barrier (data-dependent advance), draft-less fused
    steps keep the one-step-ahead overlap — stream identical to the
    synchronous single-step loop either way."""

    def run(kk, ae, spec):
        core = EngineCore(
            CFG,
            tiny_engine(
                megastep_k=kk, scheduling="chunked", prefill_chunk=32,
                async_exec=ae,
                **(dict(spec_decode="ngram", spec_k=4) if spec else {}),
            ),
            seed=0,
        )
        return drive(core, _spec_workload(core))

    assert run(1, False, spec=False) == run(8, async_exec, spec=True)


def test_eos_inside_fused_verify_continuation():
    """A seeded lane that samples EOS inside the scanned continuation of
    a FUSED verify dispatch finishes identically to the single-step
    engine — the on-device stop flags see it (masked no-ops follow), the
    host stop-scan confirms it, and the spec machinery never resurrects
    the lane."""
    probe = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = probe.add_request(_req(
        [5, 6] * 4, "p", max_tokens=12, temperature=0.9, seed=42,
        ignore_eos=True,
    ))
    d, _, _ = drive(probe, [s])
    eos = d["p"][4]
    if eos in d["p"][:4]:
        pytest.skip("seeded stream repeats before position 4")

    def run(k):
        core = EngineCore(
            CFG,
            tiny_engine(megastep_k=k, spec_decode="ngram", spec_k=4),
            seed=0, eos_token_ids=(eos,),
        )
        seqs = [
            core.add_request(_req(
                [5, 6] * 4, "e", max_tokens=12, temperature=0.9, seed=42,
            )),
            core.add_request(_req([3, 4, 5] * 3, "n", max_tokens=12,
                                  ignore_eos=True)),
        ]
        return drive(core, seqs)[:2]

    d1, f1 = run(1)
    d8, f8 = run(8)
    assert d1 == d8
    assert f1 == f8
    assert f8["e"] == "eos"


def test_fused_gauges_and_span_shapes():
    """Observability (ISSUE 12 satellite): fused mixed dispatches export
    on the scheduler gauges, and every engine_megastep span carries a
    fused_shapes attr with decode/chunk/verify row counts."""
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    core = EngineCore(
        CFG,
        tiny_engine(
            megastep_k=8, scheduling="chunked", prefill_chunk=32,
            spec_decode="ngram", spec_k=4,
        ),
        seed=0,
    )
    drive(core, _spec_workload(core))
    spans = [s for s in collector.stats() if s.name == "engine_megastep"]
    assert spans, "engine_megastep span missing"
    assert all("fused_shapes" in s.attrs for s in spans)
    assert all(s.attrs["inner_steps"] > 1 for s in spans)
    assert any(s.attrs["fused_shapes"]["verify"] >= 1 for s in spans)
    assert any(s.attrs["fused_shapes"]["chunk"] >= 1 for s in spans)
    st = core.scheduler_stats()
    assert st["fused_mixed_dispatches"] >= 1
    assert st["megastep_forced_single"] == 0
    assert st["megastep_dispatches"] >= 1
    assert 0 < st["dispatches_per_token"] < 1.0


def test_watch_overflow_forces_single_step_with_spec():
    """The ONE documented forced-k=1 path survives the universal
    megastep: a speculating request watching more stop ids than the
    device's MEGASTEP_WATCH_W slots falls back to single-step verify
    dispatches (host stop-scan sees the full list), the stream stays
    correct, and the forced-single gauge records it."""
    probe = EngineCore(CFG, tiny_engine(megastep_k=1), seed=0)
    s = probe.add_request(_req([3, 4, 5] * 3, "p", max_tokens=20,
                               ignore_eos=True))
    d, _, _ = drive(probe, [s])
    # The stop token must first occur a few tokens in: the stream ends at
    # its FIRST occurrence, and one that ends at once drafts nothing.
    stream = d["p"]
    stop_at = next(
        (i for i in range(2, len(stream)) if stream[i] not in stream[:i]), None
    )
    assert stop_at is not None, f"no late first occurrence in {stream}"
    stop_ids = list(range(300, 300 + MEGASTEP_WATCH_W)) + [stream[stop_at]]
    assert not set(stop_ids[:-1]) & set(stream)

    core = EngineCore(
        CFG,
        tiny_engine(megastep_k=8, spec_decode="ngram", spec_k=4),
        seed=0,
    )
    seq = core.add_request(_req(
        [3, 4, 5] * 3, "x", max_tokens=20, stop_token_ids=stop_ids,
        ignore_eos=True,
    ))
    done, fins, _ = drive(core, [seq])
    assert done == {"x": stream[:stop_at + 1]}
    assert fins == {"x": "stop"}
    assert core.exec_stats["megastep_dispatches"] == 0
    assert core.exec_stats["fused_mixed_dispatches"] == 0
    assert core.exec_stats["megastep_forced_single"] >= 1


@pytest.mark.parametrize("async_exec", [False, True])
def test_fused_block_headroom_under_pressure(async_exec):
    """The full fused headroom — n_steps per decode lane, n_steps +
    draft per verify lane, chunk + n_steps - 1 per completing prefill
    chunk — is reserved at plan time: pressure surfaces as preemption
    (or drain-then-preempt under async) BEFORE the dispatch, and the
    replayed stream still matches an unpressured single-step run."""

    def run(blocks, k, ae, spec):
        core = EngineCore(
            CFG,
            tiny_engine(
                num_kv_blocks=blocks, max_model_len=64, megastep_k=k,
                scheduling="chunked", async_exec=ae,
                **(dict(spec_decode="ngram", spec_k=4) if spec else {}),
            ),
            seed=0,
        )
        seqs = [
            core.add_request(_req([5, 6] * 8, "a", max_tokens=24,
                                  ignore_eos=True)),
            core.add_request(_req([7, 8] * 8, "b", max_tokens=24,
                                  ignore_eos=True)),
        ]
        done, fins, _ = drive(core, seqs, max_steps=8000)
        assert core.allocator._partials == 0
        return done, fins, core

    ref = run(64, 1, False, spec=False)[:2]
    d, f, core = run(7, 8, async_exec, spec=True)
    assert (d, f) == ref
    assert core.sched_stats["preemptions"] >= 1


def test_fused_waves_spec_respects_token_budget():
    """A token budget SMALLER than the speculating lane count (waves
    engine — chunked validates the budget up front, waves does not):
    over-budget lanes defer to later fused steps via the rotation cap,
    exactly like the legacy verify path's budget break — no bucket
    overflow, and the stream stays bit-identical to k=1."""

    def run(k):
        core = EngineCore(
            CFG,
            tiny_engine(
                megastep_k=k, spec_decode="ngram", spec_k=4,
                max_num_batched_tokens=4,
            ),
            seed=0,
        )
        seqs = [
            core.add_request(_req([3, 4, 5] * 3, f"s{i}", max_tokens=10,
                                  ignore_eos=True))
            for i in range(6)
        ]
        return drive(core, seqs)

    assert run(1) == run(8)


def test_cancel_mid_fused_megastep_discards_in_flight():
    """Cancel between steps with a fused mixed/verify dispatch in
    flight: the lane's optimistic tokens discard at commit and blocks
    release exactly once."""
    core = EngineCore(
        CFG,
        tiny_engine(
            megastep_k=8, scheduling="chunked", async_exec=True,
            spec_decode="ngram", spec_k=4,
        ),
        seed=0,
    )
    seq = core.add_request(_req([3, 4, 5] * 3, "c", max_tokens=50,
                                ignore_eos=True))
    core.step()  # dispatch prefill
    core.step()  # dispatch fused step 1, commit prefill
    core.cancel_request(seq)
    for _ in range(5):
        core.step()
    assert not core.has_work()
    assert seq not in core.running
    assert core.allocator._partials == 0


# -- mocker virtual-clock A/B -------------------------------------------------


def _mock_megastep_sim(k, base_iter_us=58000.0, B=16, isl=128, osl=64):
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    args = MockEngineArgs(
        num_kv_blocks=8192, block_size=32, max_num_seqs=B,
        max_num_batched_tokens=2048, enable_prefix_caching=False,
        base_iter_us=base_iter_us, megastep_k=k,
    )
    eng = MockTpuEngine(args)
    seqs = []
    for j in range(B):
        prompt = [1 + (j % 7)] * isl
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
    first: dict[str, float] = {}
    streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
    while any(s in eng._running or s in eng._waiting for s in seqs):
        eng._admit()
        p, d = eng._step()
        vt += (
            args.base_iter_us
            + p * args.prefill_us_per_token
            + d * args.decode_us_per_seq
        ) / 1e6
        for s in seqs:
            while not s.out.empty():
                item = s.out.get_nowait()
                if isinstance(item, dict) and item.get("token_ids"):
                    streams[s.request_id].extend(item["token_ids"])
                    first.setdefault(s.request_id, vt)
    decode_s = vt - max(first.values())
    tpot = decode_s / (B * (osl - 1))
    return streams, tpot, eng.scheduler_stats()


def test_mocker_megastep_ab_halves_tpot_at_k8():
    """The acceptance criterion on the mocker's deterministic virtual
    clock: with the dispatch overhead priced at 58 ms, fusing k=8
    iterations per dispatch cuts decode TPOT p50 to <= 0.5x — one
    overhead per 8 device iterations — with a bit-identical stream."""
    s1, tpot1, st1 = _mock_megastep_sim(1)
    s8, tpot8, st8 = _mock_megastep_sim(8)
    assert s1 == s8
    assert tpot8 <= 0.5 * tpot1, (tpot1, tpot8)
    assert st8["megastep_dispatches"] > 0
    assert st1["megastep_dispatches"] == 0
    assert st8["dispatches_per_token"] < st1["dispatches_per_token"]
    assert st8["megastep_k"] == 8


def test_mocker_megastep_fuses_spec_lanes():
    """ISSUE 12 mocker mirror: spec verify lanes RIDE the megastep —
    fused iterations emit (1 + accepted) + (k - 1) tokens per lane under
    ONE priced dispatch, the stream stays bit-identical to k=1, and the
    fused_mixed_dispatches gauge records the lifted carve-out."""
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine

    with pytest.raises(ValueError, match="megastep_k"):
        MockTpuEngine(MockEngineArgs(megastep_k=0))
    s1, st1 = _mock_megastep_sim_spec(1)
    s8, st8 = _mock_megastep_sim_spec(8)
    assert s1 == s8
    assert st1["megastep_dispatches"] == st1["fused_mixed_dispatches"] == 0
    assert st8["megastep_dispatches"] > 0
    assert st8["fused_mixed_dispatches"] > 0
    assert st8["dispatches"] < st1["dispatches"]


def _mock_megastep_sim_spec(k: int):
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    args = MockEngineArgs(
        num_kv_blocks=512, block_size=32, max_num_seqs=4,
        max_num_batched_tokens=2048, enable_prefix_caching=False,
        megastep_k=k, spec_decode="ngram", spec_k=4,
    )
    eng = MockTpuEngine(args)
    seqs = []
    for j in range(4):
        prompt = [1 + j] * 64
        s = _Seq(
            request_id=f"s{j}", prompt=prompt, max_tokens=32,
            out=asyncio.Queue(),
            seq=TokenBlockSequence(prompt, args.block_size),
            prompt_hashes=compute_seq_hashes(prompt, args.block_size),
            stop=StopConditions(max_tokens=32, ignore_eos=True),
        )
        s.spec_k = 4
        seqs.append(s)
        eng._waiting.append(s)
    streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
    while any(s in eng._running or s in eng._waiting for s in seqs):
        eng._admit()
        eng._step()
        for s in seqs:
            while not s.out.empty():
                item = s.out.get_nowait()
                if isinstance(item, dict) and item.get("token_ids"):
                    streams[s.request_id].extend(item["token_ids"])
    return streams, eng.scheduler_stats()
