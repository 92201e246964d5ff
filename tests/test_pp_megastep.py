"""Fused pp megasteps + quantization composition (ISSUE 20).

The tentpole contract: on a pp mesh the decode chain runs INSIDE the
scanned device body — the ``lax.ppermute`` stage hop rides the megastep
scan with M microbatch groups interleaved as a wavefront, sampling /
stop flags / feedback gathers live on device, and the stop state is
psum-replicated — so k fused iterations cost ONE dispatch instead of k
host round-trips per stage. The invariant is the same as every other
fast-path feature: the token stream is BIT-IDENTICAL pp=N vs pp=1 and
fused vs single-step, across greedy + seeded temperature (+ top-p +
logprobs), waves + chunked scheduling, async execution on and off, EOS
inside a fused pp megastep, host-only stops at megastep boundaries, and
block pressure.

The composition satellites: int8 weights and int8 KV pages now shard
per stage (the construction-time ValueErrors are lifted), the canonical
packed ``{kv, scale}`` buffer contract is unchanged on pp workers (the
tier round trip below pins byte identity at every hop), and the combos
that are genuinely unsupported (spec decode, MoE dispatch, pp x tp)
keep pointed construction errors.
"""

import asyncio

import numpy as np
import pytest

import jax

from dynamo_tpu import tracing
from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import MEGASTEP_WATCH_W
from dynamo_tpu.engine.model import init_params_quantized
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.parallel.pipeline import make_pp_mesh

pytestmark = [pytest.mark.unit]

# 4 layers / vocab 512: stages evenly over pp in {2, 4} (tiny_model has
# only 2 layers, so it caps at pp=2 — it drives the tier round trip).
CFG = ModelConfig(
    name="pp-mega-test",
    vocab_size=512,
    hidden_size=64,
    intermediate_size=128,
    num_layers=4,
    num_heads=8,
    num_kv_heads=8,
    head_dim=16,
    dtype="float32",
    tie_embeddings=True,
)


def _eng(**kw) -> EngineConfig:
    base = dict(
        num_kv_blocks=32,
        block_size=8,
        max_num_seqs=8,
        max_model_len=128,
        prefill_buckets=(64,),
        decode_buckets=(4, 8),
    )
    base.update(kw)
    return EngineConfig(**base)


def make_core(pp: int, quant: bool = False, seed: int = 0, **kw) -> EngineCore:
    params = (
        init_params_quantized(jax.random.PRNGKey(0), CFG) if quant else None
    )
    return EngineCore(
        CFG, _eng(**kw), params=params, seed=seed,
        pp_mesh=make_pp_mesh(pp) if pp > 1 else None,
    )


def _req(prompt, rid, max_tokens=8, temperature=0.0, seed=None, top_p=1.0,
         logprobs=None, **stop_kw):
    pre = PreprocessedRequest(
        model="t",
        token_ids=prompt,
        request_id=rid,
        sampling=SamplingOptions(temperature=temperature, seed=seed,
                                 top_p=top_p),
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


def _assert_streams_match(got, ref):
    """Token streams and finish reasons must be BIT-identical. Logprob
    FLOATS get tolerance: the pp lm head is vocab-sharded, so the
    log-softmax normalizer reduces in a different order than the
    single-device program — last-ULP drift on reported alternates is
    expected and does not touch sampling (token ids still match
    exactly)."""
    gd, gf, gl = got
    rd, rf, rl = ref
    assert gd == rd
    assert gf == rf
    assert set(gl) == set(rl)
    for rid in rl:
        assert len(gl[rid]) == len(rl[rid])
        for a, b in zip(gl[rid], rl[rid]):
            assert a["token_id"] == b["token_id"]
            assert a["logprob"] == pytest.approx(b["logprob"], abs=1e-4)
            assert [t for t, _ in a["top"]] == [t for t, _ in b["top"]]
            for (_, la), (_, lb) in zip(a["top"], b["top"]):
                assert la == pytest.approx(lb, abs=1e-4)


def _workload(core):
    """Greedy + seeded-temperature + top-p/logprobs lanes with staggered
    budgets, plus one long prompt (prefill waves / chunks between fused
    pp megasteps)."""
    rng = np.random.RandomState(0)
    seqs = [
        core.add_request(_req(
            list(range(i + 3, i + 30)), f"g{i}", max_tokens=8 + i,
            ignore_eos=True,
        ))
        for i in range(2)
    ]
    seqs.append(core.add_request(_req(
        [3, 5, 7, 9], "t", max_tokens=11, temperature=0.8, seed=11,
        ignore_eos=True,
    )))
    seqs.append(core.add_request(_req(
        [2, 4, 6, 8, 10], "p", max_tokens=9, temperature=0.9, seed=13,
        top_p=0.8, logprobs=3, ignore_eos=True,
    )))
    seqs.append(core.add_request(_req(
        list(rng.randint(1, 400, size=50)), "long", max_tokens=6,
        ignore_eos=True,
    )))
    return seqs


# -- bit-identical parity: pp on/off x fused on/off ---------------------------


@pytest.mark.parametrize(
    "pp",
    [2, pytest.param(4, marks=pytest.mark.slow)],  # pp=4 in tier-1 via the
)                                                  # int8+kvint8 compose test
def test_parity_fused_pp_vs_single_device(pp):
    """The acceptance invariant: pp=N with fused k=4 megasteps AND pp=N
    forced single-step both stream bit-identically to the unpipelined
    single-step engine — greedy, seeded temperature, top-p, and logprob
    lanes in one batch."""

    def run(p, k):
        core = make_core(p, megastep_k=k)
        out = drive(core, _workload(core))
        return out, core

    ref, _ = run(1, 1)
    got_single, _ = run(pp, 1)
    got_fused, core = run(pp, 4)
    _assert_streams_match(got_single, ref)
    _assert_streams_match(got_fused, ref)
    assert core.exec_stats["pp_fused_dispatches"] >= 1


def test_parity_pp_chunked_scheduling():
    """Chunked token-budget scheduling composes with pp (the old
    construction guard is lifted): mixed chunk+decode iterations run as
    single pp steps, decode-only iterations fuse — stream identical to
    the unpipelined single-step chunked engine."""

    def run(p, k):
        core = make_core(
            p, megastep_k=k, scheduling="chunked", prefill_chunk=32,
            max_num_batched_tokens=64,
        )
        return drive(core, _workload(core))

    _assert_streams_match(run(2, 4), run(1, 1))


@pytest.mark.slow
def test_parity_pp_async_composition():
    """pp x async-exec compose: one fused pp dispatch in flight while
    the next plans against the optimistic overlay — stream identical to
    the synchronous unpipelined loop (async OFF on the pp engine is the
    parity test above)."""

    def run(p, k, ae):
        core = make_core(p, megastep_k=k, async_exec=ae)
        return drive(core, _workload(core))

    _assert_streams_match(run(2, 4, True), run(1, 1, False))


# -- stops inside / at the boundary of a fused pp megastep --------------------


@pytest.mark.slow
def test_eos_inside_fused_pp_megastep():
    """A seeded lane that samples EOS at an inner wavefront iteration of
    a fused pp megastep finishes with reason 'eos' mid-megastep — the
    device stop flags see it on the drain stage, the psum-replicated
    alive state masks its remaining wavefront slots, and the stream
    matches the unpipelined single-step engine exactly; batch neighbors
    are untouched."""
    probe = make_core(1, megastep_k=1)
    s = probe.add_request(_req(
        [1, 2, 3], "p", max_tokens=12, temperature=0.9, seed=42,
        ignore_eos=True,
    ))
    d, _, _ = drive(probe, [s])
    eos = d["p"][4]  # mid-stream token -> EOS lands INSIDE a k=8 megastep
    if eos in d["p"][:4]:
        pytest.skip("seeded stream repeats before position 4")

    def run(p, k):
        core = EngineCore(
            CFG, _eng(megastep_k=k), seed=0, eos_token_ids=(eos,),
            pp_mesh=make_pp_mesh(p) if p > 1 else None,
        )
        seqs = [
            core.add_request(_req(
                [1, 2, 3], "e", max_tokens=12, temperature=0.9, seed=42,
            )),
            core.add_request(_req([9, 9, 9], "n", max_tokens=12,
                                  ignore_eos=True)),
        ]
        return drive(core, seqs)[:2]

    d1, f1 = run(1, 1)
    d8, f8 = run(2, 8)
    assert d1 == d8
    assert f1 == f8
    assert f8["e"] == "eos"
    assert d8["e"] == d["p"][:5]  # stopped mid-megastep, not at a boundary


def test_host_only_stop_forces_single_and_rolls_back_on_pp():
    """A stop watch WIDER than the device's MEGASTEP_WATCH_W slots is
    the one documented un-fused path — on a pp engine it must force the
    decode chain to k=1 (host stop-scan authority between dispatches),
    surface on the pp_forced_single gauge, and still match the
    unpipelined stream and finish reason exactly."""
    probe = make_core(1, megastep_k=1)
    s = probe.add_request(_req(
        [9, 9, 9], "p", max_tokens=20, temperature=0.9, seed=7,
        ignore_eos=True,
    ))
    d, _, _ = drive(probe, [s])
    stop_tok = d["p"][5]
    if stop_tok in d["p"][:5]:
        pytest.skip("seeded stream repeats before position 5")
    stop_ids = list(range(300, 300 + MEGASTEP_WATCH_W)) + [stop_tok]

    def run(p, k):
        core = make_core(p, megastep_k=k)
        seq = core.add_request(_req(
            [9, 9, 9], "x", max_tokens=20, temperature=0.9, seed=7,
            stop_token_ids=stop_ids, ignore_eos=True,
        ))
        out = drive(core, [seq])[:2]
        assert core.allocator._partials == 0
        return out, core

    ref, _ = run(1, 1)
    got, core = run(2, 8)
    assert got == ref == ({"x": d["p"][:6]}, {"x": "stop"})
    assert core.exec_stats["pp_fused_dispatches"] == 0
    assert core.exec_stats["pp_forced_single"] >= 1


# -- block pressure on a pp engine --------------------------------------------


@pytest.mark.slow
def test_block_pressure_drain_preempt_on_pp_engine():
    """k tokens of per-lane block headroom are reserved at plan time on
    the pp path too: pressure surfaces as drain -> preempt BEFORE the
    fused pp dispatch (never as mid-megastep exhaustion), and the
    preempted-and-replayed stream still matches an unpressured
    unpipelined single-step run."""

    def run(p, blocks, k):
        core = make_core(p, num_kv_blocks=blocks, max_model_len=64,
                         megastep_k=k)
        seqs = [
            core.add_request(_req(list(range(1, 17)), "a", max_tokens=24,
                                  ignore_eos=True)),
            core.add_request(_req(list(range(20, 36)), "b", max_tokens=24,
                                  ignore_eos=True)),
        ]
        done, fins, _ = drive(core, seqs, max_steps=8000)
        assert core.allocator._partials == 0
        return done, fins, core

    ref = run(1, 64, 1)[:2]
    d, f, core = run(2, 7, 8)
    assert (d, f) == ref
    assert core.sched_stats["preemptions"] >= 1


# -- quantization composition -------------------------------------------------


@pytest.mark.parametrize(
    "pp",
    [pytest.param(2, marks=pytest.mark.slow), 4],  # pp=2 compose in tier-1
)                                                  # via the tier round trip
def test_int8_weights_and_kv_compose_with_pp(pp):
    """The lifted carve-out, both quantizations at once: int8 weight
    pages AND packed {kv, scale} int8 KV shard per stage, the engine
    constructs (no ValueError), serves fused pp megasteps, and streams
    bit-identically to the unpipelined int8+kvint8 engine."""

    def run(p, k):
        core = make_core(p, quant=True, kv_dtype="int8", megastep_k=k)
        out = drive(core, _workload(core))
        return out, core

    ref, _ = run(1, 1)
    got, core = run(pp, 4)
    _assert_streams_match(got, ref)
    assert core.exec_stats["pp_fused_dispatches"] >= 1
    # The stacked quantized cache: ONE {kv, scale} dict, layer axis first.
    assert isinstance(core.cache, dict)
    assert set(core.cache) == {"kv", "scale"}
    assert core.cache["kv"].shape[0] == CFG.num_layers


def test_kvint8_pp_tier_round_trip_is_byte_stable(tmp_path):
    """THE round-trip satellite on a pp stage: int8 KV blocks written by
    the pp engine evict -> host tier -> disk tier -> onboard back to
    device BYTE-identically (the canonical packed buffer from PR 8 is
    unchanged under pp — quantize once, never re-quantize), and the
    onboarded prefix serves the same stream."""
    from dynamo_tpu.engine.kv_quant import unpack_kv_page
    from tests.test_host_kv_tier import _fill_with_noise

    t_cfg = tiny_model()
    mesh = make_pp_mesh(2)  # tiny preset has 2 layers -> pp=2

    def t_core(**kw):
        return EngineCore(
            t_cfg, tiny_engine(kv_dtype="int8", **kw), seed=0, pp_mesh=mesh,
        )

    prompt = list(range(7, 7 + 40))
    base = t_core()
    ref, _, _ = drive(base, [base.add_request(_req(prompt, "ref",
                                                   max_tokens=6))])

    core = t_core(
        num_kv_blocks=24, host_kv_blocks=4,
        disk_kv_dir=str(tmp_path / "g3"), disk_kv_blocks=256,
        max_model_len=128,
    )
    s1 = core.add_request(_req(prompt, "a", max_tokens=6))
    drive(core, [s1])
    bs = core.engine.block_size
    cap = (len(prompt) - 1) // bs
    prefix_hashes = s1.prompt_hashes[:cap]
    # Hop 0: canonical packed bytes while device-resident on the pipe.
    w0 = core.read_cached_pages(prefix_hashes)
    assert len(w0) == cap
    geom = core._page_geometry()
    for buf in w0:
        unpack_kv_page(buf, *geom)  # parses at the local geometry

    # Hop 1+2: evict through host into disk.
    _fill_with_noise(core, n_requests=8)
    _fill_with_noise(core, n_requests=8, tag=2000)
    core.offload.flush()
    in_host = [h for h in prefix_hashes if h in core.host_pool]
    in_disk = [h for h in prefix_hashes if h in core.disk_pool]
    assert in_host or in_disk, "noise did not push the prefix off-device"
    for i, h in enumerate(prefix_hashes):
        if h in core.host_pool:
            assert core.host_pool._blocks[h].kv.tobytes() == w0[i], (
                "host-tier bytes diverged from the pp-stage device write"
            )
        if h in core.disk_pool:
            assert core.disk_pool.peek(h).tobytes() == w0[i], (
                "disk-tier bytes diverged from the pp-stage device write"
            )

    # Hop 3: onboard back onto the pipe (admission prefix hit).
    s2 = core.add_request(_req(prompt, "b", max_tokens=6))
    d2, _, _ = drive(core, [s2])
    assert core.host_pool.stats.onboards + core.disk_pool.stats.onboards > 0
    assert s2.num_cached_tokens > 0
    assert d2["b"] == ref["ref"], "output changed across the tier round trip"
    w1 = core.read_cached_pages(prefix_hashes)
    assert w1 == w0, "onboarded device bytes diverged from the original"


# -- construction matrix: lifted composition vs pointed errors ----------------


def test_lifted_combos_construct():
    """Both directions pinned, the 'now works' half: every combo the
    first pp cut rejected at construction now builds a working engine."""
    make_core(2, quant=True)                      # int8 weights + pp
    make_core(2, kv_dtype="int8")                 # int8 KV + pp
    make_core(2, scheduling="chunked", prefill_chunk=32,
              max_num_batched_tokens=64)          # chunked + pp
    make_core(2, async_exec=True)                 # async + pp
    make_core(2, quant=True, kv_dtype="int8", async_exec=True,
              megastep_k=8)                       # all of it at once


def test_unsupported_combos_keep_pointed_errors():
    """The 'still rejected' half: genuinely unsupported combos fail at
    construction with pointed messages, not deep shard-setup errors."""
    with pytest.raises(ValueError, match="speculative decoding"):
        make_core(2, spec_decode="ngram", spec_k=4)
    with pytest.raises(ValueError, match="mutually exclusive"):
        from dynamo_tpu.parallel.sharding import make_mesh

        EngineCore(CFG, _eng(), seed=0, mesh=make_mesh(dp=1, tp=2),
                   pp_mesh=make_pp_mesh(2))
    with pytest.raises(ValueError, match="decode bucket"):
        make_core(4, decode_buckets=(6,))


def test_multihost_pp_cli_guard():
    """pp on the multihost leader/follower path stays a pointed CLI
    error (the one genuinely unsupported deployment shape named by the
    issue)."""
    from dynamo_tpu.backends.jax.main import run_jax_worker

    with pytest.raises(ValueError, match="--pp .* --nnodes"):
        asyncio.run(run_jax_worker(None, nnodes=2, pp=2))


# -- observability ------------------------------------------------------------


def test_pp_gauges_and_megastep_span():
    """scheduler_pp_* gauge sources and the pp_stages span attr: fused
    pp dispatches and pipe occupancy export on scheduler_stats, and
    every engine_megastep span carries pp_stages."""
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()
    core = make_core(2, megastep_k=8)
    seq = core.add_request(_req([1, 2, 3], "m", max_tokens=16,
                                ignore_eos=True))
    drive(core, [seq])
    spans = [s for s in collector.stats() if s.name == "engine_megastep"]
    assert spans, "engine_megastep span missing"
    assert all(s.attrs["pp_stages"] == 2 for s in spans)
    st = core.scheduler_stats()
    assert st["pp_stages"] == 2
    assert st["pp_fused_dispatches"] >= 1
    # k*M wavefront items over k*M + pp - 1 rounds.
    k = max(1, core.engine.megastep)
    km = k * core._pp_micro
    assert st["pp_pipe_occupancy"] == pytest.approx(km / (km + 1))
    # Unpipelined engines report the trivial pipe.
    st1 = make_core(1).scheduler_stats()
    assert st1["pp_stages"] == 1
    assert st1["pp_pipe_occupancy"] == 1.0


def test_pp_decode_states_the_decode_shape():
    """The wavefront's decode rounds are q_len = 1 calls and say so
    (``cu_q_lens=None``, as ``decode_tokens`` does): counted as the decode
    shape, so on a TPU they run the kernel's decode grid and the dispatch
    annotation's ``attn`` is not empty."""
    from dynamo_tpu.ops import ragged_attention as ra

    before = ra.traced_calls().get(("decode", "reference"), 0)
    core = make_core(2, megastep_k=8)
    seq = core.add_request(_req([1, 2, 3], "d", max_tokens=16, ignore_eos=True))
    drive(core, [seq])
    assert core.scheduler_stats()["pp_fused_dispatches"] >= 1
    assert ra.traced_calls().get(("decode", "reference"), 0) > before
    assert "reference" in ra.traced_impl("decode").split("+")


# -- mocker mirror ------------------------------------------------------------


def _mock_pp_sim(pp: int, k: int, B=8, isl=64, osl=16):
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    args = MockEngineArgs(
        num_kv_blocks=1024, block_size=32, max_num_seqs=B,
        max_num_batched_tokens=2048, enable_prefix_caching=False,
        megastep_k=k, pp=pp,
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
    streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
    pp_rounds: list[int] = []
    while any(s in eng._running or s in eng._waiting for s in seqs):
        eng._admit()
        eng._step()
        pp_rounds.append(eng._last_pp_rounds)
        for s in seqs:
            while not s.out.empty():
                item = s.out.get_nowait()
                if isinstance(item, dict) and item.get("token_ids"):
                    streams[s.request_id].extend(item["token_ids"])
    return streams, pp_rounds, eng


@pytest.mark.parametrize("pp", [2, 4])
def test_mocker_pp_fused_and_forced_single_stream_identically(pp):
    """The functional half of the former pp A/B: pp on or off and fused
    or not, the streams are the same; the k = 1 pipe reports only
    forced-single dispatches and the k = 8 pipe only fused ones, at a
    fuller pipe and fewer dispatches a token. No time is compared."""
    ref, _, _ = _mock_pp_sim(1, 1)
    assert _mock_pp_sim(1, 8)[0] == ref
    s1, _, eng1 = _mock_pp_sim(pp, 1)
    s8, _, eng8 = _mock_pp_sim(pp, 8)
    assert s1 == ref and s8 == ref
    st1, st8 = eng1.scheduler_stats(), eng8.scheduler_stats()
    assert st1["pp_stages"] == st8["pp_stages"] == pp
    assert st1["pp_forced_single"] > 0 and st1["pp_fused_dispatches"] == 0
    assert st8["pp_fused_dispatches"] > 0 and st8["pp_forced_single"] == 0
    assert st8["pp_pipe_occupancy"] > st1["pp_pipe_occupancy"]
    assert st8["dispatches_per_token"] < st1["dispatches_per_token"]


def test_mocker_pp_hops_priced():
    """The mocker mirror: decode dispatches price k*pp + pp - 1 stage
    hops on the virtual clock, and only under pp."""
    from dynamo_tpu import knobs
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine

    with pytest.raises(ValueError, match="pp"):
        MockTpuEngine(MockEngineArgs(pp=0))

    _, rounds_ref, eng_ref = _mock_pp_sim(1, 1)
    _, rounds1, _ = _mock_pp_sim(4, 1)
    _, rounds8, _ = _mock_pp_sim(4, 8)
    assert set(rounds_ref) == {0}  # pp off: no hops ever priced
    # Host-rollback baseline: bubble per token; fused: bubble per k.
    assert max(rounds1) == 1 * 4 + 3
    assert max(rounds8) == 8 * 4 + 3
    # The hop price lands on the virtual clock (and only under pp).
    base = eng_ref.iter_time_s(0, 8)
    hop = knobs.get_float("DYN_PP_HOP_US")
    assert eng_ref.iter_time_s(0, 8, pp_rounds=35) == pytest.approx(
        base + 35 * hop / 1e6
    )
