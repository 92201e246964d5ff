"""SDAR's step (``ModelConfig.block_length > 0``): a megastep that denoises a
block of places a lane and commits 0..B tokens. The engine is held to the
plain reference (``chipbench/reference/sdar_moe.py``) by the architecture's
own ``score_probe`` at ``TIGHT`` (float32 on both sides) over schedules,
thresholds, prompt tails, cuts, loops, preemption and prefix hits; the
dropless layer's shares add up under either scoring; what the model does
not carry is refused by name.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import architectures
from chipbench.architectures import sdar_moe as arch
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from chipbench.reference import sdar_moe as reference
from dynamo_tpu.engine import PRESETS, EngineConfig, ModelConfig
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    UnsupportedModelOption,
    mixtral_8x7b,
    sdar_30b_a3b_6l,
    tiny_engine,
    tiny_lfm2,
    tiny_moe,
    tiny_sdar,
)
from dynamo_tpu.engine import core as core_mod
from dynamo_tpu.engine.core import EngineCore, _megastep_blocks, _resolve_block_megastep
from dynamo_tpu.engine.model import block_hidden, block_logits, init_cache, init_params
from dynamo_tpu.engine.sampler import (
    LOGPROBS_K,
    hidden_at_most,
    sample_seeded,
    unmask_block,
)
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.ops.ragged_attention import block_attention, ragged_paged_attention_ref

CFG = tiny_sdar()
FILE = load_config("tiny-sdar-rehearsal")
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=120)]
TIGHT = 1e-4   # float32 on both sides: the readings are 1e-6


def file_with(steps: int = 2, threshold: float = 0.9) -> dict:
    cfg = json.loads(json.dumps(FILE))
    cfg["denoising_steps"] = steps
    cfg["confidence_threshold"] = threshold
    return cfg


def make_core(cfg: dict = FILE, seed: int = 5, **engine) -> EngineCore:
    fields = dict(engine_overrides(cfg), **engine)
    return EngineCore(ModelConfig(**model_fields(cfg)), EngineConfig(**fields), seed=seed)


def _req(prompt, rid, max_tokens, logprobs=None, **stop):
    return PreprocessedRequest(
        model="m", token_ids=list(prompt), request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, **stop),
        output=OutputOptions(logprobs=logprobs))


def run_to_completion(core, seqs):
    out = {s.request_id: [] for s in seqs}
    chunks = {s.request_id: [] for s in seqs}
    while any(s.finish is None for s in seqs):
        for s, o in core.step():
            out[s.request_id] += list(o.token_ids)
            chunks[s.request_id].append(len(o.token_ids))
    while core.has_work():
        core.step()
    return out, chunks


def _streams(prompts, max_tokens, cfg=FILE, **engine):
    core = make_core(cfg, **engine)
    seqs = [core.add_request(_req(p, f"s{i}", n, ignore_eos=True))
            for i, (p, n) in enumerate(zip(prompts, max_tokens))]
    return run_to_completion(core, seqs)[0], core


def held_to_reference(core, cfg, body):
    got = check.score_request(core, cfg, body)
    return check.compare(got["served"], got["scored"], atol=TIGHT), got


def test_the_preset_is_the_file_and_the_published_size_its_sums():
    mf = model_fields(FILE)
    assert dataclasses.replace(ModelConfig(**mf), name="tiny-sdar") == CFG
    assert CFG.shared_sparse and CFG.block_length == 4 and not CFG.layer_groups
    assert "tiny-sdar" in PRESETS and "sdar-30b-a3b-6l" in PRESETS
    big = sdar_30b_a3b_6l()
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2 * 2048 + 2048 * 128 + 128 * 3 * 2048 * 768
    assert big.param_bytes() == 2 * (6 * layer + 2 * 2048 * 151936 + 2048) == 8_722_111_488
    assert model_mod._routed_down_divisor(big) == 16
    # the head norms of a block model are drawn around a gain over 1 (a query reads a
    # few keys, not its context's mean); every other model's around 1, as they were
    gain = model_mod._QK_NORM_GAIN_BLOCKS
    assert 1.25 < gain < 1.75       # the chip's sweep: under it fp8 passes, over it bf16 fails
    assert model_mod._qk_norm_gain(big) == model_mod._qk_norm_gain(CFG) == gain
    assert model_mod._qk_norm_gain(tiny_lfm2()) == 1.0
    drawn = init_params(jax.random.PRNGKey(0), CFG)["layers"]
    assert abs(float(jnp.mean(drawn["q_layernorm"])) - gain) < 0.1
    assert abs(float(jnp.mean(drawn["k_layernorm"])) - gain) < 0.1
    # what tells the dropless layer from the mixtral path is the layout, not the scoring
    assert big.shared_sparse and big.router_scoring == "softmax"
    assert not mixtral_8x7b().shared_sparse and not tiny_moe().shared_sparse


# -- the engine against the reference ------------------------------------------

@pytest.mark.parametrize("steps,threshold", [(1, 0.9), (2, 0.9), (4, 0.9), (2, 0.03), (4, 0.03)],
                         ids=["1-step", "2-steps", "4-steps", "2-steps-fires", "4-steps-fires"])
def test_the_engine_keeps_the_references_schedule(steps, threshold):
    cfg = file_with(steps, threshold)
    core = make_core(cfg)
    assert core.cfg.denoising_steps == steps
    assert core.engine.megastep == max(1, 8 // (steps + 1)) * (steps + 1)
    verdict, got = held_to_reference(core, cfg, {"prompt_ids": PROMPT[:98], "max_tokens": 17,
                                                 "top": 5})
    assert verdict["ok"] and verdict["compared"] == 170 and verdict["max_abs_diff"] < TIGHT
    first, repeat = got["served"]
    assert first["tokens"] == repeat["tokens"] and repeat["cached_tokens"] == 96
    fired = core.exec_stats["places_revealed_threshold"]
    assert (fired > 0) == (threshold < 0.5)
    # every entry says where its token lies and which step revealed it; the
    # last block was cut at 3 of 4 and says what came after
    extra = first["extra"]
    assert [(e["block"], e["place"]) for e in extra] == [
        ((98 + j) // 4, (98 + j) % 4) for j in range(17)]
    assert all(0 <= e["step"] < steps for e in extra)
    assert [len(e.get("cut", ())) for e in extra] == [0] * 16 + [1]


@pytest.mark.parametrize("whole,tail", [(0, 1), (0, 2), (0, 3), (96, 0), (96, 1), (96, 2),
                                        (96, 3)])
def test_a_prompts_tail_opens_the_first_block(whole, tail):
    core = make_core()
    verdict, got = held_to_reference(
        core, FILE, {"prompt_ids": PROMPT[:whole + tail], "max_tokens": 9, "top": 5})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT
    assert got["served"][1]["cached_tokens"] == whole
    # the first block generated B - tail places: its passes are counted once a lane, two a
    # block, and every block but a request's last had its clean rows ride the next one's
    st = core.exec_stats
    assert st["block_pending_dropped"] == 2                    # the probe is sent twice
    assert st["denoise_forwards"] == 2 * (st["commit_forwards"] + st["block_pending_dropped"])
    assert st["commit_forwards"] == st["block_clean_folded"]


@pytest.mark.parametrize("fault", ["fp8", "causal", "order"])
def test_each_fault_fails_the_comparison(fault):
    cfg = file_with(4)
    failed = 0
    for seed in (1, 3):
        core = make_core(cfg, seed=seed)
        body = {"prompt_ids": PROMPT[:98], "max_tokens": 17, "top": 5}
        got = check.score_request(core, cfg, body)
        assert check.compare(got["served"], got["scored"])["ok"]
        scored = arch.score_probe(cfg, core.params, body["prompt_ids"], got["served"][0],
                                  faults=(fault,))
        failed += not check.compare(got["served"][:1], {"sequences": [scored]})["ok"]
    assert failed == 2


@pytest.mark.parametrize("lie", ["step", "place", "cut"])
def test_a_claim_that_is_no_schedule_is_not_scored(lie):
    core = make_core()
    body = {"prompt_ids": PROMPT[:98], "max_tokens": 17, "top": 5}
    probe = check.run_probe(core, body["prompt_ids"], 17, 5, "lie", extra=True)
    if lie == "step":      # every place of a block revealed by the last step
        probe["extra"] = [dict(e, step=1) for e in probe["extra"]]
    elif lie == "place":
        probe["extra"][3] = dict(probe["extra"][3], place=0)
    else:                  # the cut block's last place is not told
        probe["extra"][-1] = {k: v for k, v in probe["extra"][-1].items() if k != "cut"}
    scored = arch.score_probe(FILE, core.params, body["prompt_ids"], probe)
    assert scored["finite"] is False
    assert not check.compare([probe], {"sequences": [scored]})["ok"]


def test_the_reference_forward_is_block_masked():
    """Row p sees key j iff j // B <= p // B: a token AFTER p in p's block
    moves p's logits, one in the next block does not."""
    core = make_core()
    mf = model_fields(FILE)
    base = arch.reference_logits(core.params, mf, PROMPT[:12], [5])
    same_block = list(PROMPT[:12])
    same_block[7] = (same_block[7] + 1) % 380
    next_block = list(PROMPT[:12])
    next_block[8] = (next_block[8] + 1) % 380
    assert float(jnp.abs(arch.reference_logits(core.params, mf, same_block, [5]) - base).max()) > 1e-3
    assert float(jnp.abs(arch.reference_logits(core.params, mf, next_block, [5]) - base).max()) == 0


# -- cuts ------------------------------------------------------------------------

def test_max_tokens_cuts_a_block_and_only_whole_pages_are_hashed():
    core = make_core()
    seq = core.add_request(_req(PROMPT[:30], "cut", 11, ignore_eos=True))
    out, chunks = run_to_completion(core, [seq])
    assert len(out["cut"]) == 11 and seq.finish == "length" and seq.generated == 11
    # 30 + 11 = 41 tokens: blocks of 4 end at 44, 3 places discarded; two blocks
    # a dispatch and one chunk a dispatch: 2 + 4, then 4 + 1
    assert chunks["cut"] == [6, 5]
    assert core.exec_stats["block_places_discarded"] >= 3
    assert core.exec_stats["committed_tokens"] == 11
    # five whole pages of 8 tokens were kept (40 of 41 tokens): those are hashed
    assert core.cached_prefix_tokens(PROMPT[:30] + out["cut"]) == 40


def test_eos_inside_a_block_ends_the_request_there():
    free = make_core()
    seq = free.add_request(_req(PROMPT[:30], "free", 24, ignore_eos=True))
    want = run_to_completion(free, [seq])[0]["free"]
    at = 9                                # inside the third generated block
    eos = want[at]
    first = want.index(eos)
    core = EngineCore(CFG, EngineConfig(**engine_overrides(FILE)), seed=5,
                      eos_token_ids=(eos,))
    seq = core.add_request(_req(PROMPT[:30], "eos", 24))
    got = run_to_completion(core, [seq])[0]["eos"]
    assert got == want[:first + 1] and seq.finish == FinishReason.EOS.value
    assert seq.generated == first + 1 and first <= at
    # a stop id stops it the same way
    core = make_core()
    seq = core.add_request(_req(PROMPT[:30], "stop-id", 24, ignore_eos=True,
                                stop_token_ids=[eos]))
    assert run_to_completion(core, [seq])[0]["stop-id"] == want[:first + 1]
    assert seq.finish == "stop" and not core.running


def test_a_stop_string_cuts_a_block_from_the_host():
    """Stop strings are text: the detokenizer finds one and cancels the
    request, wherever in a block its last token lies; the engine discards
    the places after it and gives the blocks back."""
    from dynamo_tpu.llm.detokenizer import StopStringChecker

    core = make_core(async_exec=True)
    seq = core.add_request(_req(PROMPT[:30], "text", 60, ignore_eos=True))
    text = lambda toks: "".join(chr(65 + t % 26) for t in toks)  # noqa: E731
    free = make_core()
    ref = free.add_request(_req(PROMPT[:30], "w", 60, ignore_eos=True))
    want = text(run_to_completion(free, [ref])[0]["w"])
    stop = want[13:16]                    # ends at place 3 of 4 of a block
    checker = StopStringChecker([stop])
    shown = ""
    while not checker.stopped and core.has_work():
        for s, o in core.step():
            piece, stopped = checker.step(text(o.token_ids))
            shown += piece
            if stopped:
                core.cancel_request(s)
    while core.has_work():
        core.step()
    assert shown == want[: want.index(stop)] and not core.running
    assert seq.cancelled and core.exec_stats["block_places_discarded"] > 0


# -- one stream whatever the loop ------------------------------------------------

@pytest.mark.parametrize("async_exec", [False, True], ids=["sync", "async"])
@pytest.mark.parametrize("blocks", [1, 2])
def test_one_stream_whatever_the_loop_and_the_blocks_a_dispatch(async_exec, blocks):
    prompts = [PROMPT[:n] for n in (30, 17, 64, 3)]
    lengths = [21, 33, 9, 14]
    want, _ = _streams(prompts, lengths, async_exec=False, megastep_k=3)
    got, core = _streams(prompts, lengths, async_exec=async_exec, megastep_k=3 * blocks)
    assert core.engine.megastep == 3 * blocks and core.pipelined == async_exec
    assert got == want and [len(got[f"s{i}"]) for i in range(4)] == lengths
    if async_exec:
        assert core.exec_stats["pipelined_dispatches"] > 0


def test_seeded_sampling_is_one_stream_too():
    def run(**engine):
        core = make_core(**engine)
        seq = core.add_request(PreprocessedRequest(
            model="m", token_ids=PROMPT[:30], request_id="t",
            sampling=SamplingOptions(temperature=0.8, top_k=20, seed=7),
            stop=StopConditions(max_tokens=21, ignore_eos=True), output=OutputOptions()))
        return run_to_completion(core, [seq])[0]["t"]
    assert run(async_exec=False, megastep_k=3) == run(async_exec=True, megastep_k=6)


# -- preemption and the prefix cache ----------------------------------------------

def _pages(core, seq, n_pages):
    return [np.asarray(layer[np.asarray(seq.block_ids[:n_pages])]) for layer in core.cache]


def test_preempt_and_recompute_gives_the_same_pages_and_stream():
    want, _ = _streams([PROMPT[:21]], [30], async_exec=False)
    core = make_core(async_exec=False)
    seq = core.add_request(_req(PROMPT[:21], "s0", 30, ignore_eos=True))
    got = []
    while seq.generated < 15:
        for _, out in core.step():
            got += list(out.token_ids)
    # 21 + 15 = 36 tokens = 4 whole pages of 8, written by clean passes
    before = _pages(core, seq, 4)
    with core._step_lock:
        core._preempt(seq)
    core.clear_kv_cache()                 # nothing to find: the wave recomputes them all
    assert seq.prompt_len == 36 and seq.tail == 0 and seq.generated == 15
    while seq.prefilled < 36:
        core.step()
    after = _pages(core, seq, 4)
    for a, b in zip(before, after):
        np.testing.assert_allclose(a, b, atol=1e-5)    # a wave's rows against a pass's
    done, _ = run_to_completion(core, [seq])
    assert got + done["s0"] == want["s0"] and core.sched_stats["preemptions"] == 1


def test_block_pressure_preempts_and_the_streams_are_the_unpressed_ones():
    prompts = [list(range(1 + 20 * i, 17 + 20 * i)) for i in range(3)]
    roomy, _ = _streams(prompts, [33] * 3, num_kv_blocks=64, max_model_len=64)
    tight, core = _streams(prompts, [33] * 3, num_kv_blocks=14, max_model_len=64)
    assert core.sched_stats["preemptions"] >= 1
    assert tight == roomy and all(len(v) == 33 for v in tight.values())


def test_a_prefix_hit_on_a_page_of_generated_blocks():
    core = make_core()
    seq = core.add_request(_req(PROMPT[:16], "a", 17, ignore_eos=True))
    first = run_to_completion(core, [seq])[0]["a"]
    # pages 2 and 3 (tokens 16..31) hold generated blocks only
    longer = PROMPT[:16] + first[:16] + PROMPT[40:47]
    verdict, got = held_to_reference(core, FILE, {"prompt_ids": longer, "max_tokens": 9, "top": 5})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT
    assert got["served"][0]["cached_tokens"] == 32


# -- a block's clean rows ride the next block's first pass -----------------------------

def _step_until(core, seq, generated):
    got = []
    while seq.generated < generated:
        for _, out in core.step():
            got += list(out.token_ids)
    return got


@pytest.mark.parametrize("how", ["finished", "cancelled", "preempted"])
def test_a_lane_that_ends_with_a_block_pending_spends_no_pass_on_it(how):
    """The stream is the parent's, every pass is a denoising pass of a block
    that was streamed, and the pending block's clean rows are never run: the
    counters say so (a resumed lane's wave recomputes the block as prompt)."""
    core = make_core(async_exec=False)
    seq = core.add_request(_req(PROMPT[:30], "t", 21, ignore_eos=True))
    st = core.exec_stats
    if how == "finished":
        got = run_to_completion(core, [seq])[0]["t"]
        dropped = 1
    else:
        got = _step_until(core, seq, 10)
        # 2 + 4 + 4 + 4 places: the fourth block is revealed, streamed, and pending
        assert seq.generated == 14 and seq.pending_block == got[10:14]
        assert seq.processed == 40 and len(seq.hashed.all_tokens()) == 40
        if how == "cancelled":
            core.cancel_request(seq)
            passes = st["denoise_forwards"]
            while core.has_work():
                assert core.step() == []
            assert st["denoise_forwards"] == passes and not core.running
            assert st["block_pending_dropped"] == 1 and seq.pending_block == []
            assert got == PARENT_GREEDY[:14]
            assert st["commit_forwards"] == st["block_clean_folded"] == 3
            return
        with core._step_lock:
            core._preempt(seq)
        assert st["block_pending_dropped"] == 1 and seq.pending_block == []
        assert seq.prompt == PROMPT[:30] + got and seq.tail == 0   # 44 tokens: 11 whole blocks
        got += run_to_completion(core, [seq])[0]["t"]
        dropped = 2
    assert got == PARENT_GREEDY and st["block_pending_dropped"] == dropped
    # blocks revealed: 6 (2 + 4 x 4 + 3 of 4); two passes each and nothing else
    blocks = 6
    assert st["blocks_committed"] == blocks and st["denoise_forwards"] == 2 * blocks
    assert st["commit_forwards"] == st["block_clean_folded"] == blocks - dropped
    assert st["block_rows"] == (2 * blocks + blocks - dropped) * 4


@pytest.mark.parametrize("async_exec", [False, True], ids=["sync", "async"])
@pytest.mark.parametrize("blocks", [1, 2])
def test_a_page_is_published_only_after_its_last_blocks_clean_rows(async_exec, blocks):
    """A page of 8 tokens holds two blocks. Its second block's clean rows ride
    the first pass of the block after it, in a LATER dispatch where the page
    ends with the dispatch: the page is committed to the allocator, hashed
    and sent as a KV event at that dispatch's landing and not before, under
    both loops; the cursor never passes a block that is not clean; and what
    is published is final: a wave over the same tokens writes the same page."""
    core = make_core(async_exec=async_exec, megastep_k=3 * blocks)
    log = core._exec_log = []
    stored = []
    core.allocator.on_stored = lambda hashes, parent: (
        stored.extend(hashes), log.extend(("stored", len(stored) - len(hashes) + j)
                                         for j in range(len(hashes))))
    seq = core.add_request(_req(PROMPT[:16], "p", 29, ignore_eos=True))
    out, snaps = [], {}
    while seq.finish is None:
        for _, o in core.step():
            out += list(o.token_ids)
        assert seq.processed % 4 == 0 and seq.committed_blocks * 8 <= seq.processed
        assert seq.hashed is None or len(seq.hashed.all_tokens()) == seq.processed
        assert seq.processed + len(seq.pending_block) <= 16 + seq.generated + 3
        for page in range(len(snaps), seq.committed_blocks):
            snaps[page] = [np.asarray(layer[seq.block_ids[page]]) for layer in core.cache]
    while core.has_work():
        core.step()
    # 16 + 29 = 45 tokens: the prompt's two pages by the wave (dispatch 1), then pages 2-4
    # (generated blocks 0-5); the block after page 4 is the request's last, cut at 1 of 4
    assert len(stored) == 5 and len(snaps) == 5
    at = {e: i for i, e in enumerate(log)}
    for page in (2, 3, 4):
        cleans = 2 * (page - 2) + 2              # the generated block whose first pass cleans it
        landing = ("land", 2 + cleans // blocks)   # megastep m is dispatch m + 2
        assert at[landing] < at[("stored", page)]
        assert all(at[("land", n)] <= at[landing] or at[("land", n)] > at[("stored", page)]
                   for kind, n in log if kind == "land")
    # final: a fresh engine's wave over prompt + stream writes pages 2-4 as they were published
    fresh = make_core()
    again = fresh.add_request(_req(PROMPT[:16] + out[:24], "w", 1, ignore_eos=True))
    while again.prefilled < 40:
        fresh.step()
    for page in (2, 3, 4):
        for a, b in zip(snaps[page], (np.asarray(l[again.block_ids[page]]) for l in fresh.cache)):
            np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_pending_block_is_fed_on_the_device_and_a_lane_that_moves_gets_its_own(monkeypatch):
    """The pipelined loop stays pipelined: dispatch N + 1 is enqueued before N
    lands, so the host cannot hold the pending tokens when it plans; it names
    the first place of the lane's last block in N's output, by REQUEST. When
    the lane in slot 0 ends, the others move down a slot and are fed the
    blocks of the slots they had; the streams are the sync loop's."""
    prompts = [PROMPT[:30], PROMPT[:17], PROMPT[:64]]
    lengths = [9, 33, 25]
    want, _ = _streams(prompts, lengths, async_exec=False, megastep_k=3)
    core = make_core(async_exec=True, megastep_k=6)
    log = core._exec_log = []
    plans = []
    dispatch = core._dispatch_megastep

    def spy(seqs, n_steps, feed_lanes=None, opens=None):
        plans.append(([s.request_id for s in seqs], list(feed_lanes), len(log),
                      [list(s.pending_block) for s in seqs]))
        return dispatch(seqs, n_steps, feed_lanes=feed_lanes, opens=opens)

    monkeypatch.setattr(core, "_dispatch_megastep", spy)
    seqs = [core.add_request(_req(p, f"s{i}", n, ignore_eos=True))
            for i, (p, n) in enumerate(zip(prompts, lengths))]
    got = run_to_completion(core, seqs)[0]
    assert got == want
    S, B, fed, moved = 4, 4, 0, 0                       # a dispatch's width; two blocks each
    for (before, _, _, _), (lanes, feeds, when, held) in zip(plans, plans[1:]):
        for slot, (rid, feed, pending) in enumerate(zip(lanes, feeds, held)):
            if feed is None:
                continue
            # the step in flight has not landed: the host holds an OLDER block, or none
            landed = [n for kind, n in log[:when] if kind == "land"]
            dispatched = [n for kind, n in log[:when] if kind == "dispatch"]
            assert max(dispatched) not in landed
            assert feed == ((2 - 1) * S + before.index(rid)) * B
            fed += 1
            moved += before.index(rid) != slot
    assert fed > 6 and moved >= 2
    st = core.exec_stats
    assert st["pipelined_dispatches"] > 0 and st["drains"] == 0
    assert st["block_pending_dropped"] == 3
    assert st["block_clean_folded"] == st["blocks_committed"] - 3


# -- the head and the sampler only where a place can still be hidden -----------------

def _plain_megastep_blocks(params, cache, lanes, block_tables, known, *, n_steps, need_mask,
                           all_greedy, want_logprobs, cfg, engine):
    """The block megastep in its plain form, kept here as what
    ``core._megastep_blocks`` is held to: ONE scanned body for every pass,
    the clean pass too, with the head and the sampler on every row of it."""
    B, steps = cfg.block_length, cfg.denoising_steps
    n_blocks, S, K = n_steps // (steps + 1), lanes.shape[0], LOGPROBS_K
    f32 = lambda col: jax.lax.bitcast_convert_type(lanes[:, col], jnp.float32)  # noqa: E731
    position, active = lanes[:, core_mod._L_POSITION], lanes[:, core_mod._L_ACTIVE] != 0
    seeds = jnp.repeat(lanes[:, core_mod._L_SEED], B)
    temperature, top_k, top_p = (
        jnp.repeat(f32(core_mod._L_TEMPERATURE), B), jnp.repeat(lanes[:, core_mod._L_TOP_K], B),
        jnp.repeat(f32(core_mod._L_TOP_P), B))
    watch, min_left = lanes[:, core_mod._L_WATCH:], lanes[:, core_mod._L_MIN_LEFT]
    place = jnp.arange(B, dtype=jnp.int32)

    def one_pass(carry, p):
        toks, hidden, step_of, lp, cache, pos, act, counts = carry
        x, cache = block_hidden(params, cache, jnp.where(hidden, cfg.mask_token_id, toks),
                                block_tables, pos, act, cfg, engine)
        logits = block_logits(params, x, None, cfg)
        counters = ((pos[:, None] + place[None, :]) * steps + p).reshape(-1)
        x0 = sample_seeded(logits, seeds, counters, temperature, top_k, top_p,
                           need_mask=need_mask, all_greedy=all_greedy)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, x0[:, None], axis=-1)[:, 0] - lse
        reveal, by_threshold = unmask_block(
            jnp.exp(chosen).reshape(S, B), hidden, p,
            steps=steps, threshold=cfg.confidence_threshold)
        reveal = reveal & act[:, None] & (p < steps)
        toks = jnp.where(reveal, x0.reshape(S, B), toks)
        step_of = jnp.where(reveal, p, step_of)
        hidden = hidden & ~reveal
        n = jnp.sum(reveal, axis=1)
        counts = counts + jnp.stack([
            jnp.sum(jnp.where(by_threshold, n, 0)),
            jnp.sum(jnp.where(by_threshold, 0, n))]).astype(jnp.int32)
        if want_logprobs:
            top_lps, top_ids = jax.lax.top_k(logits, K)
            new = (chosen.reshape(S, B), top_ids.astype(jnp.int32).reshape(S, B, K),
                   (top_lps - lse[:, None]).reshape(S, B, K))
            lp = tuple(jnp.where(reveal if a.ndim == 2 else reveal[..., None], a, old)
                       for a, old in zip(new, lp))
        return (toks, hidden, step_of, lp, cache, pos, act, counts), None

    def one_block(carry, b):
        cache, pos, alive, budget, floor, counts = carry
        act = active & alive
        opens = (b == 0) & (known >= 0)
        toks = jnp.where(opens, known, 0)
        hidden = ~opens
        lp = (jnp.zeros((S, B), jnp.float32), jnp.zeros((S, B, K), jnp.int32),
              jnp.zeros((S, B, K), jnp.float32)) if want_logprobs else None
        (toks, _, step_of, lp, cache, _, _, counts), _ = jax.lax.scan(
            one_pass,
            (toks, hidden, jnp.full((S, B), -1, jnp.int32), lp, cache, pos, act, counts),
            jnp.arange(steps + 1))
        ordinal = jnp.cumsum(hidden, axis=1) * hidden
        hit = (toks[:, :, None] == watch[:, None, :]).any(axis=2) & hidden & (
            ordinal >= floor[:, None])
        made = jnp.sum(hidden, axis=1)
        budget, floor = budget - made, floor - made
        alive = alive & ~hit.any(axis=1) & (budget > 0)
        pos = pos + B * act.astype(jnp.int32)
        return (cache, pos, alive, budget, floor, counts), (toks, step_of, lp, act)

    (cache, _, _, _, _, counts), (tokens, step_of, lps, ran) = jax.lax.scan(
        one_block,
        (cache, position, jnp.ones_like(active), lanes[:, core_mod._L_BUDGET], min_left,
         jnp.zeros(2, jnp.int32)),
        jnp.arange(n_blocks))
    aux = jnp.concatenate([step_of.reshape(-1), ran.astype(jnp.int32).reshape(-1), counts])
    return tokens, lps, cache, None, aux


def _megastep_inputs(cfg, engine, temperature, masked, seed=0):
    """Eight lanes at three blocks a dispatch: a plain lane, three whose first
    block a prompt's tail opens with 1-3 known places, one that is not live,
    one whose budget ends inside the second block and one that stops on a
    watched id (both dead for the blocks after), one more plain; a cache of
    noise, so that a block's rows read a past."""
    S, B, pages = 8, cfg.block_length, 6
    rs = np.random.RandomState(seed)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    cache = tuple(jnp.asarray(rs.randn(*c.shape), c.dtype) for c in init_cache(cfg, engine))
    tables = jnp.asarray(np.arange(S * pages).reshape(S, pages), jnp.int32)
    watch = np.full((S, core_mod.MEGASTEP_WATCH_W), -1, np.int32)
    watch[6, :] = np.arange(40, 40 + core_mod.MEGASTEP_WATCH_W * 40, 40)   # some id will come
    lanes = core_mod.pack_lanes(
        tokens=np.zeros(S, np.int32), feed_idx=None,
        positions=np.asarray([8, 16, 12, 8, 8, 20, 8, 24], np.int32),
        active=np.asarray([1, 1, 1, 1, 0, 1, 1, 1], np.int32),
        seeds=np.arange(11, 11 + S, dtype=np.int32), counters=np.zeros(S, np.int32),
        temperature=np.full(S, temperature, np.float32),
        top_k=np.full(S, 20 if masked else 0, np.int32),
        top_p=np.full(S, 0.9 if masked else 1.0, np.float32), watch=watch,
        budgets=np.asarray([99, 99, 99, 99, 99, 6, 99, 99], np.int32),
        min_left=np.zeros(S, np.int32))
    known = np.full((S, B), -1, np.int32)
    for lane, tail in ((1, 1), (2, 2), (3, 3)):
        known[lane, :tail] = rs.randint(1, 380, size=tail)
    return params, cache, jnp.asarray(lanes), tables, jnp.asarray(known)


def _folded(known, pending=None):
    """``core._megastep_blocks``'s ``known``: the places a lane's first block opens
    with beside the pending block the host holds for it (none: -1)."""
    none = jnp.full_like(known, -1)
    return jnp.stack([known, none if pending is None else pending], axis=1)


NO_FEED = jnp.zeros(1024, jnp.int32)


def _positions_of(lanes, tables, pages, page, blocks_of):
    """A mask over ``[pages, page]`` of the cache: the places of lane ``i``'s blocks
    ``blocks_of[i]`` (counted from the lane's position), through its table row."""
    B = CFG.block_length
    mask = np.zeros((pages, page), bool)
    for i, blocks in enumerate(blocks_of):
        for b in blocks:
            for pos in range(int(lanes[i, core_mod._L_POSITION]) + b * B,
                             int(lanes[i, core_mod._L_POSITION]) + (b + 1) * B):
                mask[int(tables[i, pos // page]), pos % page] = True
    return mask


@pytest.mark.parametrize("steps,temperature,threshold,want_lp", [
    (steps, temperature, threshold, True)
    for steps in (1, 2, 4) for temperature in (0.0, 0.7) for threshold in (0.9, 0.004)
] + [(2, 0.7, 0.9, False), (4, 0.0, 0.004, False)])
def test_the_megastep_is_bit_equal_to_its_plain_form(steps, temperature, threshold, want_lp):
    """The plain form (a clean pass of its own after every block's denoising
    passes, the head and the sampler on every row of every pass) stays the
    DEFINITION. Held to it over two dispatches, three blocks then one: the
    tokens, the step that revealed each place, the lanes that ran and the two
    reveal counts to the bit; the log-probabilities and the cache to 1e-5,
    because a row's products now run in a batch of ``2 S B`` rows beside its
    lane's pending block where the plain form's ran among ``S B`` (the CPU's
    readings are 0 to a few 1e-7). The cache is the plain form's everywhere
    but in each lane's LAST block run, whose clean rows have yet to ride a
    pass: after the first dispatch that block holds the K/V of a pass with
    places still masked; the second dispatch brings it final, fed from the
    first one's output on the device (lane 7: handed down by the host), and
    leaves its own block pending in turn. A lane the device saw end (5: its
    budget, 6: a watched id) keeps its last block as it was: no pass is spent
    on it. A threshold of 0.004 reveals MORE than the quota (a pass then finds
    fewer hidden places than its slots); seeded lanes ask for top-k / top-p
    where the threshold fires, so both samplers are held."""
    cfg = tiny_sdar(denoising_steps=steps, confidence_threshold=threshold)
    engine = tiny_engine(block_size=8)
    masked = temperature > 0 and threshold < 0.5
    params, cache, lanes, tables, known = _megastep_inputs(cfg, engine, temperature, masked)
    S, B = known.shape
    static = dict(need_mask=masked, all_greedy=temperature == 0, want_logprobs=want_lp,
                  cfg=cfg, engine=engine)
    folded = jax.jit(lambda *a, n: _megastep_blocks(*a, n_steps=n, **static),
                     static_argnames="n")
    plain = jax.jit(lambda *a, n: _plain_megastep_blocks(*a, n_steps=n, **static),
                    static_argnames="n")
    n1 = 3 * (steps + 1)
    got = folded(params, cache, lanes, tables, NO_FEED, _folded(known), n=n1)
    want = plain(params, cache, lanes, tables, known, n=n1)
    aux = np.asarray(want[4])
    ran = aux[3 * S * B: -2].reshape(3, S)
    assert ran[:, 4].sum() == 0 and ran[:, 0].all()            # the idle lane; a plain one
    assert ran[:, 5].tolist() == [1, 1, 0]                       # the budget ended in block 2
    assert (aux[-2] > 0) == (threshold < 0.5) and aux[-2] + aux[-1] > 0
    if threshold < 0.5 and steps > 1:     # some first pass revealed MORE than its quota
        first = (aux[: 3 * S * B].reshape(3, S, B) == 0).sum(axis=2)
        assert first.max() > hidden_at_most(B, steps)[0] - hidden_at_most(B, steps)[1]

    def same(got, want, pending):
        """Tokens and aux to the bit, log-probabilities and every page but the
        garbage page (where dead lanes' rows collide) and the ``pending`` places."""
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))
        if want_lp:
            for a, b in zip(got[1], want[1]):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        else:
            assert got[1] is None and want[1] is None
        for a, b in zip(got[2], want[2]):
            np.testing.assert_allclose(np.asarray(a)[~pending], np.asarray(b)[~pending], atol=1e-5)

    lanes_np, page, pages = np.asarray(lanes), engine.block_size, cache[0].shape[0]
    last = [[int(ran[:, i].sum()) - 1] if ran[:, i].any() else [] for i in range(S)]
    pending = _positions_of(lanes_np, tables, pages, page, last)
    pending[-1] = True
    same(got, want, pending)
    if threshold > 0.5:       # a plain lane's last block is NOT final yet: its last pass saw masks
        mine = _positions_of(lanes_np, tables, pages, page, [[2]] + [[]] * (S - 1))
        assert float(np.abs(np.asarray(got[2][0])[mine] - np.asarray(want[2][0])[mine]).max()) > 1e-3
    # the sparse layers' counts are summed over every pass: ``steps`` a block
    assert got[3].shape == (5,) and int(got[3][1]) == 3 * steps * cfg.num_layers

    # the second dispatch, one block: lanes 0-3 fed from the first one's output on the
    # device, lane 7 handed its block by the host; 4-6 are over
    alive = np.asarray([1, 1, 1, 1, 0, 0, 0, 1], np.int32)
    again = lanes_np.copy()
    again[:, core_mod._L_POSITION] += 3 * B
    again[:, core_mod._L_ACTIVE] = alive
    plain_lanes = jnp.asarray(again)
    again[:, core_mod._L_FEED] = np.where(alive == 1, (2 * S + np.arange(S)) * B, -1)
    again[7, core_mod._L_FEED] = -1
    from_host = np.full((S, B), -1, np.int32)
    from_host[7] = np.asarray(got[0])[2, 7]
    nothing = jnp.full((S, B), -1, jnp.int32)
    feed = jnp.pad(got[0].reshape(-1), (0, NO_FEED.shape[0] - got[0].size))
    got2 = folded(params, got[2], jnp.asarray(again), tables, feed,
                  _folded(nothing, jnp.asarray(from_host)), n=steps + 1)
    want2 = plain(params, want[2], plain_lanes, tables, nothing, n=steps + 1)
    assert np.asarray(want2[4])[S * B: -2].tolist() == alive.tolist()
    pending = _positions_of(lanes_np, tables, pages, page, [[3] if alive[i] else last[i] for i in range(S)])
    pending[-1] = True
    same(got2, want2, pending)


@pytest.mark.parametrize("B,steps,want", [
    (4, 2, (4, 2, 0)), (4, 4, (4, 3, 2, 1, 0)), (4, 1, (4, 0)), (4, 3, (4, 2, 1, 0)),
    (8, 3, (8, 5, 2, 0)), (8, 8, (8, 7, 6, 5, 4, 3, 2, 1, 0))])
def test_the_bound_on_hidden_places_is_unmask_blocks_quota(B, steps, want):
    """``hidden_at_most`` against what ``unmask_block`` leaves hidden where
    the threshold never fires: step by step the same counts, and a lane that
    opened with known places stays under them."""
    assert hidden_at_most(B, steps) == want
    rs = np.random.RandomState(B * 10 + steps)
    hidden = jnp.asarray(np.stack([np.ones(B, bool), np.arange(B) >= 1, np.arange(B) >= B - 1]))
    for p in range(steps):
        assert int(hidden[0].sum()) == want[p]
        assert all(int(n) <= want[p] for n in hidden.sum(axis=1))
        reveal, _ = unmask_block(jnp.asarray(rs.rand(3, B), jnp.float32), hidden, jnp.int32(p),
                                 steps=steps, threshold=2.0)
        again, _ = unmask_block(jnp.asarray(rs.rand(3, B), jnp.float32), hidden, p,
                                steps=steps, threshold=2.0)     # a step known at trace time
        assert reveal.sum(axis=1).tolist() == again.sum(axis=1).tolist()
        hidden = hidden & ~reveal
    assert not bool(hidden.any())


def _head_products(jaxpr, vocab: int) -> list[int]:
    """The row count of each product whose result is ``vocab`` wide, sub-programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape[-1] == vocab:
            found.append(int(np.prod(eqn.outvars[0].aval.shape[:-1])))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _head_products(sub, vocab)
    return found


def _switches(jaxpr, branches: int) -> list:
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and len(eqn.params["branches"]) == branches:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _switches(sub, branches)
    return found


def _count(jaxpr, primitive: str) -> int:
    """Equations of that primitive, sub-programs included."""
    return sum((eqn.primitive.name == primitive)
               + sum(_count(sub, primitive) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_pass_has_a_head_of_its_hidden_places_and_the_clean_pass_none(steps):
    """The one product with the vocabulary a pass has lies in the branch the
    pass's number picks: ``S x H_p`` rows of the CURRENT half in pass ``p``
    and none outside the switch; the clean rows (the pending half of a
    block's first pass) go through no head, and there is no clean pass."""
    cfg = tiny_sdar(denoising_steps=steps)
    engine = tiny_engine(block_size=8)
    *args, known = _megastep_inputs(cfg, engine, 0.7, False)
    S = args[2].shape[0]
    jaxpr = jax.make_jaxpr(lambda *a: _megastep_blocks(
        *a, n_steps=2 * (steps + 1), need_mask=False, all_greedy=False, want_logprobs=True,
        cfg=cfg, engine=engine))(*args, NO_FEED, _folded(known)).jaxpr
    want = [[S * H] for H in hidden_at_most(cfg.block_length, steps)[:steps]]
    if steps == 1:     # one pass a block: no switch, the head as it lies
        assert not _switches(jaxpr, 2) and _head_products(jaxpr, cfg.vocab_size) == want[0]
        return
    (switch,) = _switches(jaxpr, steps)
    by_pass = [_head_products(branch.jaxpr, cfg.vocab_size) for branch in switch.params["branches"]]
    assert by_pass == want
    assert sorted(_head_products(jaxpr, cfg.vocab_size)) == sorted(sum(by_pass, []))


@pytest.mark.parametrize("steps", [2, 4])
def test_the_block_megastep_holds_one_stack(steps):
    """Every pass of every block is the ONE scanned body: a layer's two
    grouped products (gate/up, down) appear ``num_layers`` times in the
    whole program, at the one shape ``[S current blocks | S pending blocks]``,
    whatever the blocks a dispatch and the steps a block; and nothing in the
    compiled text is a rematerialised copy (on the v5e, written-out passes
    cost a head computed once a copy and 15 s of set-up: PERF.md, PR 43)."""
    cfg = tiny_sdar(denoising_steps=steps)
    engine = tiny_engine(block_size=8, num_kv_blocks=640)
    S, B = 64, cfg.block_length                  # 2 S B = 512 rows: a wave's grouped product
    shapes = (
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)),
        jax.eval_shape(lambda: init_cache(cfg, engine)),
        jax.ShapeDtypeStruct((S, core_mod.LANE_COLS), jnp.int32),
        jax.ShapeDtypeStruct((S, 10), jnp.int32),
        jax.ShapeDtypeStruct((2 * S * B,), jnp.int32),
        jax.ShapeDtypeStruct((S, 2, B), jnp.int32))
    program = jax.jit(lambda *a: _megastep_blocks(
        *a, n_steps=2 * (steps + 1), need_mask=False, all_greedy=False, want_logprobs=False,
        cfg=cfg, engine=engine))
    jaxpr = jax.make_jaxpr(program)(*shapes).jaxpr
    assert _count(jaxpr, "ragged_dot_general") == 2 * cfg.num_layers
    assert _count(jaxpr, "scan") == 2              # the blocks, and a block's passes
    text = program.lower(*shapes).compile().as_text()
    assert ".remat" not in text


# what the parent commit (PR 42) served, the engine of ``make_core()``, PROMPT[:30], 21 tokens
PARENT_GREEDY = [178, 241, 332, 244, 244, 30, 132, 241, 241, 178, 175, 241, 232, 168, 232, 283, 283,
                 293, 241, 241, 156]
PARENT_SEEDED = [241, 125, 241, 244, 150, 381, 246, 144, 381, 283, 343, 155, 233, 232, 306, 283, 175,
                 293, 125, 241, 109]


@pytest.mark.parametrize("sampling,want", [
    (dict(temperature=0.0), PARENT_GREEDY),
    (dict(temperature=0.8, top_k=20, seed=7), PARENT_SEEDED)], ids=["greedy", "seeded"])
def test_served_tokens_are_the_parent_commits(sampling, want):
    core = make_core()
    seq = core.add_request(PreprocessedRequest(
        model="m", token_ids=PROMPT[:30], request_id="t", sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=21, ignore_eos=True), output=OutputOptions()))
    assert run_to_completion(core, [seq])[0]["t"] == want


def test_the_rows_that_went_through_the_head_are_counted():
    from chipbench.readers import prometheus
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters

    for steps in (2, 4):
        core = make_core(file_with(steps))
        seq = core.add_request(_req(PROMPT[:34], "c", 25, ignore_eos=True))
        run_to_completion(core, [seq])
        st = core.exec_stats
        B = core.cfg.block_length
        # every block's clean rows rode the next block's first pass, but the last one's
        assert st["block_pending_dropped"] == 1
        assert st["denoise_forwards"] == steps * (st["commit_forwards"] + 1) > 0
        assert st["block_rows"] == (st["denoise_forwards"] + st["commit_forwards"]) * B
        assert st["head_rows"] * steps == st["denoise_forwards"] * sum(hidden_at_most(B, steps))
        assert st["head_rows"] * 2 == st["block_rows"] + B      # 6 of 12 rows; 10 of 20
    registry = MetricsRegistry()
    registry.registry.register(_EngineCounters(core.step_phase_seconds, core.scheduler_stats))
    text = registry.render().decode()
    assert prometheus.total([text], "dynamo_engine_block_head_rows_total") == st["head_rows"]
    assert prometheus.total([text], "dynamo_engine_block_rows_total") == st["block_rows"]
    # the two counters of the fold, and the passes a live lane ran: every pass a denoising one
    assert prometheus.total([text], "dynamo_engine_block_clean_folded_total") == \
        st["block_clean_folded"] == st["blocks_committed"] - 1 > 0
    assert prometheus.total([text], "dynamo_engine_block_pending_dropped_total") == 1
    assert prometheus.total([text], "dynamo_engine_denoise_forwards_total") == st["denoise_forwards"]
    assert prometheus.total([text], "dynamo_engine_denoise_forwards_total", {"pass": "commit"}) is None


# -- the pieces -------------------------------------------------------------------

def test_unmask_block_reveals_by_threshold_or_by_quota():
    conf = jnp.asarray([[0.2, 0.95, 0.93, 0.1],     # two over: by threshold (quota 1)
                        [0.3, 0.3, 0.2, 0.91],      # one over, quota 1: that one
                        [0.3, 0.5, 0.5, 0.1],       # none over: the surest, ties to the lower
                        [0.99, 0.2, 0.1, 0.3]])     # the surest is not hidden
    hidden = jnp.asarray([[True] * 4, [True] * 4, [True] * 4, [False, True, True, True]])
    reveal, by_threshold = unmask_block(conf, hidden, jnp.int32(0), steps=4, threshold=0.9)
    assert reveal.tolist() == [[False, True, True, False], [False, False, False, True],
                               [False, True, False, False], [False, False, False, True]]
    assert by_threshold.tolist() == [True, True, False, False]
    # 3 steps of 4 places: quotas 2, 1, 1
    for step, quota in ((0, 2), (1, 1), (2, 1)):
        reveal, _ = unmask_block(conf[2:3], hidden[2:3], jnp.int32(step), steps=3, threshold=2.0)
        assert int(reveal.sum()) == quota


def test_the_fold_is_the_block_mask():
    """block_attention's one decode-shaped call against the plain reference
    call a ROW, each row told its block's end: the same numbers."""
    rs = np.random.RandomState(3)
    B, n_q, n_kv, d, page = 4, 4, 2, 16, 8
    kv_pages = jnp.asarray(rs.randn(6, page, 2 * n_kv, d), jnp.float32)
    q = jnp.asarray(rs.randn(3 * B, n_q, d), jnp.float32)
    ends = jnp.asarray([8, 12, 4], jnp.int32)        # three blocks of two sequences
    tables = jnp.asarray([[0, 1, 5], [0, 1, 5], [2, 3, 5]], jnp.int32)
    got = block_attention(q, kv_pages, ends, tables, jnp.asarray([3], jnp.int32),
                          block_length=B, sm_scale=0.25, shape="block-ragged")
    rows = ragged_paged_attention_ref(
        q, kv_pages, jnp.repeat(ends, B), jnp.repeat(tables, B, axis=0), None,
        jnp.asarray([3 * B], jnp.int32), sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rows), atol=1e-5)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shares_of_the_dropless_layer_add_up_under_either_scoring(scoring):
    """Four chips' shares of 8 experts (each computed with the share's own
    parameters) add up to the uncut layer, and that to the plain sum over the
    chosen experts: the softmax shares too."""
    whole = tiny_sdar(router_scoring=scoring, block_length=0, denoising_steps=0,
                      confidence_threshold=1.0, mask_token_id=0)
    params = init_params(jax.random.PRNGKey(5), whole)
    lp = model_mod.layer_params(params, 1, whole)
    y = jnp.asarray(np.random.RandomState(1).randn(21, 64), jnp.float32)
    im = whole.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        if scoring == "softmax":
            w = reference.routing_weights(y, lp["w_router"], top_k=2)
        else:
            from chipbench.reference import lfm2_moe

            w = lfm2_moe.routing_weights(y, lp["w_router"], jnp.zeros(8), top_k=2, scale=1.0,
                                         norm_eps=whole.router_norm_eps)
        assert int((w > 0).sum()) == 21 * 2
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
        want = sum(w[:, e, None] * reference.mlp_block(
            y, lp["w_gu"][e][:, :im], lp["w_gu"][e][:, im:], lp["w_down"][e]) for e in range(8))
        uncut = model_mod._shared_sparse_mlp(y, lp, whole)
        assert float(jnp.abs(uncut - want).max()) < TIGHT
        total = 0
        for rank in range(4):
            cfg = dataclasses.replace(whole, experts_held=(rank, 4))
            lp_r = model_mod.layer_params(init_params(jax.random.PRNGKey(5), cfg), 1, cfg)
            assert lp_r["w_gu"].shape[0] == 2
            part = model_mod._shared_sparse_mlp(y, lp_r, cfg)
            assert float(jnp.abs(part).max()) > 1e-4      # the share adds something
            total = total + part
        assert float(jnp.abs(total - want).max()) < TIGHT


def test_mixtral_keeps_its_path_and_its_numbers():
    """The capacity-bounded layer is chosen by the layout the tree has, as
    before: a softmax-scored model WITHOUT moe_intermediate_size goes through
    _moe_mlp, token for token what its own reference gives."""
    from chipbench.reference import mixtral as mixtral_reference  # noqa: F401

    cfg = tiny_moe()
    params = init_params(jax.random.PRNGKey(2), cfg)
    lp = model_mod.layer_params(params, 0, cfg)
    assert "w_gate" in lp and "w_gu" not in lp
    y = jnp.asarray(np.random.RandomState(2).randn(5, 64), jnp.float32)
    np.testing.assert_array_equal(np.asarray(model_mod._mlp(y, lp, cfg, 1)),
                                  np.asarray(model_mod._moe_mlp(y, lp, cfg, None)))
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        dataclasses.replace(cfg, router_scoring="sigmoid")


# -- refused by name ----------------------------------------------------------------

@pytest.mark.parametrize("option,engine", [
    ("spec_decode", {"spec_decode": "ngram"}),
    ("scheduling", {"scheduling": "chunked"}),
    ("kv_dtype", {"kv_dtype": "int8"}),
])
def test_an_option_the_block_step_does_not_carry_is_refused_at_start_up(option, engine):
    with pytest.raises(UnsupportedModelOption) as e:
        EngineCore(CFG, tiny_engine(**engine), seed=0)
    assert e.value.option == option and "tiny-sdar" in str(e.value)


def test_meshes_quantised_weights_and_misfit_sizes_are_refused():
    from dynamo_tpu.backends.jax.main import build_engine

    with pytest.raises(UnsupportedModelOption, match="tp"):
        EngineCore(CFG, tiny_engine(), seed=0, mesh=object())
    with pytest.raises(UnsupportedModelOption, match="pp"):
        EngineCore(CFG, tiny_engine(), seed=0, pp_mesh=object())
    with pytest.raises(NotImplementedError, match="tiny-sdar"):
        build_engine("tiny-sdar", {"num_kv_blocks": 16, "block_size": 8}, quant="int8")
    with pytest.raises(ValueError, match="whole blocks"):
        EngineCore(CFG, tiny_engine(block_size=6, prefill_buckets=(24,)), seed=0)
    with pytest.raises(ValueError, match="denoising_steps"):
        tiny_sdar(denoising_steps=5)
    with pytest.raises(ValueError, match="only a model that generates by blocks"):
        dataclasses.replace(tiny_lfm2(), denoising_steps=2)
    with pytest.raises(ValueError, match="block_length"):
        dataclasses.replace(CFG, block_length=0)
    with pytest.raises(NotImplementedError):
        dataclasses.replace(tiny_lfm2(), block_length=4, denoising_steps=2)


@pytest.mark.parametrize("option,sampling", [
    ("frequency_penalty", {"frequency_penalty": 0.5}),
    ("presence_penalty", {"presence_penalty": 0.5}),
    ("repetition_penalty", {"repetition_penalty": 1.2}),
    ("n", {"n": 2}),
])
def test_a_request_for_left_to_right_sampling_is_refused_by_name(option, sampling):
    core = make_core()
    with pytest.raises(ValueError, match=option):
        core.add_request(PreprocessedRequest(
            model="m", token_ids=PROMPT[:8], request_id="r",
            sampling=SamplingOptions(temperature=0.0, **sampling),
            stop=StopConditions(max_tokens=4), output=OutputOptions()))
    assert not core.has_work()


def test_resolved_schedule_and_what_the_worker_reports():
    eng = _resolve_block_megastep(tiny_sdar(denoising_steps=4), tiny_engine(block_size=8))
    assert eng.megastep == 5                                   # one block of 5 passes
    eng = _resolve_block_megastep(CFG, tiny_engine(block_size=8))
    assert CFG.denoising_steps == 2 and eng.megastep == 6      # two blocks of 3 passes
    core = make_core()
    stats = core.scheduler_stats()
    assert stats["block_length"] == 4 and stats["denoising_steps"] == 2
    assert stats["megastep_k"] == 6


def test_counters_of_a_run():
    from dynamo_tpu.ops.ragged_attention import traced_calls

    core = make_core()
    seq = core.add_request(_req(PROMPT[:34], "c", 25, ignore_eos=True))
    run_to_completion(core, [seq])
    st = core.exec_stats
    # 34 = 8 whole blocks + a tail of 2; 25 tokens: 2 + 5 whole blocks + 3 of 4
    assert st["blocks_committed"] == 7
    assert st["places_revealed_quota"] + st["places_revealed_threshold"] >= 26
    assert st["denoise_forwards"] == 2 * 7 and st["commit_forwards"] == st["block_clean_folded"] == 6
    assert st["block_pending_dropped"] == 1
    assert st["committed_tokens"] == 25 and st["block_places_discarded"] >= 1
    traced = traced_calls()
    assert traced.get(("block-decode", "reference"), 0) > 0
    assert traced.get(("block-ragged", "reference"), 0) > 0
    assert not core.running and core.allocator.free_blocks > 0


# -- a checkpoint under the base family's names ---------------------------------------

def test_loads_a_checkpoint_under_the_base_familys_names(tmp_path):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    rng = np.random.RandomState(0)
    h, d, E, im, v = 64, 16, 8, 32, 384
    mat = lambda o, i: (rng.randn(o, i) * i ** -0.5).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": np.ones(h, np.float32),
          "lm_head.weight": mat(v, h)}
    for l in range(2):
        p = f"model.layers.{l}."
        sd[p + "input_layernorm.weight"] = (1 + 0.1 * rng.randn(h)).astype(np.float32)
        sd[p + "post_attention_layernorm.weight"] = (1 + 0.1 * rng.randn(h)).astype(np.float32)
        for name, out in (("q_proj", 4 * d), ("k_proj", 2 * d), ("v_proj", 2 * d)):
            sd[p + f"self_attn.{name}.weight"] = mat(out, h)
        sd[p + "self_attn.o_proj.weight"] = mat(h, 4 * d)
        sd[p + "self_attn.q_norm.weight"] = (1 + 0.1 * rng.randn(d)).astype(np.float32)
        sd[p + "self_attn.k_norm.weight"] = (1 + 0.1 * rng.randn(d)).astype(np.float32)
        sd[p + "mlp.gate.weight"] = mat(E, h)
        for e in range(E):
            sd[p + f"mlp.experts.{e}.gate_proj.weight"] = mat(im, h)
            sd[p + f"mlp.experts.{e}.up_proj.weight"] = mat(im, h)
            sd[p + f"mlp.experts.{e}.down_proj.weight"] = mat(h, im) / 4
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items()
          if k not in ("name", "torch_dtype", "serve", "source", "deployment", "reduced",
                       "assumed", "probe")}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32)
    assert cfg == dataclasses.replace(CFG, name="sdar_moe", dtype="bfloat16")
    assert [a.shape for a in loaded["moe"]["w_gu"]] == [(E, h, 2 * im)] * 2
    np.testing.assert_array_equal(loaded["moe"]["w_gu"][1][5, :, im:],
                                  sd["model.layers.1.mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(loaded["layers"]["k_layernorm"][1],
                                  sd["model.layers.1.self_attn.k_norm.weight"])
    np.testing.assert_array_equal(loaded["layers"]["wqkv"][0][:, 4 * d: 6 * d],
                                  sd["model.layers.0.self_attn.k_proj.weight"].T)
    # the loaded tree serves, and the reference reads it as it reads a drawn one
    served = EngineCore(dataclasses.replace(cfg, dtype="float32"),
                        EngineConfig(**engine_overrides(FILE)),
                        params=jax.tree.map(jnp.asarray, loaded))
    verdict, _ = held_to_reference(served, FILE, {"prompt_ids": PROMPT[:30], "max_tokens": 9,
                                                  "top": 5})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT


def test_architectures_knows_the_module_and_its_optional_member():
    assert "sdar_moe" in architectures.known()
    assert architectures.of(FILE) is arch and callable(arch.score_probe)
