"""SDAR's step (``ModelConfig.block_length > 0``): a megastep that denoises a
block of places a lane and commits 0..B tokens. The engine is held to the
plain reference (``chipbench/reference/sdar_moe.py``) by the architecture's
own ``score_probe`` at ``TIGHT`` (float32 on both sides) over schedules,
thresholds, prompt tails, cuts, loops, preemption and prefix hits, and a
block's clean rows ride the next block's first pass. The megastep as a
program, the pieces, the refusals and the checkpoint are in
``tests/test_sdar_megastep.py`` (split off in PR 45: ROADMAP D17).
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import sdar_moe as arch
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from dynamo_tpu.engine import PRESETS, EngineConfig, ModelConfig
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import mixtral_8x7b, sdar_30b_a3b_6l, tiny_lfm2, tiny_moe, tiny_sdar
from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.engine.model import init_params
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

CFG = tiny_sdar()
FILE = load_config("tiny-sdar-rehearsal")
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=120)]
TIGHT = 1e-4   # float32 on both sides: the readings are 1e-6
# what the parent commit (PR 42) served, the engine of ``make_core()``, PROMPT[:30], 21 tokens
PARENT_GREEDY = [178, 241, 332, 244, 244, 30, 132, 241, 241, 178, 175, 241, 232, 168, 232, 283, 283,
                 293, 241, 241, 156]
PARENT_SEEDED = [241, 125, 241, 244, 150, 381, 246, 144, 381, 283, 343, 155, 233, 232, 306, 283, 175,
                 293, 125, 241, 109]


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
