"""Laguna's two pools under pressure (split off ``tests/test_laguna.py`` in
PR 45 so that six workers balance: ROADMAP D17; the configuration, the
helpers and the reference are that file's): a wave cuts a prompt inside a
block and inside a window; window blocks are released and handed to another
lane and both streams stay sound; either pool running out preempts, and the
resumed stream is the unpressed one and recomputes what it gave back; a lane
holds its window and no more, while it decodes and between its waves; a
window lane is itself in a plan; embeddings run both kinds of layer."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import check
from dynamo_tpu.engine import tiny_engine
from dynamo_tpu.engine import model as model_mod
from tests.test_engine_core import _req, run_to_completion
from tests.test_laguna import BLOCK, CFG, FILE, PROMPT, TIGHT, WINDOW, make_core


def _probes(core, prompts, budgets):
    """Several greedy requests with log-probabilities at once, as
    ``check.run_probe`` sends one; ``{request id: probe}``."""
    from dynamo_tpu.llm.protocols.common import (
        OutputOptions,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    seqs = [core.add_request(PreprocessedRequest(
        model="probe", token_ids=list(p), request_id=f"p{i}",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=m, ignore_eos=True),
        output=OutputOptions(logprobs=5))) for i, (p, m) in enumerate(zip(prompts, budgets))]
    out = {s.request_id: {"tokens": [], "entries": []} for s in seqs}
    for _ in range(4000):
        for s, o in core.step():
            out[s.request_id]["tokens"] += list(o.token_ids)
            out[s.request_id]["entries"] += list(o.logprobs or [])
        if all(s.finish is not None for s in seqs):
            break
    return {rid: {"tokens": v["tokens"],
                  "top_ids": [[t for t, _ in e["top"]] for e in v["entries"]],
                  "top_lps": [[lp for _, lp in e["top"]] for e in v["entries"]]}
            for rid, v in out.items()}


def _held(core, prompts, probes):
    verdicts = []
    for i, p in enumerate(prompts):
        probe = probes[f"p{i}"]
        scored = check.score_probe(FILE, core.params, list(p), probe)
        verdicts.append(check.compare([probe], {"sequences": [scored]}))
    return verdicts


def test_a_wave_cuts_a_prompt_inside_a_block_and_inside_a_window():
    """Three prompts in waves of 32 tokens: the wave's budget ends a prompt's
    chunk at 32 - 21 = 11 and then 32 - 10 - 19 = 3 tokens, inside a block
    of 4 and a window of 8; the next wave goes on from there."""
    core = make_core(prefill_buckets=(16, 32))
    prompts = [PROMPT[:21], PROMPT[30:51], PROMPT[60:79]]
    probes = _probes(core, prompts, [17, 9, 25])
    for verdict in _held(core, prompts, probes):
        assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert core.scheduler_stats()["window_blocks_in_use"] == 0


def test_released_window_blocks_are_another_lanes_and_both_streams_are_sound():
    """The smallest window pool the engine accepts: four lanes of 60 to 90
    positions each pass several times the pool through their windows, so
    every block is handed from lane to lane, and from a lane to itself."""
    eng = tiny_engine(block_size=BLOCK, prefill_buckets=(16, 32), megastep_k=4)
    least = eng.window_table_blocks(WINDOW) + eng.window_span_blocks(WINDOW, 4)
    with pytest.raises(ValueError, match="num_window_blocks"):
        make_core(prefill_buckets=(16, 32), megastep_k=4, num_window_blocks=least - 1)
    core = make_core(prefill_buckets=(16, 32), megastep_k=4, num_window_blocks=least)
    prompts = [PROMPT[:40], PROMPT[10:67], PROMPT[33:60], PROMPT[5:50]]
    probes = _probes(core, prompts, [33, 25, 41, 17])
    for verdict in _held(core, prompts, probes):
        assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    st = core.scheduler_stats()
    assert st["window_blocks"] == least and st["window_blocks_in_use"] == 0
    assert st["window_blocks_released"] > 3 * least


def _streams(prompts, max_tokens, **engine):
    core = make_core(**engine)
    seqs = [core.add_request(_req(p, f"s{i}", max_tokens=m, ignore_eos=True))
            for i, (p, m) in enumerate(zip(prompts, max_tokens))]
    done, _ = run_to_completion(core, seqs, max_steps=4000)
    return done, core


@pytest.mark.parametrize("pool", ["full", "window"])
def test_either_pool_running_out_preempts_and_the_resumed_stream_is_the_unpressed(pool):
    # five lanes: 13 blocks each of the full pool; of the window pool's a decode span of
    # 5 (8 keys and 8 queries from position 17, 25, ...: they start a block's second token)
    prompts = [PROMPT[17 * i:17 * i + 17] for i in range(5)]
    roomy, _ = _streams(prompts, [33] * 5, num_kv_blocks=80, max_model_len=64)
    tight = {"full": {"num_kv_blocks": 40}, "window": {"num_window_blocks": 18}}[pool]
    pressed, core = _streams(prompts, [33] * 5, **{
        "num_kv_blocks": 80, "max_model_len": 64, "prefill_buckets": (16, 32), **tight})
    assert core.sched_stats["preemptions"] >= 1
    assert pressed == roomy and all(len(v) == 33 for v in pressed.values())
    assert core.window_allocator.used_blocks == 0 == core.allocator.used_blocks


def test_a_resumed_stream_recomputes_what_it_gave_back():
    """Preempted by hand with room to spare: nothing of it is found again
    (its window blocks were given back, and no block is a hit), every row is
    recomputed through both pools, and the stream is the undisturbed one."""
    want = _streams([PROMPT[:21]], [30], async_exec=False)[0]["s0"]
    core = make_core(async_exec=False)
    seq = core.add_request(_req(PROMPT[:21], "s0", max_tokens=30, ignore_eos=True))
    got = []
    while seq.generated < 17:
        for _, out in core.step():
            got += list(out.token_ids)
    held = len(seq.win_ids)
    with core._step_lock:
        core._preempt(seq)
    assert held >= WINDOW // BLOCK and seq.win_ids == [] and seq.win_first == 0
    assert core.window_allocator.used_blocks == 0
    done, _ = run_to_completion(core, [seq])
    assert got + done["s0"] == want and core.sched_stats["preemptions"] == 1
    assert seq.num_cached_tokens == 0


def test_a_lane_holds_its_window_and_no_more_while_it_decodes():
    core = make_core(async_exec=False, megastep_k=1)
    seq = core.add_request(_req(PROMPT[:50], "s0", max_tokens=40, ignore_eos=True))
    most = 0
    while seq.finish is None:
        core.step()
        if seq.generated >= 2 and seq.finish is None:
            most = max(most, len(seq.win_ids))
            query = seq.processed - 1       # the position the step just run attended from
            assert seq.win_first == max(0, query - WINDOW + 1) // BLOCK
            assert seq.win_first + len(seq.win_ids) == query // BLOCK + 1
    # 8 keys and 1 query span at most 3 blocks of 4, wherever they start
    assert most == core.engine.window_span_blocks(WINDOW, 1) == WINDOW // BLOCK + 1


def test_a_prompt_between_its_waves_holds_its_window_and_not_its_chunk():
    """Eight prompts of three waves each arrive together and prefill keeps
    its priority, so the first waits seven prompts long for its first decode
    step: it holds the blocks a later query sees (a window's span), given
    back as soon as its wave is dispatched, not its last chunk's too; no
    lane is preempted for the pool, and every stream is the lone stream's."""
    engine = dict(max_num_seqs=8, prefill_buckets=(16, 32, 64), decode_buckets=(8,))
    rs = np.random.RandomState(7)
    prompts = [[int(t) for t in rs.randint(1, 380, size=150)] for _ in range(8)]
    core = make_core(**engine)
    seqs = [core.add_request(_req(p, f"s{i}", max_tokens=12, ignore_eos=True))
            for i, p in enumerate(prompts)]
    span = core.engine.window_span_blocks(WINDOW, core.engine.megastep_k)
    got: dict[str, list[int]] = {s.request_id: [] for s in seqs}
    most = 0
    while any(s.finish is None for s in seqs):
        for seq, out in core.step():
            got[seq.request_id].extend(out.token_ids)
        held = [len(s.win_ids) for s in seqs if s.finish is None]
        assert all(n <= span for n in held), held
        most = max(most, core.window_allocator.used_blocks)
    assert most <= 8 * span and core.sched_stats["preemptions"] == 0
    lone = make_core(**engine)
    alone = lone.add_request(_req(prompts[3], "s3", max_tokens=12, ignore_eos=True))
    done, _ = run_to_completion(lone, [alone])
    assert got["s3"] == done["s3"] and len(done["s3"]) == 12


def test_a_window_lane_is_itself_and_a_plan_compares_none():
    """``Sequence`` compares by identity (PR 40): two lanes whose fields
    are equal, window blocks and all, are two lanes, and planning,
    dispatching and committing 128 decoding lanes calls no ``__eq__`` of
    it (the generated one built a tuple of 29 fields for every lane a
    ``seq in ready`` passed: +11 ms of ``plan`` a dispatch when the
    window's two fields joined it, PR 39)."""
    from dynamo_tpu.engine.core import Sequence
    from dynamo_tpu.llm.protocols.common import SamplingOptions, StopConditions

    fields = dict(request_id="a", prompt=[1, 2], sampling=SamplingOptions(),
                  stop=StopConditions(max_tokens=2), seed=1, win_first=2, win_ids=[5, 6])
    a, b = Sequence(**fields), Sequence(**fields)
    assert a != b and a == a and [a, b].index(b) == 1
    assert "__eq__" not in vars(Sequence)
    core = make_core(max_num_seqs=128, decode_buckets=(128,), max_model_len=64,
                     num_kv_blocks=128 * 16 + 8, num_window_blocks=128 * 6 + 32)
    seqs = [core.add_request(_req(PROMPT[i % 9: i % 9 + 10], f"s{i}", max_tokens=40,
                                  ignore_eos=True)) for i in range(128)]
    while core.exec_stats["megastep_dispatches"] < 2:
        core.step()
    calls = []
    Sequence.__eq__ = lambda x, y: calls.append(1) or x is y
    try:
        for _ in range(2):
            core.step()
    finally:
        del Sequence.__eq__
    assert len(core.running) == 128 and all(s.finish is None for s in seqs)
    assert not calls


def test_embeddings_run_both_kinds_of_layer():
    core = make_core()
    ids = PROMPT[:37]
    got = core.embed(ids)
    params, eng = core.params, core.engine
    scratch = dataclasses.replace(eng, num_kv_blocks=32, num_window_blocks=32, max_model_len=128)
    cache = model_mod.cache_for_blocks(CFG, scratch, 32)
    T = 64
    tokens = np.zeros(T, np.int32)
    tokens[:37] = ids
    pos = jnp.arange(T, dtype=jnp.int32)
    tables = np.arange(32, dtype=np.int32)[None]
    packed = np.concatenate([tables, np.zeros((1, 1), np.int32), tables], axis=1)
    x, _ = model_mod.forward_hidden(
        params, cache, jnp.asarray(tokens), pos,
        jnp.where(pos < 37, pos // BLOCK, 32), pos % BLOCK, jnp.asarray([T], jnp.int32),
        jnp.asarray(packed), jnp.asarray([0, T], jnp.int32), jnp.asarray([1], jnp.int32),
        CFG, scratch)
    want = np.asarray(x[:37], np.float32).mean(axis=0)
    assert float(np.abs(got - want).max()) < TIGHT
