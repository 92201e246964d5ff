"""A model of gated-delta-rule layers among multi-head attention without rope
(``tiny-olmo-hybrid``: ``model.linear_layer``, a slab a lane beside the
paged K/V): the engine against the plain reference
(chipbench/reference/olmo_hybrid.py, token by token, float32) through every
step shape, the slab's rules (a slot a sequence, zeros at position 0, the
garbage slot, the invariant), preemption by replay, refusals, counts and
the checkpoint's names. The mixer alone: tests/test_linear_attention.py."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import olmo_hybrid as arch
from chipbench.configs import load_config, model_fields
from chipbench.reference import check
from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    PRESETS,
    UnsupportedModelOption,
    olmo_hybrid_7b_pp2_16l,
    tiny_model,
    tiny_olmo_hybrid,
)
from dynamo_tpu.engine.model import forward_hidden, init_cache, init_params
from dynamo_tpu.ops import linear_attention, ragged_attention
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_olmo_hybrid()
FILE = load_config("tiny-olmo-hybrid-rehearsal")
MF = model_fields(FILE)
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=80)]
BODY = {"prompt_ids": PROMPT[:70], "max_tokens": 17, "top": 5}
# float32 on both sides: the readings are ~1e-5 (a chunk of 64 rows is one
# triangular solve where the reference takes 64 turns of the recurrence)
TIGHT = 1e-4


def make_core(cfg=CFG, **engine) -> EngineCore:
    return EngineCore(cfg, tiny_engine(**engine), seed=5)


def held_to_reference(core, body=BODY, atol=check.LOGPROB_ATOL):
    got = check.score_request(core, FILE, body)
    return check.compare(got["served"], got["scored"], atol=atol), got


def test_the_preset_is_the_file():
    assert dataclasses.replace(ModelConfig(**MF), name="tiny-olmo-hybrid") == CFG
    assert CFG.linear and CFG.layer_groups and not CFG.hybrid and not CFG.windowed
    assert CFG.post_norm and CFG.qk_norm_over == "projection" and CFG.rope_theta is None
    assert CFG.layers_of("linear") == (0, 1, 2, 4) and CFG.layers_of("attention") == (3,)
    assert CFG.cache_layer_counts == {"attention": 1, "conv": 0, "linear": 4}
    # two heads of 64 side by side in a tile of 128 lanes
    assert CFG.slab_shapes(9) == {"state": (9, 1, 32, 128), "conv": (9, 3, 2, 128)}
    # 3 KV heads are no count the attention kernel's page tiles: kept as 4, the spare zero
    assert CFG.cache_kv_heads == 4 and CFG.kv_page_tail(8) == (8, 8, 128)
    assert CFG.state_bytes_per_sequence() == 4 * (2 * 32 * 64 * 4 + 3 * 256 * 4)
    assert "tiny-olmo-hybrid" in PRESETS and "olmo-hybrid-7b-pp2-16l" in PRESETS
    assert not tiny_model().linear and tiny_model().cache_layer_counts == {
        "attention": tiny_model().num_layers, "conv": 0}


@pytest.fixture(scope="module")
def served():
    """The default engine (megastep k = 8, the one-step-ahead loop) sent the
    probe twice: a prefill wave of 70 rows (a chunk of 64 and one of 6), then
    decode through the slab; nothing of the first send is found by the second."""
    core = make_core()
    return core, check.score_request(core, FILE, BODY)


def test_prefill_then_decode_through_the_slab_agree_with_one_full_forward(served):
    core, got = served
    assert core.engine.megastep == 8 and core.pipelined
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert verdict["compared"] == 2 * 17 * 5 and verdict["argmax_mismatches"] == 0
    first, repeat = got["served"]
    assert first["tokens"] == repeat["tokens"] and len(first["tokens"]) == 17
    # no block holds a linear layer's state: prefix caching is off, nothing is found
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0
    assert core.engine.enable_prefix_caching is False


def test_the_slab_and_its_counters_are_on_the_status_surface(served):
    core, _ = served
    st = core.scheduler_stats()
    assert st["cache_layers"] == {"attention": 1, "conv": 0, "linear": 4}
    assert st["state_bytes_per_sequence"] == CFG.state_bytes_per_sequence()
    assert st["state_slots"] == {"held": 0, "free": 8} and st["state_replayed_tokens"] == 0
    assert st["kv_bytes_per_token"] == 2 * 3 * 128 * 4          # the published K/V
    assert st["cache_page_shape"] == {"attention": [8, 8, 128]}  # the page as kept
    assert st["state_bytes_per_block"] == 0
    assert [c["state"].shape for c in core.cache if isinstance(c, dict)] == [(9, 1, 32, 128)] * 4
    assert core.cache[0]["state"].dtype == jnp.float32
    calls = linear_attention.traced_calls()
    assert calls["step", "jnp"] >= 4 and calls["scan", "jnp"] >= 4
    from dynamo_tpu.runtime.status_server import ENGINE_COUNTERS, SCHEDULER_GAUGES

    assert "state_bytes_per_sequence" in SCHEDULER_GAUGES
    assert "state_replayed_tokens" in ENGINE_COUNTERS


@pytest.mark.parametrize("engine", [
    {"megastep_k": 1, "async_exec": False},           # a dispatch a token, synchronous
    {"megastep_k": 2},                                # another megastep length
    {"scheduling": "chunked", "prefill_chunk": 16},   # the prompt in chunks, mixed steps
    {"prefill_buckets": (16, 32), "max_model_len": 128},   # waves shorter than the prompt
], ids=["k1-sync", "k2", "chunked", "short-waves"])
def test_other_step_shapes_agree_with_reference(engine, served):
    core = make_core(**engine)
    verdict, got = held_to_reference(core)
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert got["served"][0]["tokens"] == served[1]["served"][0]["tokens"]


def test_the_published_heads_without_a_spare_one_agree_with_reference():
    """4 KV heads tile as they are (no spare head in the page): the same
    layers through the unpadded page."""
    file = {**FILE, "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_size": 512}
    cfg = ModelConfig(**model_fields(file))
    assert cfg.cache_kv_heads == 4 and cfg.kv_page_tail(8) == (8, 8, 128)
    core = make_core(cfg)
    got = check.score_request(core, file, {**BODY, "max_tokens": 9})
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict


# -- slots: taken, given back, reused; preemption by replay ----------------------

def _streams(prompts, max_tokens, **engine):
    core = make_core(**engine)
    seqs = [core.add_request(_req(p, f"s{i}", max_tokens=m, ignore_eos=True))
            for i, (p, m) in enumerate(zip(prompts, max_tokens))]
    done, _ = run_to_completion(core, seqs, max_steps=4000)
    return done, core


def test_a_slot_reused_after_a_finished_lane_with_nan_in_it_gives_a_fresh_engines_stream():
    want = _streams([PROMPT[:21]], [12])[0]["s0"]
    done, core = _streams([PROMPT[30:70]], [9])
    assert sorted(core._free_slots) == list(range(8))
    # whatever a slot held (and the garbage slot), a NaN among it, stays out
    core.cache = tuple(
        jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), c) if isinstance(c, dict) else c
        for c in core.cache)
    seq = core.add_request(_req(PROMPT[:21], "again", max_tokens=12, ignore_eos=True))
    done, _ = run_to_completion(core, [seq])
    assert done["again"] == want and sorted(core._free_slots) == list(range(8))


def test_a_sequence_holds_one_slot_from_admission_to_its_end_and_admission_stops_at_none():
    core = make_core(max_num_seqs=2, decode_buckets=(2,))
    seqs = [core.add_request(_req(PROMPT[i:i + 12], f"s{i}", max_tokens=20, ignore_eos=True))
            for i in range(3)]
    core.step()
    held = sorted(s.slot for s in core.running)
    assert len(core.running) == 2 and held == [0, 1] and core._free_slots == []
    assert seqs[2].slot == -1 and core.scheduler_stats()["state_slots"] == {"held": 2, "free": 0}
    done, _ = run_to_completion(core, seqs, max_steps=2000)
    assert all(len(done[f"s{i}"]) == 20 for i in range(3))
    assert sorted(core._free_slots) == [0, 1] and all(s.slot == -1 for s in seqs)
    alone = _streams([PROMPT[2:14]], [20])[0]["s0"]
    assert done["s2"] == alone
    # a cancelled lane gives its slot back once, whatever else releases it
    seq = core.add_request(_req(PROMPT[:12], "gone", max_tokens=50, ignore_eos=True))
    core.step()
    core.cancel_request(seq)
    core.step()
    core._release_blocks(seq)
    assert sorted(core._free_slots) == [0, 1]


def test_preempt_and_resume_replays_from_position_0_to_the_unpressed_stream():
    prompts = [list(range(1 + 20 * i, 17 + 20 * i)) for i in range(3)]
    roomy, core = _streams(prompts, [33] * 3, num_kv_blocks=64, max_model_len=64)
    assert core.exec_stats["state_replayed_tokens"] == 0
    tight, core = _streams(prompts, [33] * 3, num_kv_blocks=14, max_model_len=64)
    assert core.sched_stats["preemptions"] >= 1
    assert tight == roomy and all(len(v) == 33 for v in tight.values())
    # every token a victim had run is run again: nothing of it is found in a block
    assert core.exec_stats["state_replayed_tokens"] >= 16 * core.sched_stats["preemptions"]
    assert sorted(core._free_slots) == list(range(8))


def test_a_stream_preempted_by_hand_comes_back_into_another_slot():
    want = _streams([PROMPT[:21]], [30], async_exec=False)[0]["s0"]
    core = make_core(async_exec=False)
    seq = core.add_request(_req(PROMPT[:21], "s0", max_tokens=30, ignore_eos=True))
    got = []
    while seq.generated < 17:
        for _, out in core.step():
            got += list(out.token_ids)
    first = seq.slot
    with core._step_lock:
        core._preempt(seq)
        core._free_slots.insert(0, core._free_slots.pop())   # the next taker gets another slot
    assert seq.slot == -1 and core.exec_stats["state_replayed_tokens"] == 21 + 16
    done, _ = run_to_completion(core, [seq])
    assert got + done["s0"] == want and seq.num_cached_tokens == 0
    assert first not in (None, -1)


def test_a_lane_goes_on_after_a_megastep_in_which_another_stopped():
    """Lanes that stop inside a megastep (budgets of 3 and 11: mid-megastep,
    seen by the host one step late on the one-step-ahead loop) run dead
    iterations on the garbage slot and one dead dispatch on their own, which
    no one reads again; the lanes that go on, and a request admitted into a
    freed slot later, give the streams they give alone."""
    prompts = [PROMPT[:19], PROMPT[5:30], PROMPT[11:23], PROMPT[2:41]]
    budgets = [3, 41, 11, 25]
    alone = {}
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        alone[f"s{i}"] = _streams([p], [m])[0]["s0"]
    together, core = _streams(prompts, budgets)
    assert core.engine.megastep == 8 and core.pipelined
    assert together == alone
    late = core.add_request(_req(PROMPT[7:38], "late", max_tokens=12, ignore_eos=True))
    done, _ = run_to_completion(core, [late])
    assert done["late"] == _streams([PROMPT[7:38]], [12])[0]["s0"]


# -- the model's own entry: chunks, ragged waves, the invariant ------------------

ENG = EngineConfig(num_kv_blocks=40, block_size=8, max_num_seqs=4, max_model_len=80,
                   prefill_buckets=(80,), decode_buckets=(4,))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(5), CFG)


@pytest.fixture(scope="module")
def ragged_step():
    """``step(cache, parts, T) -> (hidden rows of each part, cache)`` through
    ``forward_hidden``: ``parts`` = [(sequence, ids, first position)], sequence
    ``s`` owning blocks ``10 s .. 10 s + 9`` and lane slot ``s``; rows padded
    to ``T``."""
    S, P = 4, ENG.max_blocks_per_seq

    @jax.jit
    def run(params, cache, tokens, positions, write_pages, kv_lens, tables, cu, num_seqs):
        return forward_hidden(params, cache, tokens, positions, write_pages, positions % 8,
                              kv_lens, tables, cu, num_seqs, CFG, ENG)

    def step(params, cache, parts, T):
        tokens = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        pages = np.full(T, ENG.garbage_block, np.int32)
        kv_lens = np.ones(S, np.int32)
        tables = np.full((S, P + 1), ENG.garbage_block, np.int32)
        tables[:, P] = ENG.garbage_slot
        cu = np.zeros(S + 1, np.int32)
        t = 0
        for i, (s, ids, start) in enumerate(parts):
            n = len(ids)
            pos = np.arange(start, start + n)
            tokens[t:t + n], positions[t:t + n] = ids, pos
            pages[t:t + n] = 10 * s + pos // 8
            kv_lens[i] = start + n
            tables[i, :10] = 10 * s + np.arange(10)
            tables[i, P] = s
            t += n
            cu[i + 1:] = t
        hidden, cache = run(params, cache, *(jnp.asarray(a) for a in (
            tokens, positions, pages, kv_lens, tables, cu)), jnp.asarray([len(parts)], jnp.int32))
        out, t = [], 0
        for _, ids, _ in parts:
            out.append(np.asarray(hidden[t:t + len(ids)]))
            t += len(ids)
        return out, cache

    return step


def _reference_logits(params, ids):
    return np.asarray(arch.reference_logits(params, MF, ids, list(range(len(ids))),
                                            vocab_chunks=3))


def _logits(params, hidden):
    return np.asarray(hidden @ params["lm_head"])


@pytest.mark.parametrize("cut", [1, 2, 3, 4, 63, 64, 65, 71])
def test_a_prompt_cut_at_any_row_gives_the_uncut_prompt(cut, params, ragged_step):
    """The planner may cut a prompt anywhere: rows 1-4 put the cut inside the
    four taps' reach, 63-65 at a scan chunk's edge +- 1; the second part reads
    its state and its convolution's rows from the slab."""
    ids = PROMPT[:72]
    cache = init_cache(CFG, ENG)
    (a,), cache = ragged_step(params, cache, [(1, ids[:cut], 0)], 80)
    (b,), cache = ragged_step(params, cache, [(1, ids[cut:], cut)], 80)
    np.testing.assert_allclose(_logits(params, np.concatenate([a, b])),
                               _reference_logits(params, ids), atol=TIGHT)


def test_a_prompt_a_row_at_a_time_is_the_uncut_prompt(params, ragged_step):
    ids = PROMPT[:11]
    cache, rows = init_cache(CFG, ENG), []
    for p, tok in enumerate(ids):
        (h,), cache = ragged_step(params, cache, [(2, [tok], p)], 80)
        rows.append(h)
    np.testing.assert_allclose(_logits(params, np.concatenate(rows)),
                               _reference_logits(params, ids), atol=TIGHT)


def test_two_prompts_in_one_wave_are_each_alone(params, ragged_step):
    """Three sequences in one ``[T, h]``: a whole prompt, the rest of a prompt
    whose first 13 rows went before, and one row: a chunk of the scan never
    straddles two sequences, a shift along ``T`` never crosses into the
    sequence before, and each sequence's first rows come from ITS slot."""
    a, b, c = PROMPT[:17], PROMPT[3:33], PROMPT[9:30]
    cache = init_cache(CFG, ENG)
    _, cache = ragged_step(params, cache, [(2, b[:13], 0), (0, c[:20], 0)], 80)
    (ha, hb, hc), cache = ragged_step(
        params, cache, [(1, a, 0), (2, b[13:], 13), (0, c[20:], 20)], 80)
    for ids, h, first in ((a, ha, 0), (b, hb, 13), (c, hc, 20)):
        np.testing.assert_allclose(_logits(params, h), _reference_logits(params, ids)[first:],
                                   atol=TIGHT)
    (alone,), _ = ragged_step(params, init_cache(CFG, ENG), [(3, a, 0)], 80)
    np.testing.assert_allclose(ha, alone, atol=1e-5)


def _decode_fn(params, table):
    @jax.jit
    def decode(cache, token, position, active):
        tokens = jnp.zeros(4, jnp.int32).at[0].set(token)
        positions = jnp.zeros(4, jnp.int32).at[0].set(position)
        return model_mod.decode_tokens(params, cache, tokens, table, positions, active,
                                       CFG, ENG)
    return decode


@pytest.mark.parametrize("past_the_cursor", [
    "masked",           # iterations of a lane the device saw stop: active false
    "written",          # ... written to the lane's own slot: the control, must FAIL
    "ends",             # iterations after a host-only stop / the one-step-ahead dispatch
    "preempted",        # a lane preempted with its step in flight
])
def test_nothing_written_past_a_cursor_is_read_by_a_sequence_that_goes_on(
        past_the_cursor, params):
    """THE INVARIANT of ``model.linear_layer`` (``conv_layer``'s). A lane
    computed to position 12, then run two iterations FURTHER on junk tokens.
    ``masked``: ``active`` false sends those updates to the garbage slot and
    the lane, continued from 13, is the reference's. ``written``: updated in
    its own slot they are part of the state for ever, and the continuation is
    NOT the reference's: so every write past a cursor must end the sequence
    or go to the garbage slot, and speculation is refused. ``ends``: the lane
    ends; whoever takes its slot next starts at position 0 and reads zeros.
    ``preempted``: its state is discarded with the slot; it replays from 0
    into another and is the reference's from there."""
    ids = PROMPT[:16]
    cache = init_cache(CFG, ENG)
    P = ENG.max_blocks_per_seq
    table = np.full((4, P + 1), ENG.garbage_block, np.int32)
    table[:, P] = ENG.garbage_slot
    table[0, :10], table[0, P] = np.arange(10), 2
    decode = _decode_fn(params, jnp.asarray(table))
    lane0, none = jnp.asarray([True, False, False, False]), jnp.zeros(4, bool)
    for p in range(13):
        _, cache = decode(cache, ids[p], p, lane0)
    for p in (13, 14):       # two iterations past the cursor on a junk token
        _, cache = decode(cache, 7, p, none if past_the_cursor == "masked" else lane0)
    if past_the_cursor in ("masked", "written"):
        first = 13
    else:   # the slot's next taker, or the lane itself into another slot: from position 0
        first = 0
        if past_the_cursor == "preempted":
            table[0, P] = 1
            decode = _decode_fn(params, jnp.asarray(table))
    got = []
    for p in range(first, 16):
        logits, cache = decode(cache, ids[p], p, lane0)
        got.append(np.asarray(logits[0]))
    worst = np.abs(np.asarray(got) - _reference_logits(params, ids)[first:]).max()
    assert worst > 1e-2 if past_the_cursor == "written" else worst < TIGHT, worst
    with pytest.raises(UnsupportedModelOption, match="spec_decode") as e:
        make_core(spec_decode="ngram")
    assert "past the cursor" in str(e.value)


def test_embeddings_path_runs_both_kinds_of_layer(params, ragged_step):
    core = EngineCore(CFG, tiny_engine(), seed=5, params=params)
    (hidden,), _ = ragged_step(params, init_cache(CFG, ENG), [(0, PROMPT[:40], 0)], 80)
    np.testing.assert_allclose(core.embed(PROMPT[:40]), hidden.mean(0), atol=1e-5)
    np.testing.assert_allclose(core.embed(PROMPT[:40]), hidden.mean(0), atol=1e-5)  # its slot again


def test_the_full_layers_count_under_the_dense_shapes_and_build_no_rope(served, monkeypatch):
    calls = ragged_attention.traced_calls()
    assert calls[("decode", "reference")] >= 1 and calls[("ragged", "reference")] >= 1

    def no_rope(*a, **k):
        raise AssertionError("a rope table was built for a model without rope")

    monkeypatch.setattr(model_mod, "rope_tables", no_rope)
    monkeypatch.setattr(model_mod, "rope_apply", no_rope)
    eng = served[0].engine
    jax.make_jaxpr(lambda: model_mod.decode_tokens(
        served[0].params, init_cache(CFG, eng), jnp.zeros(8, jnp.int32),
        jnp.zeros((8, eng.max_blocks_per_seq + 1), jnp.int32), jnp.arange(8, dtype=jnp.int32),
        jnp.ones(8, bool), CFG, eng))()
    after = ragged_attention.traced_calls()
    assert after[("decode", "reference")] == calls[("decode", "reference")] + 1   # one full layer


# -- faults in the layers are caught ----------------------------------------------

def _patched_layout(mp, change):
    real = arch.published_layout

    def layout(params, l, mf, mlp_blocks=8):
        kind, w, ffn_norm, blocks = real(params, l, mf, mlp_blocks)
        return kind, change(kind, dict(w)), ffn_norm, blocks
    mp.setattr(arch, "published_layout", layout)


def _fault_tap_order_reversed(mp):
    _patched_layout(mp, lambda kind, w: {**w, "conv_w": w["conv_w"][::-1]}
                    if kind == "linear_attention" else w)


def _fault_output_norm_dropped(mp):
    _patched_layout(mp, lambda kind, w: {**w, "o_norm": jnp.ones_like(w["o_norm"])}
                    if kind == "linear_attention" else w)


def _fault_qk_norm_per_head(mp):
    real = model_mod.dense_layer
    mp.setattr(model_mod, "dense_layer", lambda x, lp, *a, **k: real(
        x, {**lp, "q_layernorm": jnp.ones_like(lp["q_layernorm"])}, *a, **k))


def _fault_norm_on_the_input(mp):
    real = model_mod._residual_mlp

    def pre_norm(x, lp, cfg, *a, **k):
        return real(x, lp, dataclasses.replace(cfg, post_norm=False), *a, **k)
    mp.setattr(model_mod, "_residual_mlp", pre_norm)


def _fault_rope_applied(mp):
    real = model_mod.dense_layer

    def with_rope(x, lp, cache_l, positions, *a, **k):
        k["rope_cs"] = model_mod.rope_tables(positions, 128, 10000.0)
        return real(x, lp, cache_l, positions, *a, **k)
    mp.setattr(model_mod, "dense_layer", with_rope)


@pytest.mark.parametrize("fault", [
    _fault_tap_order_reversed, _fault_output_norm_dropped, _fault_qk_norm_per_head,
    _fault_norm_on_the_input, _fault_rope_applied,
], ids=lambda f: f.__name__[7:])
def test_a_fault_in_the_layers_is_caught(fault, monkeypatch):
    fault(monkeypatch)
    verdict, _ = held_to_reference(make_core(), {**BODY, "max_tokens": 9}, atol=TIGHT)
    assert not verdict["ok"] and verdict["max_abs_diff"] > 100 * TIGHT, verdict


@pytest.mark.parametrize("fault", ["fp8", "decay", "neg_eigval", "state_bf16"])
def test_the_references_faults_move_it_off_the_engine(fault, served):
    """What the controls of ``correct`` change in the reference, at the size
    a test can hold and in float32: each is far outside what separates the
    engine from the sound reference (``state_bf16`` reads ~0.08 here, under
    the benchmark's tolerance: PERF.md section 7)."""
    core, got = served
    probe = got["served"][0]
    scored = check.score_probe(FILE, core.params, BODY["prompt_ids"], probe, faults=(fault,))
    verdict = check.compare([probe], {"sequences": [scored]}, atol=TIGHT)
    assert not verdict["ok"] and verdict["max_abs_diff"] > 100 * TIGHT, verdict


# -- refusals and counts ------------------------------------------------------------

@pytest.mark.parametrize("option,build", [
    ("prefix_caching", lambda: make_core(enable_prefix_caching=True)),
    ("spec_decode", lambda: make_core(spec_decode="ngram")),
    ("host_kv_blocks", lambda: make_core(host_kv_blocks=8)),
    ("disk_kv_dir", lambda: make_core(host_kv_blocks=0, disk_kv_dir="/nowhere")),
    ("kv_dtype", lambda: make_core(kv_dtype="int8")),
    ("tp", lambda: EngineCore(CFG, tiny_engine(), seed=5, mesh=object())),
    ("pp", lambda: EngineCore(CFG, tiny_engine(), seed=5, pp_mesh=object())),
    ("ring_prefill", lambda: EngineCore(CFG, tiny_engine(), seed=5, sp_mesh=object())),
    ("ring_prefill", lambda: make_core(ring_prefill_threshold=64)),
], ids=["prefix-caching", "speculation", "host-tier", "disk-tier", "int8-kv", "tp", "pp",
        "sp-mesh", "ring-threshold"])
def test_an_option_a_per_lane_state_does_not_carry_is_refused_at_start_up(option, build):
    with pytest.raises(UnsupportedModelOption, match=option) as e:
        build()
    assert e.value.option == option and "tiny-olmo-hybrid" in str(e.value)
    assert isinstance(e.value, NotImplementedError)


def test_a_block_does_not_leave_the_device(served):
    core, _ = served
    for option, leave in (
            ("disagg", lambda: core.kv_page_shape),
            ("disagg", lambda: core.export_descriptors("nobody")),
            ("disagg", lambda: core.import_blocks([])),
            ("peer_kv", lambda: core.read_cached_pages([1, 2]))):
        with pytest.raises(UnsupportedModelOption, match=option) as e:
            leave()
        assert e.value.option == option and "lane slot" in str(e.value)


def test_int8_weights_and_int8_pages_are_refused_by_name():
    from dynamo_tpu.backends.jax.main import build_engine

    with pytest.raises(UnsupportedModelOption, match="quant") as e:
        build_engine("tiny-olmo-hybrid", {"num_kv_blocks": 16, "block_size": 8}, quant="int8")
    assert e.value.option == "quant"
    with pytest.raises(NotImplementedError, match="tiny-olmo-hybrid"):
        model_mod.init_params_quantized(jax.random.PRNGKey(0), CFG)
    with pytest.raises(NotImplementedError, match="unquantised"):
        CFG.quantized_param_bytes()
    with pytest.raises(NotImplementedError, match="float32 slab"):
        init_cache(CFG, tiny_engine(kv_dtype="int8"))


@pytest.mark.parametrize("change,error", [
    ({"linear_key_head_dim": 0}, ValueError),
    ({"linear_conv_kernel_dim": 1}, ValueError),
    ({"linear_num_key_heads": 1}, NotImplementedError),
    ({"layer_types": ("linear_attention",) * 4 + ("conv",), "conv_L_cache": 3},
     NotImplementedError),
    ({"sandwich_norm": True}, NotImplementedError),
    ({"attn_qkv_bias": True}, NotImplementedError),
    ({"qk_norm_over": "row"}, ValueError),
    ({"qk_norm": False}, ValueError),
    ({"num_experts": 4}, NotImplementedError),
], ids=["no-key-width", "one-tap", "shared-key-heads", "conv-beside", "sandwich", "qkv-bias",
        "unknown-span", "span-without-norm", "experts"])
def test_a_field_that_does_not_apply_raises(change, error):
    with pytest.raises(error):
        dataclasses.replace(CFG, **change)


def test_the_linear_fields_mean_nothing_to_a_model_without_such_layers():
    for field, value in (("linear_key_head_dim", 32), ("linear_allow_neg_eigval", True)):
        with pytest.raises(ValueError, match="linear_attention"):
            dataclasses.replace(tiny_model(), **{field: value})
    assert tiny_model().state_bytes_per_sequence() == 0


def test_counts_of_the_published_size_by_hand():
    cfg = olmo_hybrid_7b_pp2_16l()
    h, H, dk, dv = 3840, 30, 96, 192
    linear = 2 * h * H * dk + 3 * h * H * dv + 2 * h * H + 4 * 11520 + 2 * H + dv
    full = 4 * h * h + 2 * h
    swiglu = 3 * h * 11008
    assert linear == 88_750_332 and full == 58_990_080 and swiglu == 126_812_160
    total = 12 * linear + 4 * full + 16 * (swiglu + 2 * h) + 2 * 100352 * h + h
    assert cfg.param_bytes() == 2 * total and 8.20e9 < cfg.param_bytes() < 8.21e9
    assert cfg.state_bytes_per_sequence() == 12 * (H * dk * dv * 4 + 3 * 11520 * 2) == 27_371_520
    assert cfg.slab_shapes(49) == {"state": (49, 15, 96, 384), "conv": (49, 3, 90, 128)}
    assert cfg.kv_unit_values == 2 * 30 * 128                       # the published K/V a token
    assert cfg.cache_kv_heads == 32 and cfg.kv_page_tail(32) == (32, 64, 128)
    assert cfg.bytes_per_block(32, "attention") == 4 * 32 * 64 * 128 * 2 == 2 ** 21
    assert cfg.cache_layer_counts == {"attention": 4, "conv": 0, "linear": 12}


# -- the checkpoint's names --------------------------------------------------------

def test_loader_takes_the_checkpoints_names(tmp_path, params, ragged_step):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    h, v, inter, H, dk, dv, K = 384, 384, 320, 2, 32, 64, 4
    rng = np.random.RandomState(11)
    mat = lambda out, inp: (rng.randn(out, inp) * inp ** -0.5).astype(np.float32)  # noqa: E731
    norm = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": norm(h),
          "lm_head.weight": mat(v, h)}
    for l, kind in enumerate(CFG.layer_types):
        p = f"model.layers.{l}."
        sd[p + "post_attention_layernorm.weight"] = norm(h)
        sd[p + "post_feedforward_layernorm.weight"] = norm(h)
        for name, out in (("gate_proj", inter), ("up_proj", inter)):
            sd[p + f"mlp.{name}.weight"] = mat(out, h)
        sd[p + "mlp.down_proj.weight"] = mat(h, inter)
        if kind == "linear_attention":
            a = p + "linear_attn."
            for name, out in (("q_proj", H * dk), ("k_proj", H * dk), ("v_proj", H * dv),
                              ("g_proj", H * dv), ("b_proj", H), ("a_proj", H)):
                sd[a + name + ".weight"] = mat(out, h)
            sd[a + "o_proj.weight"] = mat(h, H * dv)
            for name, ch in (("q", H * dk), ("k", H * dk), ("v", H * dv)):
                sd[a + f"{name}_conv1d.weight"] = (rng.randn(ch, 1, K) * K ** -0.5).astype(
                    np.float32)
            sd[a + "A_log"] = np.log(rng.uniform(0.8, 1.25, H)).astype(np.float32)
            sd[a + "dt_bias"] = rng.uniform(-6, -2, H).astype(np.float32)
            sd[a + "o_norm.weight"] = norm(dv)
        else:
            for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                sd[p + f"self_attn.{name}.weight"] = mat(h, h)
            sd[p + "self_attn.q_norm.weight"] = norm(h)
            sd[p + "self_attn.k_norm.weight"] = norm(h)
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items()
          if k not in ("name", "torch_dtype", "serve", "source", "deployment", "reduced",
                       "assumed")}
    (tmp_path / "config.json").write_text(json.dumps(hf))

    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32)
    assert cfg == dataclasses.replace(CFG, name="olmo_hybrid", dtype="bfloat16")
    assert jax.tree.map(jnp.shape, loaded) == jax.tree.map(jnp.shape, params)
    np.testing.assert_array_equal(      # layer 4 is the fourth linear layer; taps [K, q | k | v]
        loaded["linear"]["conv_w"][3][:, H * dk:2 * H * dk],
        sd["model.layers.4.linear_attn.k_conv1d.weight"][:, 0, :].T)
    np.testing.assert_array_equal(loaded["linear"]["w_qkv"][1][:, 2 * H * dk:],
                                  sd["model.layers.1.linear_attn.v_proj.weight"].T)
    np.testing.assert_array_equal(loaded["linear"]["w_ba"][0][:, H:],
                                  sd["model.layers.0.linear_attn.a_proj.weight"].T)
    np.testing.assert_array_equal(loaded["linear"]["dt_bias"][2],
                                  sd["model.layers.2.linear_attn.dt_bias"])
    np.testing.assert_array_equal(loaded["attn"]["wqkv"][0][:, h:2 * h],
                                  sd["model.layers.3.self_attn.k_proj.weight"].T)
    np.testing.assert_array_equal(loaded["attn"]["k_layernorm"][0],
                                  sd["model.layers.3.self_attn.k_norm.weight"])
    np.testing.assert_array_equal(loaded["layers"]["mlp_norm"][4],
                                  sd["model.layers.4.post_feedforward_layernorm.weight"])
    # the loaded tree serves: the engine on it against the reference on it
    loaded = jax.tree.map(jnp.asarray, loaded)
    ids = PROMPT[:19]
    (hidden,), _ = ragged_step(loaded, init_cache(CFG, ENG), [(0, ids, 0)], 80)
    np.testing.assert_allclose(_logits(loaded, hidden), _reference_logits(loaded, ids),
                               atol=TIGHT)


def test_the_smoke_holds_a_tpu_worker_to_the_state_kernel():
    """``chip_smoke.judge_linear_traced``: on a TPU a decode step's state
    update that traced its ``jax.numpy`` path fails the run; on the CPU that
    path is the one there is; a model without such layers shows no series."""
    import chip_smoke

    chip_smoke.judge_linear_traced("aggregated", {"step/pallas": 24.0, "scan/jnp": 48.0}, "tpu")
    chip_smoke.judge_linear_traced("aggregated", {"step/jnp": 8.0, "scan/jnp": 8.0}, "cpu")
    chip_smoke.judge_linear_traced("aggregated", {}, "tpu")
    with pytest.raises(chip_smoke.PhaseFailed, match="jnp path on a TPU"):
        chip_smoke.judge_linear_traced("aggregated", {"step/jnp": 12.0}, "tpu")
