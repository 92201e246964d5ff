"""LFM2's layers (``ModelConfig.layer_types`` with "conv", ``kv_head_pairs``,
``router_bias``) at a tiny size on the CPU in float32: gated three-tap
convolutions whose state rides the block table, one GQA layer in four at
head width 64 (cached in pairs, QK-norm before rope), two dense layers then
eight whole bias-chosen experts. The engine is held to the plain reference
(``chipbench/reference/lfm2_moe.py``) through prefill, decode, a megastep,
prefix hits, a preemption, every chunk boundary against the three taps and
several sequences in one ragged wave; faults must fail the comparison; the
invariant of a rolling state (nothing written past a cursor is read by a
sequence that goes on) is pinned; every option the two-shaped cache does
not carry is refused by name."""

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import lfm2_moe as arch
from chipbench.configs import load_config, model_fields
from chipbench.reference import check
from chipbench.reference import lfm2_moe as reference
from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    PRESETS,
    UnsupportedModelOption,
    lfm2_24b_a2b_10l,
    tiny_lfm2,
    tiny_model,
)
from dynamo_tpu.engine.model import forward_hidden, init_cache, init_params
from dynamo_tpu.ops import ragged_attention
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_lfm2()
FILE = load_config("tiny-lfm2-rehearsal")
MF = model_fields(FILE)
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=41)]
BODY = {"prompt_ids": PROMPT[:40], "max_tokens": 17, "top": 5}
TIGHT = 1e-4   # float32 on both sides: the readings are 1e-6 to 1e-5


def make_core(cfg=CFG, **engine) -> EngineCore:
    return EngineCore(cfg, tiny_engine(**engine), seed=5)


def held_to_reference(core, body=BODY):
    got = check.score_request(core, FILE, body)
    return check.compare(got["served"], got["scored"]), got


def test_the_preset_is_the_file():
    assert dataclasses.replace(ModelConfig(**MF), name="tiny-lfm2") == CFG
    assert CFG.hybrid and CFG.shared_sparse and CFG.kv_head_pairs and not CFG.latent
    assert CFG.layers_of("conv") == (0, 1, 3, 4, 5) and CFG.layers_of("attention") == (2,)
    # two KV heads a 128-wide row; two rows of u a block, in 128-lane rows
    assert CFG.kv_page_tail(8) == (8, 2, 128) and CFG.kv_page_tail(8, "conv") == (2, 2, 128)
    assert CFG.cache_layers("attention") == CFG.num_cache_layers == 1
    assert CFG.cache_layers("conv") == 5 and CFG.state_bytes_per_block() == 5 * 2 * 256 * 4
    assert "tiny-lfm2" in PRESETS and "lfm2-24b-a2b-10l" in PRESETS
    assert not tiny_model().hybrid and not tiny_model().kv_head_pairs


@pytest.fixture(scope="module")
def served():
    """The default engine (megastep k = 8, the one-step-ahead loop) sent
    the probe twice: prefill, decode through both kinds of page, a prefix hit."""
    core = make_core()
    return core, check.score_request(core, FILE, BODY)


def test_prefill_decode_megastep_and_prefix_hit_agree_with_reference(served):
    core, got = served
    assert core.engine.megastep == 8 and core.pipelined
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert verdict["compared"] == 2 * 17 * 5 and verdict["argmax_mismatches"] == 0
    first, repeat = got["served"]
    assert first["tokens"] == repeat["tokens"] and len(first["tokens"]) == 17
    # 40 tokens are five whole blocks: the hit is cut back a WHOLE block, so
    # that the rows computed start a block and find the state of the one before
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] == 32


def test_the_state_is_found_not_rebuilt_and_the_counter_says_where(served):
    core, _ = served
    reads = core.scheduler_stats()["conv_state_reads"]
    # two probes: the second's first rows read a shared block's page; each
    # probe's decode is two megasteps of 8 (one lane), the first fed by the wave
    assert reads["prefix_hit"] == 1 and reads["earlier_dispatch"] == 4
    assert reads["same_step"] == 4 * 7
    st = core.scheduler_stats()
    assert st["cache_layers"] == {"attention": 1, "conv": 5}
    assert st["kv_bytes_per_token"] == 2 * 2 * 64 * 4 and st["kv_cache_layers"] == 1
    assert st["state_bytes_per_block"] == 5 * 2 * 256 * 4
    assert core.kv_cache_stats()["bytes_per_block"] == 8 * st["kv_bytes_per_token"]
    # the experts' counters (PR 32): 4 sparse layers, all 8 experts held
    decode = st["expert_stats"]["decode"]
    assert st["experts_held"] == 8 and decode[1] == 2 * 2 * 8 * 4
    assert decode[2] == decode[3] == 2 * 16 * 2 * 4
    from dynamo_tpu.runtime.status_server import SCHEDULER_GAUGES

    assert "state_bytes_per_block" in SCHEDULER_GAUGES


def test_paired_heads_trace_the_serving_entry_by_shape(served):
    """64-wide heads reach the attention entry as 128-wide pairs: counted
    under the shapes every dense model counts under (on a TPU: "library")."""
    calls = ragged_attention.traced_calls()
    assert calls[("decode", "reference")] >= 1 and calls[("ragged", "reference")] >= 1
    before = dict(calls)
    eng = served[0].engine
    jax.make_jaxpr(lambda: model_mod.decode_tokens(
        served[0].params, init_cache(CFG, eng), jnp.zeros(8, jnp.int32),
        jnp.zeros((8, eng.max_blocks_per_seq), jnp.int32), jnp.arange(8, dtype=jnp.int32),
        jnp.ones(8, bool), CFG, eng))()
    after = ragged_attention.traced_calls()
    assert after[("decode", "reference")] == before[("decode", "reference")] + 1  # one attention layer


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
    assert got["served"][1]["cached_tokens"] == 32


@pytest.mark.parametrize("n,hit", [(41, 40), (37, 32), (33, 32), (32, 24)],
                         ids=["one-row-past-a-block", "mid-block", "first-row-of-a-block",
                              "ends-on-a-block"])
def test_a_prefix_hit_wherever_the_prompt_ends(n, hit):
    """The rows left to compute after a hit start a block: a prompt that
    ends one row into a block (the three taps reach two rows back into the
    shared block), mid-block, and on a boundary (the hit gives up a block)."""
    core = make_core()
    verdict, got = held_to_reference(core, {"prompt_ids": PROMPT[:n], "max_tokens": 9, "top": 5})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert [s["cached_tokens"] for s in got["served"]] == [0, hit]
    assert core.conv_state_reads["prefix_hit"] == 1


def test_a_prefix_hit_gives_the_first_sends_digits_in_bfloat16():
    """The benchmark's ``correct`` wants the second send of a probe (a
    prefix hit) to choose the first send's tokens: ``u`` is rounded to the
    model dtype before it is used or cached, so a row's taps see the same
    values from the pages as from the wave."""
    core = make_core(dataclasses.replace(CFG, dtype="bfloat16"))
    first, again = (check.run_probe(core, PROMPT[:40], 17, 5, tag) for tag in ("a", "b"))
    assert again["cached_tokens"] == 32 and first["tokens"] == again["tokens"]
    assert first["top_ids"] == again["top_ids"]


def _streams(prompts, max_tokens, **engine):
    core = make_core(**engine)
    seqs = [core.add_request(_req(p, f"s{i}", max_tokens=m, ignore_eos=True))
            for i, (p, m) in enumerate(zip(prompts, max_tokens))]
    done, _ = run_to_completion(core, seqs, max_steps=4000)
    return done, core


def test_preempt_and_resume_gives_the_unpressed_stream():
    prompts = [list(range(1 + 20 * i, 17 + 20 * i)) for i in range(3)]
    roomy, _ = _streams(prompts, [33] * 3, num_kv_blocks=64, max_model_len=64)
    tight, core = _streams(prompts, [33] * 3, num_kv_blocks=14, max_model_len=64)
    assert core.sched_stats["preemptions"] >= 1
    assert tight == roomy and all(len(v) == 33 for v in tight.values())


def test_a_resumed_stream_finds_its_state_in_the_blocks_it_had_filled():
    """Preempted by hand with room to spare, so that its full blocks are
    still in the prefix index when it comes back: the resume is a prefix hit
    on its OWN blocks (prompt and generated tokens alike), the rows after
    them are recomputed, and the stream is the undisturbed one."""
    want = _streams([PROMPT[:21]], [30], async_exec=False)[0]["s0"]
    core = make_core(async_exec=False)
    seq = core.add_request(_req(PROMPT[:21], "s0", max_tokens=30, ignore_eos=True))
    got = []
    while seq.generated < 17:
        for _, out in core.step():
            got += list(out.token_ids)
    with core._step_lock:
        core._preempt(seq)
    done, _ = run_to_completion(core, [seq])
    assert got + done["s0"] == want and core.sched_stats["preemptions"] == 1
    # 21 + 17 tokens had been computed: four whole blocks of 8 came back from the index
    assert seq.num_cached_tokens == 32 and core.conv_state_reads["prefix_hit"] == 1


def test_a_lane_goes_on_after_a_megastep_in_which_another_stopped():
    """Lanes that stop inside a megastep (budgets of 3 and 11: mid-megastep,
    and seen by the host one step late on the one-step-ahead loop) run dead
    iterations and one dead dispatch; the lanes that go on, and requests
    admitted into the freed blocks later, give the streams they give alone."""
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


# -- the model's own entry: chunks, ragged waves, the invariant ----------------

ENG = EngineConfig(num_kv_blocks=24, block_size=8, max_num_seqs=4, max_model_len=64,
                   prefill_buckets=(64,), decode_buckets=(4,))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(5), CFG)


@pytest.fixture(scope="module")
def ragged_step():
    """``step(cache, parts, T) -> (hidden rows of each part, cache)`` through
    ``forward_hidden``: ``parts`` = [(sequence, ids, first position)], sequence
    ``s`` owning blocks ``8 s .. 8 s + 7``; rows padded to ``T``."""
    S = 4

    @jax.jit
    def run(params, cache, tokens, positions, write_pages, kv_lens, tables, cu, num_seqs):
        return forward_hidden(params, cache, tokens, positions, write_pages, positions % 8,
                              kv_lens, tables, cu, num_seqs, CFG, ENG)

    def step(params, cache, parts, T):
        tokens = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        pages = np.full(T, ENG.garbage_block, np.int32)
        kv_lens = np.ones(S, np.int32)
        tables = np.full((S, ENG.max_blocks_per_seq), ENG.garbage_block, np.int32)
        cu = np.zeros(S + 1, np.int32)
        t = 0
        for i, (s, ids, start) in enumerate(parts):
            n = len(ids)
            pos = np.arange(start, start + n)
            tokens[t:t + n], positions[t:t + n] = ids, pos
            pages[t:t + n] = 8 * s + pos // 8
            kv_lens[i] = start + n
            tables[i, :8] = 8 * s + np.arange(8)
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


def _reference_hidden_logits(params, ids):
    return np.asarray(arch.reference_logits(params, MF, ids, list(range(len(ids))),
                                            vocab_chunks=3))


def _logits(params, hidden):
    return np.asarray(hidden @ params["embed"].T)


@pytest.mark.parametrize("chunk", [1, 2, 3, 33])
def test_a_prompt_cut_into_chunks_meets_every_boundary_against_the_three_taps(
        chunk, params, ragged_step):
    """Chunks of 1, 2 and 3 put a boundary at every offset of a block
    against taps that reach two rows back; 33 is a block and a row."""
    ids = PROMPT[:41]
    cache, rows = init_cache(CFG, ENG), []
    for start in range(0, len(ids), chunk):
        (h,), cache = ragged_step(params, cache, [(1, ids[start:start + chunk], start)], chunk)
        rows.append(h)
    np.testing.assert_allclose(_logits(params, np.concatenate(rows)),
                               _reference_hidden_logits(params, ids), atol=TIGHT)


def test_several_sequences_in_one_ragged_wave(params, ragged_step):
    """Three sequences in one ``[T, h]``: a whole prompt, the rest of a
    prompt whose first 13 rows went before (mid-block), and one row: a
    shift along ``T`` never crosses into the sequence before, and each
    sequence's first rows come from ITS pages."""
    a, b, c = PROMPT[:17], PROMPT[3:33], PROMPT[9:30]
    cache = init_cache(CFG, ENG)
    _, cache = ragged_step(params, cache, [(2, b[:13], 0), (0, c[:20], 0)], 64)
    (ha, hb, hc), cache = ragged_step(
        params, cache, [(1, a, 0), (2, b[13:], 13), (0, c[20:], 20)], 64)
    for ids, h, first in ((a, ha, 0), (b, hb, 13), (c, hc, 20)):
        np.testing.assert_allclose(_logits(params, h),
                                   _reference_hidden_logits(params, ids)[first:], atol=TIGHT)


def test_only_the_rows_that_end_a_chunk_or_a_block_are_written(params, ragged_step):
    """19 rows from position 0: block 0's page holds u at 6 and 7, block 1's
    at 14 and 15, block 2's at 17 (slot 1) and 18 (slot 0); no other page is
    touched (the other rows wrote the garbage page)."""
    cache = init_cache(CFG, ENG)
    _, cache = ragged_step(params, cache, [(0, PROMPT[:19], 0)], 64)
    state = np.asarray(cache[0])                     # layer 0 is conv
    assert state.shape == (25, 2, 2, 128)
    written = {int(p) for p in np.nonzero(np.abs(state).reshape(25, -1).sum(1))[0]}
    assert written == {0, 1, 2, ENG.garbage_block}
    lp = model_mod.layer_params(params, 0, CFG)
    x = params["embed"][jnp.asarray(PROMPT[:19])]
    y = model_mod.rms_norm(x, lp["attn_norm"], CFG.rms_norm_eps)
    gate_b, _, z = jnp.split(y @ lp["in_proj"], 3, axis=-1)
    u = np.asarray(gate_b * z)
    for page, slot, position in ((0, 0, 6), (0, 1, 7), (1, 0, 14), (1, 1, 15), (2, 1, 17),
                                 (2, 0, 18)):
        np.testing.assert_allclose(state[page, slot].reshape(-1), u[position], atol=1e-5)


@pytest.mark.parametrize("dead_lane_is", ["masked", "written"])
def test_nothing_written_past_a_cursor_is_read_by_a_sequence_that_goes_on(
        dead_lane_is, params):
    """THE INVARIANT of ``model.conv_layer``. A lane computed to position 12,
    then run two iterations FURTHER on junk tokens (what a megastep does to a
    lane past its stop), then continued from 13. ``active`` false (what the
    megastep passes for a lane the device saw stop) sends those writes to the
    garbage page and the continuation is the reference's; written to the
    lane's own block they overwrite u at 11 and 12, which the continuation
    reads, and it is not. So every write past a cursor must end the sequence
    or go to the garbage page, and speculation (which rejects rows it has
    written and goes on) is refused."""
    ids = PROMPT[:16]
    cache = init_cache(CFG, ENG)
    table = np.full((4, ENG.max_blocks_per_seq), ENG.garbage_block, np.int32)
    table[0, :8] = np.arange(8)
    table = jnp.asarray(table)
    lane0 = jnp.asarray([True, False, False, False])

    @jax.jit
    def decode(cache, token, position, active):
        tokens = jnp.zeros(4, jnp.int32).at[0].set(token)
        positions = jnp.zeros(4, jnp.int32).at[0].set(position)
        return model_mod.decode_tokens(params, cache, tokens, table, positions, active,
                                       CFG, ENG)

    for p in range(13):
        logits, cache = decode(cache, ids[p], p, lane0)
    for p in (13, 14):       # two dead iterations on a junk token
        _, cache = decode(cache, 7, p, lane0 if dead_lane_is == "written" else ~lane0 & False)
    got = []
    for p in range(13, 16):
        logits, cache = decode(cache, ids[p], p, lane0)
        got.append(np.asarray(logits[0]))
    want = _reference_hidden_logits(params, ids)[13:]
    worst = np.abs(np.asarray(got) - want).max()
    assert worst < TIGHT if dead_lane_is == "masked" else worst > 1e-2, worst
    with pytest.raises(UnsupportedModelOption, match="spec_decode") as e:
        make_core(spec_decode="ngram")
    assert "past the cursor" in str(e.value)


def test_embeddings_path_runs_both_kinds_of_layer(params, ragged_step):
    core = EngineCore(CFG, tiny_engine(), seed=5, params=params)
    (hidden,), _ = ragged_step(params, init_cache(CFG, ENG), [(0, PROMPT[:40], 0)], 64)
    np.testing.assert_allclose(core.embed(PROMPT[:40]), hidden.mean(0), atol=1e-5)


# -- paired heads against the per-head softmax ---------------------------------

@pytest.mark.parametrize("decode", [True, False], ids=["decode-shape", "ragged"])
def test_paired_64_wide_heads_are_the_per_head_softmax(decode):
    """Pages that keep two KV heads a 128-wide row, queries ``[q | 0]`` /
    ``[0 | q]``, the matching half of the output: the same heads as the
    per-head reference over pages of 64-wide heads holding the same K and V,
    and the textbook's softmax(q k^T / 8) v."""
    rng = np.random.RandomState(3)
    n_q, n_kv, d, ps, P, S = 8, 4, 64, 8, 6, 3
    lens = np.asarray([41, 9, 24], np.int32)
    q_lens = [1, 1, 1] if decode else [5, 9, 2]
    T = sum(q_lens)
    k = rng.randn(S * P * ps, n_kv, d).astype(np.float32)
    v = rng.randn(S * P * ps, n_kv, d).astype(np.float32)
    plain = np.stack([k, v], axis=2).reshape(S * P, ps, 2 * n_kv, d)       # K even, V odd
    cfg = dataclasses.replace(CFG, num_heads=n_q, num_kv_heads=n_kv, hidden_size=n_q * d)
    paired = np.asarray(model_mod._interleave_kv(
        jnp.asarray(k.reshape(-1, n_kv * d)), jnp.asarray(v.reshape(-1, n_kv * d)), cfg)
    ).reshape(S * P, ps, n_kv, 2 * d)
    assert paired.shape[1:] == cfg.kv_page_tail(ps) and paired.nbytes == plain.nbytes
    np.testing.assert_array_equal(paired[0, 0, 0], np.concatenate([k[0, 0], k[0, 1]]))
    np.testing.assert_array_equal(paired[0, 0, 3], np.concatenate([v[0, 2], v[0, 3]]))
    q = jnp.asarray(rng.randn(T, n_q, d), jnp.float32)
    tables = jnp.asarray(np.arange(S * P).reshape(S, P), jnp.int32)
    cu = None if decode else jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    args = (jnp.asarray(lens), tables, cu, jnp.asarray([S], jnp.int32))
    got = ragged_attention.paired_heads_attention(q, jnp.asarray(paired), *args, sm_scale=0.125)
    want = ragged_attention.ragged_paged_attention_ref(q, jnp.asarray(plain), *args,
                                                       sm_scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    t = 0
    for s in range(S):
        for i in range(q_lens[s]):
            n = lens[s] - q_lens[s] + i + 1
            ks, vs = k[s * P * ps: s * P * ps + n], v[s * P * ps: s * P * ps + n]
            for h in range(n_q):
                sc = ks[:, h // 2] @ np.asarray(q[t, h]) * 0.125
                p = np.exp(sc - sc.max())
                np.testing.assert_allclose(np.asarray(got[t, h]), (p / p.sum()) @ vs[:, h // 2],
                                           atol=2e-5)
            t += 1


DENSE64_FILE = {
    "model_type": "qwen2", "name": "tiny-64", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "attention_bias": False, "torch_dtype": "float32"}


def test_a_dense_model_of_64_wide_heads_is_served_in_pairs_and_keeps_its_options():
    """The pairing follows from the geometry alone (``head_dim`` 64, an even
    number of KV heads), whatever the model: a dense GQA model of such heads
    agrees with ITS reference through prefill, decode and a prefix hit, and
    keeps speculation."""
    dense = dataclasses.replace(tiny_model(), head_dim=64)
    assert dense.kv_head_pairs and not dense.hybrid and dense.kv_page_tail(8) == (8, 2, 128)
    for engine in ({}, {"spec_decode": "ngram"}):
        core = EngineCore(dense, tiny_engine(**engine), seed=5)
        assert core.cfg.kv_head_pairs and core.cache[0].shape[1:] == (8, 2, 128)
        got = check.score_request(core, DENSE64_FILE, BODY)
        verdict = check.compare(got["served"], got["scored"])
        assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
        assert got["served"][1]["cached_tokens"] == 32


@pytest.mark.parametrize("option", ["kv_dtype", "tp", "pp", "ring_prefill"])
def test_a_dense_model_of_64_wide_heads_keeps_what_the_pair_does_not_carry(option):
    """Int8 pages and meshes ran for such a model before there were pairs:
    they still do, on the unpaired page it had (``core._unpaired_where_not_
    carried``). The model a caller holds is not changed."""
    from dynamo_tpu.ops.ring_attention import sequence_parallel_mesh
    from dynamo_tpu.parallel.pipeline import make_pp_mesh
    from dynamo_tpu.parallel.sharding import make_mesh

    dense = dataclasses.replace(tiny_model(), head_dim=64)
    how = {"kv_dtype": lambda: dict(engine=tiny_engine(kv_dtype="int8")),
           "tp": lambda: dict(mesh=make_mesh(dp=1, tp=2)),
           "pp": lambda: dict(pp_mesh=make_pp_mesh(2)),
           "ring_prefill": lambda: dict(engine=tiny_engine(ring_prefill_threshold=32),
                                        sp_mesh=sequence_parallel_mesh(2))}[option]()
    core = EngineCore(dense, how.pop("engine", tiny_engine()), seed=5, **how)
    assert dense.kv_head_pairs and not core.cfg.kv_head_pairs
    assert core.cfg.kv_page_tail(8) == (8, 4, 64)
    assert dataclasses.replace(core.cfg, kv_pairing=True) == dense
    # against the same weights served in pairs on one device, which the test above holds
    # to the reference (the reference itself reads the one-device fused layout only)
    paired = EngineCore(dense, tiny_engine(), seed=5)
    want = check.run_probe(paired, BODY["prompt_ids"], 17, 5, "paired")
    first, repeat = (check.run_probe(core, BODY["prompt_ids"], 17, 5, tag) for tag in "ab")
    assert first["tokens"] == repeat["tokens"] == want["tokens"]
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] == 32
    np.testing.assert_allclose(first["top_lps"], want["top_lps"],
                               atol=0.05 if option == "kv_dtype" else TIGHT)


@pytest.mark.parametrize("backend,impl", [("tpu", "pallas"), ("cpu", "reference")])
def test_paired_heads_get_the_kernel_on_a_tpu_by_geometry(backend, impl, monkeypatch):
    """A 64-wide head alone would get the ``jnp`` reference ON a TPU; as a
    pair it is 128 lanes wide and its decode call, 32 query heads on 4 paired
    rows, gets the first-party kernel of grouped heads (the library kernel
    until PR 55; chosen at trace time; the kernel itself is not run here)."""
    monkeypatch.setattr(ragged_attention.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(ragged_attention, "_TRACED", ragged_attention._TRACED.copy())
    monkeypatch.setattr(ragged_attention, "_TRACED_IMPLS", dict(ragged_attention._TRACED_IMPLS))
    monkeypatch.setattr(ragged_attention, "pallas_ragged_attention",
                        lambda q, *a, **kw: jnp.zeros_like(q))
    before = ragged_attention.traced_calls()
    big = lfm2_24b_a2b_10l()
    pages = jnp.zeros((5, *big.kv_page_tail(32)), jnp.bfloat16)
    assert pages.shape == (5, 32, 8, 128)
    out = jax.eval_shape(lambda q: ragged_attention.paired_heads_attention(
        q, pages, jnp.ones(4, jnp.int32), jnp.zeros((4, 3), jnp.int32), None,
        jnp.asarray([4], jnp.int32), sm_scale=0.125), jnp.zeros((4, 32, 64), jnp.bfloat16))
    assert out.shape == (4, 32, 64)
    after = ragged_attention.traced_calls()
    assert {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)} == {
        ("decode", impl): 1}


# -- the router's bias -----------------------------------------------------------

def _sparse_layer(cfg=CFG, layer=2):
    return model_mod.layer_params(init_params(jax.random.PRNGKey(5), cfg), layer, cfg)


def test_the_bias_decides_the_choice_and_nothing_else():
    lp = _sparse_layer()
    y = jnp.asarray(np.random.RandomState(8).randn(512, 256), jnp.float32)
    bias = lp["expert_bias"]
    assert bias.dtype == jnp.float32 and 0.02 < float(jnp.std(bias)) < 0.1
    weights, chosen = model_mod.route_sigmoid(y, lp["w_router"], CFG, bias=bias)
    _, unbiased = model_mod.route_sigmoid(y, lp["w_router"], CFG)
    changed = int(jnp.sum(jnp.any(chosen != unbiased, axis=1)))
    assert 512 // 20 < changed < 512 * 3 // 4, changed       # a measurable share of tokens
    with jax.default_matmul_precision("highest"):
        sc = jax.nn.sigmoid(y @ lp["w_router"])
        want = reference.routing_weights(y, lp["w_router"], bias, top_k=2, scale=1.0,
                                         norm_eps=1e-6)
    assert bool(jnp.all((want > 0) == chosen)) and int(chosen.sum()) == 512 * 2
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want), atol=1e-5)
    picked = jnp.where(chosen, sc, 0.0)        # the weights are the plain scores, normalised
    np.testing.assert_allclose(
        np.asarray(weights), np.asarray(picked / (picked.sum(-1, keepdims=True) + 1e-6)),
        atol=1e-5)
    # without a bias the function is what it was (A.X-K1's path)
    plain = dataclasses.replace(CFG, router_bias=False)
    w2, c2 = model_mod.route_sigmoid(y, lp["w_router"], plain)
    assert bool(jnp.all(c2 == unbiased)) and float(jnp.abs(w2.sum(-1) - 1).max()) < 1e-4


def test_routed_down_projections_are_drawn_at_one_over_2k_where_no_shared_expert_carries():
    """The scale the chip's control chose (``model._routed_down_divisor``):
    1 / k beside a shared expert (A.X-K1's, unchanged), 1 / 2k where the
    routed terms are the whole layer; every other leaf at the fan-in scale."""
    from dynamo_tpu.engine.config import axk1_ep16, tiny_axk1

    assert model_mod._routed_down_divisor(lfm2_24b_a2b_10l()) == 8
    assert model_mod._routed_down_divisor(axk1_ep16()) == 8 == axk1_ep16().num_experts_per_tok
    for cfg in (CFG, tiny_axk1()):
        moe = init_params(jax.random.PRNGKey(3), cfg)["moe"]
        im = cfg.moe_intermediate_size
        down = float(jnp.std(jnp.stack(moe["w_down"]).astype(jnp.float32))) * im ** 0.5
        gate = float(jnp.std(jnp.stack(moe["w_gu"]).astype(jnp.float32))) * cfg.hidden_size ** 0.5
        assert abs(down * model_mod._routed_down_divisor(cfg) - 1) < 0.05 and abs(gate - 1) < 0.05
    assert model_mod._routed_down_divisor(CFG) == 2 * CFG.num_experts_per_tok


def test_the_experts_run_under_one_loop_however_many_are_held():
    """Every expert on every row (a decode step's bytes and operations do not
    follow the routing) under ONE ``fori_loop`` of a fixed number of turns,
    not a body an expert: 64 bodies a layer took 18 s to compile; at A.X-K1's
    12 the loop costs what the bodies cost (PERF.md section 6, PR 35)."""
    lp = _sparse_layer()
    y = jnp.asarray(np.random.RandomState(2).randn(24, 256), jnp.float32)
    looped = model_mod._shared_sparse_mlp(y, lp, CFG)
    text = str(jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, lp, CFG))(y))
    assert text.count("scan[") == 1 and "while[" not in text and "cond[" not in text
    weights, chosen = model_mod.route_sigmoid(y, lp["w_router"], CFG, bias=lp["expert_bias"])
    bodies = sum(weights[:, e, None] * model_mod._swiglu(y, lp["w_gu"][e], lp["w_down"][e])
                 for e in range(CFG.num_experts))
    np.testing.assert_allclose(np.asarray(looped), np.asarray(bodies), atol=2e-5)
    for held in (12, 64):     # both cells' counts divide into whole iterations
        assert held % math.gcd(model_mod._EXPERTS_LOOP_UNROLL, held) == 0


def _routed_layer(rs, held, h=32, im=16, total=None, dtype=jnp.float32):
    """(cfg, lp) of one sparse layer that holds the first ``held`` of
    ``total`` experts, 4 a token, bias on the choice, no shared expert."""
    total = total or held
    cfg = dataclasses.replace(CFG, hidden_size=h, moe_intermediate_size=im, num_experts=total,
                              num_experts_per_tok=4, num_heads=1, num_kv_heads=1, head_dim=h,
                              experts_held=None if held == total else (0, total // held))
    lp = {"w_router": jnp.asarray(rs.randn(h, total), jnp.float32),
          "expert_bias": jnp.asarray(rs.randn(total) * 0.05, jnp.float32),
          "w_gu": jnp.asarray(rs.randn(held, h, 2 * im) * 0.2, dtype),
          "w_down": jnp.asarray(rs.randn(held, im, h) * 0.2, dtype)}
    return cfg, lp


@pytest.mark.parametrize("held", [12, 16, 17, 64])
def test_wide_batches_follow_the_load_whatever_the_count_of_held_experts(held):
    """A batch wider than ``_EXPERTS_ALL_ROWS_MAX`` rows runs ONE grouped
    product over the chosen pairs sorted by expert (PR 36), at 12 held
    experts and at 64 alike: no ``switch`` over capacity tiers (PR 35's
    ``_experts_by_load`` halted the v5e at 64 and is gone), no limit on the
    count, the same sums as every expert on every row."""
    rs = np.random.RandomState(held)
    cfg, lp = _routed_layer(rs, held)
    y = jnp.asarray(rs.randn(512, 32), jnp.float32)
    got = model_mod._shared_sparse_mlp(y, lp, cfg)
    text = str(jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, lp, cfg))(y))
    # no branch and no loop over the experts; a chip that holds every expert unrolls the
    # combine's passes over a row's places too
    assert "cond[" not in text and "scan[" not in text and "while[" not in text
    assert text.count("ragged_dot_general[") == 2 and "_experts_grouped" in text
    weights, chosen = model_mod.route_sigmoid(y, lp["w_router"], cfg, bias=lp["expert_bias"])
    want = model_mod._experts_all_rows(y, weights, lp["w_gu"], lp["w_down"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the decode widths never follow the load, whatever is held
    narrow = str(jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, lp, cfg))(y[:128]))
    assert "cond[" not in narrow and "ragged_dot" not in narrow and narrow.count("scan[") == 1
    for gone in ("_experts_by_load", "_EXPERT_TIERS", "_EXPERTS_BY_LOAD_MAX_HELD"):
        assert not hasattr(model_mod, gone)
    assert lfm2_24b_a2b_10l().num_experts_held == 64


def _rigged(case: str, N: int, Eh: int, k: int, rs):
    """(chosen_held ``[N, Eh]``, w_held) of a routing no router would draw."""
    chosen = np.zeros((N, Eh), bool)
    if case == "one-expert":            # every row to expert 5 alone
        chosen[:, 5] = True
    else:
        idx = np.argsort(rs.rand(N, Eh), axis=1)[:, :k]
        chosen[np.arange(N)[:, None], idx] = True
    if case == "an-expert-with-no-row":
        chosen[:, 3] = False
        chosen[:, 0] = False
    if case == "a-padded-tail":         # ``row_valid`` false past row 300
        chosen[300:] = False
    if case == "rows-with-no-held-expert":
        chosen[::3] = False
    w = np.where(chosen, rs.rand(N, Eh) + 0.1, 0.0)
    return jnp.asarray(chosen), jnp.asarray(w, jnp.float32)


@pytest.mark.parametrize("case", [
    "even", "one-expert", "an-expert-with-no-row", "a-padded-tail", "rows-with-no-held-expert"])
def test_the_grouped_product_computes_every_chosen_pair_at_any_skew(case, monkeypatch):
    """Dropless: every row on ONE expert (a capacity would drop most of
    them), experts that get no row (empty groups, the first one among
    them), padding rows and rows whose experts this chip does not hold (they
    belong to no group): the sums of every expert on every row, with the
    sorted places in one slab and in many."""
    rs = np.random.RandomState(7)
    N, Eh, k, h, im = 384, 16, 4, 32, 16
    chosen, w_held = _rigged(case, N, Eh, k, rs)
    w_gu = jnp.asarray(rs.randn(Eh, h, 2 * im) * 0.2, jnp.float32)
    w_down = jnp.asarray(rs.randn(Eh, im, h) * 0.2, jnp.float32)
    y = jnp.asarray(rs.randn(N, h), jnp.float32)
    got = model_mod._experts_grouped(y, w_held, chosen, w_gu, w_down, k=k, impl="ragged_dot",
                                     all_held=case == "even")
    want = model_mod._experts_all_rows(y, w_held, w_gu, w_down)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if case == "a-padded-tail":
        assert not np.asarray(got[300:]).any()
    # the same a slab of the sorted places at a time: 1,536 places of 448 B in slabs of 40 kB
    monkeypatch.setattr(model_mod, "_GROUPED_SLAB_BYTES", 40_000)
    assert model_mod._slab_places(N * k, h, im, 4, 1) == 86 and -(-N * k // 86) == 18
    for all_held in (False, True):
        slabbed = jax.jit(functools.partial(
            model_mod._experts_grouped.__wrapped__, k=k, impl="ragged_dot", all_held=all_held))
        np.testing.assert_allclose(np.asarray(slabbed(y, w_held, chosen, w_gu, w_down)),
                                   np.asarray(want), atol=2e-5)
    text = str(jax.make_jaxpr(functools.partial(
        model_mod._experts_grouped.__wrapped__, k=k, impl="ragged_dot", all_held=True))(
        y, w_held, chosen, w_gu, w_down))
    assert "while[" in text and "cond[" not in text      # as many slabs as hold a pair
    monkeypatch.undo()
    # places padded to whole tiles, as the Pallas kernel needs them: the same sums
    rows, counts, place, weight = model_mod._sorted_pairs(chosen, w_held, k, 128)
    assert rows.shape == (N * k,) and N * k % 128 == 0
    assert model_mod._sorted_pairs(chosen, w_held, k, 1000)[0].shape == (2000,)
    pairs = int(chosen.sum())
    assert int(counts.sum()) == pairs
    # the permutation: a sorted place reads its pair's row, expert by expert
    experts = np.repeat(np.arange(Eh), np.asarray(counts))
    assert np.asarray(chosen)[np.asarray(rows[:pairs]), experts].all()
    live = np.asarray(place) < pairs
    assert live.sum() == pairs and (np.asarray(weight)[~live] == 0).all()
    assert (np.diff(np.where(live, np.asarray(place), 10 ** 6), axis=1) >= 0).all()


def test_the_sorted_places_go_a_slab_at_a_time_where_they_would_hold_a_gigabyte():
    """LFM2's widest wave is one slab (226 MB of sorted rows, results and
    activation); A.X-K1's 16,384 places of 62 KB go 4,096 at a time, as
    many slabs as hold a pair (a tenth of its places are live)."""
    assert model_mod._slab_places(2048 * 4, 2048, 1536, 2, 128) == 8192
    assert model_mod._slab_places(2048 * 8, 7168, 2048, 2, 128) == 4096
    assert model_mod._slab_places(1024 * 8, 7168, 2048, 2, 128) == 4096
    assert model_mod._slab_places(512 * 8, 7168, 2048, 2, 128) == 4096
    assert model_mod._slab_places(300 * 4, 256, 128, 4, 1) == 1200
    for places, h, im in ((8192, 2048, 1536), (4096, 7168, 2048)):
        assert places * (h * 2 + 2 * im * 4 + im * 2 + h * 4) <= model_mod._GROUPED_SLAB_BYTES


def test_the_combine_adds_a_tokens_terms_in_ascending_expert_order():
    """Given the products every expert on every row gives, the grouped
    layer's combine is BIT-equal to ``_experts_all_rows``: the same terms
    in the same order (its other terms are exact zeros), so a token's sum
    has one order in a wave and in a decode step."""
    from dynamo_tpu.ops import grouped_matmul

    rs = np.random.RandomState(11)
    N, Eh, k, h, im = 64, 8, 4, 16, 8
    chosen, w_held = _rigged("rows-with-no-held-expert", N, Eh, k, rs)
    w_gu = jnp.asarray(rs.randn(Eh, h, 2 * im), jnp.float32)
    w_down = jnp.asarray(rs.randn(Eh, im, h), jnp.float32)
    y = jnp.asarray(rs.randn(N, h), jnp.float32)
    rows, counts, place, weight = model_mod._sorted_pairs(chosen, w_held, k, 1)
    # the products in sorted order, each by the call every-expert-on-every-row makes
    per_expert = [model_mod._swiglu(y, w_gu[e], w_down[e]) for e in range(Eh)]
    experts = np.repeat(np.arange(Eh), np.asarray(counts))
    sorted_y = jnp.stack([per_expert[e][r] for e, r in zip(experts, np.asarray(rows))])
    combine = lambda full: jax.jit(functools.partial(
        grouped_matmul.combine, jnp.zeros((N, h), jnp.float32), full=full))
    got = combine(False)(sorted_y, place, weight)
    want = jax.jit(model_mod._experts_all_rows)(y, w_held, w_gu, w_down)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and another order is another sum: the order is what is pinned
    np.testing.assert_array_equal(np.asarray(combine(True)(sorted_y, place, weight)), np.asarray(want))
    flipped = combine(True)(sorted_y, place[:, ::-1], weight[:, ::-1])
    # a chip that holds few experts: the passes no row needs are skipped, the digits stay
    few = jnp.where(jnp.arange(N)[:, None] % 7 == 0, place, N * k)
    skipped = combine(False)(sorted_y, few.at[:, 1:].set(N * k), weight)
    first = jnp.where(few[:, :1] < sorted_y.shape[0],
                      weight[:, :1] * sorted_y[jnp.minimum(few[:, 0], sorted_y.shape[0] - 1)], 0.0)
    np.testing.assert_array_equal(np.asarray(skipped), np.asarray(0.0 + first))
    loop = str(jax.make_jaxpr(combine(False))(sorted_y, place, weight))
    unrolled = str(jax.make_jaxpr(combine(True))(sorted_y, place, weight))
    assert "while[" in loop and "while[" not in unrolled and "cond[" not in loop + unrolled


def test_the_fifth_count_is_the_rows_the_expert_products_ran_on():
    """Every held expert on every row: ``held / k`` = 16 x the pairs held
    for LFM2's 64 and 4. Grouped: the rows of the tiles that hold a pair,
    ``pairs <= rows < pairs + held x tile`` (the CPU's tile is one row)."""
    from dynamo_tpu.ops import grouped_matmul

    rs = np.random.RandomState(3)
    cfg, lp = _routed_layer(rs, 64)
    for rows in (128, 512):
        stats: list = []
        model_mod._shared_sparse_mlp(jnp.asarray(rs.randn(rows, 32), jnp.float32), lp, cfg,
                                     expert_stats=stats)
        touched, steps, pairs, routed, computed = (int(n) for n in stats[0])
        assert pairs == routed == rows * 4 and steps == 1 and touched <= 64
        assert computed == (16 * pairs if rows == 128 else pairs)
    counts = jnp.asarray(rs.randint(0, 300, size=64), jnp.int32).at[5].set(0)
    for tile in (1, 8, 128):
        visited = int(grouped_matmul.rows_visited(counts, tile))
        assert int(counts.sum()) <= visited < int(counts.sum()) + 64 * tile and visited % tile == 0
    # one group over three tiles' boundaries, one inside a tile, one empty
    assert int(grouped_matmul.rows_visited(jnp.asarray([130, 20, 0, 1]), 128)) == (2 + 1 + 0 + 1) * 128


def test_the_traced_calls_counter_and_the_annotation_name_the_path(served):
    """``dynamo_engine_expert_calls_traced_total{shape, impl}``, counted at
    trace time where the path is chosen, and ``experts`` on the
    ``engine/dispatch`` annotation: a wave (more rows than every expert on
    every row serves) the grouped product, a step every row."""
    from dynamo_tpu.ops import grouped_matmul
    from dynamo_tpu.runtime.status_server import _EngineCounters

    core, _ = served
    before = grouped_matmul.traced_calls()
    assert before[("step", "all_rows")] >= 4          # the probe's programs: 4 sparse layers each
    lp = _sparse_layer()
    for rows, key in ((300, ("wave", "grouped/ragged_dot")), (256, ("step", "all_rows"))):
        jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, lp, CFG))(
            jnp.zeros((rows, 256), jnp.float32))
        after = grouped_matmul.traced_calls()
        assert {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)} == {key: 1}
        before = after
    assert model_mod.expert_call_shape(256) == "step" and model_mod.expert_call_shape(257) == "wave"
    assert core._experts_traced("prefill", 512) == {"experts": "grouped/ragged_dot"}
    assert core._experts_traced("prefill", 64) == {"experts": "all_rows"}
    assert core._experts_traced("megastep", 1024) == {"experts": "all_rows"}
    assert make_core(tiny_model())._experts_traced("prefill", 512) == {}
    marks = []
    core.clock.mark, mark = (lambda *a, **kw: marks.append(kw)), core.clock.mark
    try:
        core._mark_dispatch("prefill", 1, 1, 1, 300, 512)
    finally:
        core.clock.mark = mark
    assert marks[0]["experts"] == "grouped/ragged_dot" and marks[0]["padded"] == 512
    families = {f.name: f for f in _EngineCounters(lambda: {}, core.scheduler_stats).collect()}
    traced = {(s.labels["shape"], s.labels["impl"]): s.value
              for s in families["dynamo_engine_expert_calls_traced"].samples}
    assert traced[("wave", "grouped/ragged_dot")] >= 1 and traced[("step", "all_rows")] >= 4
    computed = {s.labels["phase"]: s.value
                for s in families["dynamo_engine_expert_rows_computed"].samples}
    held = {s.labels["phase"]: s.value
            for s in families["dynamo_engine_expert_pairs_held"].samples}
    # 8 of 8 experts held, 2 a token: every row runs 4 x the pairs it holds
    assert computed["decode"] >= 4 * held["decode"] > 0 and computed["prefill"] >= held["prefill"] > 0


# -- faults --------------------------------------------------------------------

def _fault_bias_dropped_from_the_choice(mp):
    real = reference.routing_weights
    mp.setattr(reference, "routing_weights",
               lambda y, w, bias, **kw: real(y, w, jnp.zeros_like(bias), **kw))


def _fault_bias_left_in_the_weights(mp):
    def routing(y, w_router, bias, *, top_k, scale, norm_eps):
        T, E = y.shape[0], w_router.shape[1]
        sc = jax.nn.sigmoid(y @ w_router) + bias
        vals, idx = jax.lax.top_k(sc, top_k)
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + norm_eps) * scale
        return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], idx].set(vals)

    mp.setattr(reference, "routing_weights", routing)


def _fault_softmax_for_sigmoid(mp):
    mp.setattr(reference.jax.nn, "sigmoid", lambda z: jax.nn.softmax(z, axis=-1))


def _fault_qk_norm_dropped(mp):
    real = reference.rms_norm
    mp.setattr(reference, "rms_norm",
               lambda x, w, eps: x if x.ndim == 3 else real(x, w, eps))


def _fault_qk_norm_after_rope(mp):
    real_norm, real_rope = reference.rms_norm, reference.rope
    pending = {}

    def norm(x, w, eps):         # the head norms are put off ...
        if x.ndim != 3:
            return real_norm(x, w, eps)
        pending[x.shape[1]] = w
        return x

    def rope(x, pos, theta):     # ... until after the rotation
        return real_norm(real_rope(x, pos, theta), pending[x.shape[1]], 1e-5)

    mp.setattr(reference, "rms_norm", norm)
    mp.setattr(reference, "rope", rope)


def _patched_layout(mp, change):
    real = arch.published_layout

    def layout(params, l, mf, mlp_blocks=8):
        return change(l, *real(params, l, mf, mlp_blocks), params, mf)

    mp.setattr(arch, "published_layout", layout)


def _fault_tap_order_reversed(mp):
    _patched_layout(mp, lambda l, kind, w, norm, mlp, *_: (
        kind, {**w, "conv_w": w["conv_w"][::-1]} if kind == "conv" else w, norm, mlp))


def _fault_c_gate_dropped(mp):
    def short_conv(x, w, *, eps):
        T, h = x.shape
        y = reference.rms_norm(x, w["operator_norm"], eps)
        gate_b, _, z = jnp.split(y @ w["in_proj"], 3, axis=-1)
        padded = jnp.concatenate([jnp.zeros((2, h), x.dtype), gate_b * z], axis=0)
        c = sum(w["conv_w"][j] * padded[j:j + T] for j in range(3))
        return x + c @ w["out_proj"]

    mp.setattr(reference, "short_conv", short_conv)


def _fault_second_dense_layer_made_sparse(mp):
    real = arch.published_layout

    def change(l, kind, w, norm, mlp, params, mf):
        return kind, w, norm, (real(params, 2, mf)[3] if l == 1 else mlp)

    _patched_layout(mp, change)


@pytest.mark.parametrize("fault", [
    "bias_dropped_from_the_choice", "bias_left_in_the_weights", "softmax_for_sigmoid",
    "qk_norm_dropped", "qk_norm_after_rope", "tap_order_reversed", "c_gate_dropped",
    "second_dense_layer_made_sparse"])
def test_a_fault_in_the_layers_is_caught(served, fault, monkeypatch):
    """The reference with a fault in it parts from the engine by far more
    than the 1e-4 the tests hold the two to."""
    core, got = served
    globals()[f"_fault_{fault}"](monkeypatch)
    seqs = [check.score_probe(FILE, core.params, BODY["prompt_ids"], probe, vocab_chunks=3)
            for probe in got["served"]]
    verdict = check.compare(got["served"], {"sequences": seqs})
    assert verdict["max_abs_diff"] > 100 * TIGHT, verdict


def test_state_taken_from_the_wrong_block_is_caught(monkeypatch):
    """A fault in the PROGRAM: the first rows of a block read the page of
    the block two back. Whole-prompt waves do not notice (their rows come
    from the wave); the decode rows that start a block do."""
    real = model_mod.conv_state_rows
    monkeypatch.setattr(
        model_mod, "conv_state_rows",
        lambda state, tables, pos, slots, bs: real(
            state, jnp.roll(tables, 1, axis=1), pos, slots, bs))
    core = make_core(dataclasses.replace(CFG, name="tiny-lfm2-wrong-block"))
    verdict, _ = held_to_reference(core)
    assert not verdict["ok"] and verdict["max_abs_diff"] > check.LOGPROB_ATOL, verdict


# -- refusals and counts ---------------------------------------------------------

@pytest.mark.parametrize("option,build", [
    ("kv_dtype", lambda: make_core(kv_dtype="int8")),
    ("host_kv_blocks", lambda: make_core(host_kv_blocks=8)),
    ("disk_kv_dir", lambda: make_core(host_kv_blocks=0, disk_kv_dir="/nowhere")),
    ("tp", lambda: EngineCore(CFG, tiny_engine(), seed=5, mesh=object())),
    ("pp", lambda: EngineCore(CFG, tiny_engine(), seed=5, pp_mesh=object())),
    ("ring_prefill", lambda: EngineCore(CFG, tiny_engine(), seed=5, sp_mesh=object())),
    ("ring_prefill", lambda: make_core(ring_prefill_threshold=64)),
    ("spec_decode", lambda: make_core(spec_decode="ngram")),
], ids=["int8-kv", "host-tier", "disk-tier", "tp", "pp", "sp-mesh", "ring-threshold",
        "speculation"])
def test_an_option_the_hybrid_cache_does_not_carry_is_refused_at_start_up(option, build):
    with pytest.raises(UnsupportedModelOption, match=option) as e:
        build()
    assert e.value.option == option and "tiny-lfm2" in str(e.value)
    assert isinstance(e.value, NotImplementedError)


def test_a_block_does_not_leave_the_device(served):
    core, _ = served
    for option, leave in (
            ("disagg", lambda: core.kv_page_shape),
            ("disagg", lambda: core.export_descriptors("nobody")),
            ("disagg", lambda: core.import_blocks([])),
            ("disagg", lambda: core.import_blocks_direct(make_core(), "nobody")),
            ("peer_kv", lambda: core.read_cached_pages([1, 2]))):
        with pytest.raises(UnsupportedModelOption, match=option) as e:
            leave()
        assert e.value.option == option and "two shapes" in str(e.value)


def test_int8_weights_a_mesh_rule_and_paired_int8_pages_are_refused_by_name():
    from dynamo_tpu.backends.jax.main import build_engine
    from dynamo_tpu.parallel.sharding import param_partition_specs

    with pytest.raises(UnsupportedModelOption, match="quant") as e:
        build_engine("tiny-lfm2", {"num_kv_blocks": 16, "block_size": 8}, quant="int8")
    assert e.value.option == "quant"
    with pytest.raises(NotImplementedError, match="tiny-lfm2"):
        model_mod.init_params_quantized(jax.random.PRNGKey(0), CFG)
    with pytest.raises(NotImplementedError, match="unquantised"):
        CFG.quantized_param_bytes()
    with pytest.raises(NotImplementedError, match="conv state pages or paired KV heads"):
        init_cache(CFG, tiny_engine(kv_dtype="int8"))
    with pytest.raises(UnsupportedModelOption, match="tp"):
        param_partition_specs(CFG, 2)


@pytest.mark.parametrize("change,error", [
    ({"layer_types": ("conv",) * 5}, ValueError),
    ({"layer_types": ("conv", "conv", "linear_attention", "conv", "conv", "conv")}, ValueError),
    ({"conv_L_cache": 1}, ValueError),
    ({"conv_bias": True}, NotImplementedError),
    ({"attn_qkv_bias": True}, NotImplementedError),
    ({"sandwich_norm": True}, NotImplementedError),
    ({"router_scoring": "softmax", "first_dense_layers": 0, "moe_intermediate_size": 0,
      "router_bias": False, "router_norm_eps": 1e-20}, NotImplementedError),
], ids=["too-few-kinds", "unknown-kind", "one-tap", "conv-bias", "qkv-bias", "sandwich",
        "mixtral-mlp"])
def test_a_field_that_does_not_apply_raises(change, error):
    with pytest.raises(error):
        dataclasses.replace(CFG, **change)


def test_the_conv_fields_mean_nothing_to_a_model_without_conv_layers():
    for stray in ({"conv_L_cache": 3}, {"conv_bias": True}, {"router_bias": True},
                  {"router_norm_eps": 1e-6}):
        with pytest.raises(ValueError):
            dataclasses.replace(tiny_model(), **stray)
    with pytest.raises(ValueError, match="multiple"):
        CFG.kv_page_tail(7, "conv")


def test_counts_of_the_published_size_by_hand():
    a = lfm2_24b_a2b_10l()
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1536
    sparse = 2048 * 64 + 64 + 64 * expert
    dense = 3 * 2048 * 11776
    assert (conv, attn, expert, dense) == (16_783_360, 10_485_888, 9_437_184, 72_351_744)
    total = 65536 * 2048 + 8 * conv + 2 * attn + 10 * 2 * 2048 + 2 * dense + 8 * sparse + 2048
    assert a.param_bytes() == 2 * total == 10_534_180_352          # 10.53 GB, tied
    assert dataclasses.replace(a, tie_embeddings=False).param_bytes() == 2 * (
        total + 65536 * 2048)                                       # 10.80 GB untied
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), a))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) - 1   # fuse_tp
    assert n == total
    assert [x.shape for x in params["moe"]["w_gu"]] == [(64, 2048, 3072)] * 8
    assert params["moe"]["expert_bias"].shape == (8, 64)
    assert params["moe"]["expert_bias"].dtype == jnp.float32
    assert params["dense_mlp"]["wgu"].shape == (2, 2048, 2 * 11776)
    assert params["conv"]["in_proj"].shape == (8, 2048, 6144)
    assert params["conv"]["conv_w"].shape == (8, 3, 2048)
    assert params["attn"]["wqkv"].shape == (2, 2048, 2048 + 2 * 512)
    assert params["attn"]["q_layernorm"].shape == (2, 64) and "lm_head" not in params
    assert set(params["layers"]) == {"attn_norm", "mlp_norm"}
    eng = EngineConfig(num_kv_blocks=16384, block_size=32)
    shapes = [s.shape for s in jax.eval_shape(lambda: init_cache(a, eng))]
    assert shapes == [(16385, 2, 16, 128) if k == "conv" else (16385, 32, 8, 128)
                      for k in a.layer_types]
    # 2,048 B a token an attention layer (no padded heads), 8,192 B a block a conv layer
    assert 32 * 8 * 128 * 2 == 32 * 2048 and a.kv_unit_values * 2 == 2048
    assert 2 * 16 * 128 * 2 == 8192 and a.state_bytes_per_block() == 8 * 8192
    per_block = 2 * 65536 + 8 * 8192
    assert per_block == 192 * 1024 and sum(int(np.prod(s[1:])) * 2 for s in shapes) == per_block
    core_bytes = a.num_cache_layers * a.kv_unit_values * 2
    assert core_bytes == 4096                    # dynamo_engine_kv_bytes_per_token
    whole = dataclasses.replace(
        a, num_layers=40,
        layer_types=("conv", "conv") + 9 * ("full_attention", "conv", "conv", "conv")
        + ("full_attention", "conv"))
    assert 2.3e10 < whole.param_bytes() / 2 < 2.45e10            # "24B"
    assert whole.cache_layers("attention") == 10 and whole.cache_layers("conv") == 30


# -- the checkpoint's names ---------------------------------------------------

def test_loader_takes_the_checkpoints_names(tmp_path, ragged_step):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    h, v, d, E, im, inter = 256, 384, 64, 8, 64, 320
    rng = np.random.RandomState(11)
    mat = lambda out, inp: (rng.randn(out, inp) * inp ** -0.5).astype(np.float32)  # noqa: E731
    norm = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.embedding_norm.weight": norm(h)}
    for l, kind in enumerate(CFG.layer_types):
        p = f"model.layers.{l}."
        sd[p + "operator_norm.weight"] = norm(h)
        sd[p + "ffn_norm.weight"] = norm(h)
        if kind == "conv":
            sd[p + "conv.in_proj.weight"] = mat(3 * h, h)
            sd[p + "conv.conv.weight"] = (rng.randn(h, 1, 3) * 3 ** -0.5).astype(np.float32)
            sd[p + "conv.out_proj.weight"] = mat(h, h)
        else:
            for name, out in (("q_proj", 4 * d), ("k_proj", 2 * d), ("v_proj", 2 * d)):
                sd[p + f"self_attn.{name}.weight"] = mat(out, h)
            sd[p + "self_attn.out_proj.weight"] = mat(h, 4 * d)
            sd[p + "self_attn.q_layernorm.weight"] = norm(d)
            sd[p + "self_attn.k_layernorm.weight"] = norm(d)
        if l < 2:
            ffns = {"feed_forward": inter}
        else:
            sd[p + "feed_forward.gate.weight"] = mat(E, h)
            sd[p + "feed_forward.expert_bias"] = (0.05 * rng.randn(E)).astype(np.float32)
            ffns = {f"feed_forward.experts.{e}": im for e in range(E)}
        for prefix, width in ffns.items():
            sd[p + prefix + ".w1.weight"] = mat(width, h)
            sd[p + prefix + ".w3.weight"] = mat(width, h)
            sd[p + prefix + ".w2.weight"] = mat(h, width) / (2 if width == im else 1)
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items()
          if k not in ("name", "torch_dtype", "serve", "source", "deployment", "reduced",
                       "assumed")}
    (tmp_path / "config.json").write_text(json.dumps(hf))

    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32)
    assert cfg == dataclasses.replace(CFG, name="lfm2_moe", dtype="bfloat16")
    assert [a.shape for a in loaded["moe"]["w_gu"]] == [(E, h, 2 * im)] * 4
    np.testing.assert_array_equal(          # expert 5 of layer 3 is expert 5 of sparse layer 1
        loaded["moe"]["w_gu"][1][5, :, :im],
        sd["model.layers.3.feed_forward.experts.5.w1.weight"].T)
    np.testing.assert_array_equal(
        loaded["moe"]["w_gu"][0][2, :, im:],
        sd["model.layers.2.feed_forward.experts.2.w3.weight"].T)
    np.testing.assert_array_equal(
        loaded["moe"]["w_down"][3][7], sd["model.layers.5.feed_forward.experts.7.w2.weight"].T)
    np.testing.assert_array_equal(loaded["moe"]["expert_bias"][1],
                                  sd["model.layers.3.feed_forward.expert_bias"])
    np.testing.assert_array_equal(      # conv layer 4 is the fourth conv layer; taps [L, h]
        loaded["conv"]["conv_w"][3], sd["model.layers.4.conv.conv.weight"][:, 0, :].T)
    np.testing.assert_array_equal(loaded["conv"]["in_proj"][0],
                                  sd["model.layers.0.conv.in_proj.weight"].T)
    np.testing.assert_array_equal(loaded["attn"]["wqkv"][0][:, 4 * d: 6 * d],
                                  sd["model.layers.2.self_attn.k_proj.weight"].T)
    np.testing.assert_array_equal(loaded["attn"]["k_layernorm"][0],
                                  sd["model.layers.2.self_attn.k_layernorm.weight"])
    np.testing.assert_array_equal(loaded["dense_mlp"]["wgu"][1][:, inter:],
                                  sd["model.layers.1.feed_forward.w3.weight"].T)
    np.testing.assert_array_equal(loaded["final_norm"], sd["model.embedding_norm.weight"])
    assert set(loaded) == {"embed", "final_norm", "fuse_tp", "layers", "conv", "attn", "moe",
                           "dense_mlp"}

    loaded = jax.device_put(loaded)
    ids = PROMPT[:24]
    (hidden,), _ = ragged_step(loaded, init_cache(CFG, ENG), [(0, ids, 0)], 64)
    np.testing.assert_allclose(_logits(loaded, hidden), _reference_hidden_logits(loaded, ids),
                               atol=5e-5)
