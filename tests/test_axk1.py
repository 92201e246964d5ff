"""A.X-K1's layer (``ModelConfig.attention == "mla"``, ``router_scoring ==
"sigmoid"``) at a tiny size on the CPU in float32: latent attention with
YaRN over a latent page, one dense layer then two sigmoid-routed ones of
which this chip holds a quarter of the experts. The engine is held to the
plain reference (``chipbench/reference/axk1.py``) through prefill, decode
through the latent cache, a megastep, a prefix hit, a preemption and every
way a block leaves the device and comes back; the shares add up to the
uncut layer, nothing is dropped at any skew, faults must fail the
comparison, and every option the latent page does not carry is refused.
The attention calls alone (absorbed against expanded, the first-party
decode kernel, the ragged call) are in ``tests/test_axk1_attention.py``
(split off in PR 45: ROADMAP D17)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import axk1 as arch
from chipbench.reference import axk1 as reference
from chipbench.reference import check
from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    PRESETS,
    UnsupportedModelOption,
    axk1_ep16,
    tiny_axk1,
)
from dynamo_tpu.engine.model import forward_hidden, init_cache, init_params
from dynamo_tpu.ops import ragged_attention
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_axk1()
SCALING = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
           "original_max_position_embeddings": 64, "type": "yarn"}
# what chipbench's check reads of a configuration file, for this preset
FILE = {"model_type": "axk1", "name": "tiny-axk1", "vocab_size": 384, "hidden_size": 64,
        "intermediate_size": 160, "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "torch_dtype": "float32", "attention_bias": False,
        "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rope_scaling": SCALING, "first_k_dense_replace": 1,
        "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 4,
        "scoring_func": "sigmoid", "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1, "topk_method": "none",
        "experts_held": {"rank": 0, "of": 4, "published": 16}}
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=40)]
BODY = {"prompt_ids": PROMPT, "max_tokens": 17, "top": 5}
TIGHT = 1e-4   # float32 on both sides: the readings are 1e-6 to 1e-5


def make_core(cfg=CFG, **engine) -> EngineCore:
    return EngineCore(cfg, tiny_engine(**engine), seed=5)


def held_to_reference(core, got=None, cfg=FILE):
    got = got or check.score_request(core, cfg, BODY)
    return check.compare(got["served"], got["scored"]), got


def test_the_preset_is_the_file():
    from chipbench.configs import model_fields

    assert ModelConfig(**model_fields(dict(FILE))) == CFG
    assert CFG.latent and CFG.shared_sparse and CFG.kv_page_tail(8) == (20, 16)
    assert CFG.experts_held_range == (0, 4) and CFG.num_experts == 16
    assert "tiny-axk1" in PRESETS and "a.x-k1-ep16" in PRESETS


@pytest.fixture(scope="module")
def served():
    """The default engine (megastep k = 8, the one-step-ahead loop) sent
    the probe twice: prefill, absorbed decode over latent pages, a prefix hit."""
    core = make_core()
    return core, check.score_request(core, FILE, BODY)


def test_prefill_decode_megastep_and_prefix_hit_agree_with_reference(served):
    core, got = served
    assert core.engine.megastep == 8 and core.pipelined
    verdict, _ = held_to_reference(core, got)
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert verdict["compared"] == 2 * 17 * 5 and verdict["argmax_mismatches"] == 0
    first, repeat = got["served"]
    assert first["tokens"] == repeat["tokens"] and len(first["tokens"]) == 17
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] == 32


def test_each_program_traced_its_own_attention_path(served):
    """Prefill waves expand the heads, decode steps absorb: pinned by the
    counter /metrics exports (dynamo_engine_attention_calls_traced_total)."""
    core, _ = served
    calls = ragged_attention.traced_calls()
    assert calls[("latent-ragged", "jnp")] >= CFG.num_layers
    assert calls[("latent-decode", "jnp")] >= CFG.num_layers
    assert ragged_attention.traced_impl("latent-decode") == "jnp"
    before = dict(calls)
    ids = jnp.asarray(PROMPT[:8], jnp.int32)
    eng = core.engine
    table = jnp.zeros((8, eng.max_blocks_per_seq), jnp.int32)
    jax.make_jaxpr(lambda: model_mod.decode_tokens(
        core.params, init_cache(CFG, eng), ids, table, jnp.arange(8, dtype=jnp.int32),
        jnp.ones(8, bool), CFG, eng))()
    after = ragged_attention.traced_calls()
    assert after[("latent-decode", "jnp")] == before[("latent-decode", "jnp")] + CFG.num_layers
    assert after[("latent-ragged", "jnp")] == before[("latent-ragged", "jnp")]
    assert ("decode", "reference") not in after or after == {**before, **after}


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


def test_a_prefix_hit_gives_the_first_sends_digits_in_bfloat16():
    """The benchmark's ``correct`` wants the second send of a probe (a prefix
    hit) to choose the first send's tokens. In bfloat16 that holds only if a
    prompt's logits do not depend on how many of its blocks were found in the
    cache: the cached keys are expanded by the fresh keys' arithmetic and the
    two parts of the softmax are weighted in exact powers of two (v5e, PR 32:
    with an absorbed cached part the two sends differed by 0.02 and a greedy
    near-tie fell the other way)."""
    core = make_core(dataclasses.replace(CFG, dtype="bfloat16"))
    first, again = (check.run_probe(core, PROMPT, 17, 5, tag) for tag in ("a", "b"))
    assert again["cached_tokens"] >= 32 and first["tokens"] == again["tokens"]
    assert first["top_ids"] == again["top_ids"]
    assert np.abs(np.asarray(first["top_lps"]) - np.asarray(again["top_lps"])).max() < 1e-4


def test_preempt_and_resume_gives_the_unpressed_stream():
    def run(blocks):
        core = make_core(num_kv_blocks=blocks, max_model_len=64)
        seqs = [core.add_request(_req(list(range(1 + 20 * i, 17 + 20 * i)), f"s{i}",
                                      max_tokens=33, ignore_eos=True)) for i in range(3)]
        done, _ = run_to_completion(core, seqs, max_steps=4000)
        return done, core

    roomy, _ = run(64)
    tight, core = run(14)     # three streams of 7 blocks each do not fit together
    assert core.sched_stats["preemptions"] >= 1
    assert tight == roomy and all(len(v) == 33 for v in tight.values())


# -- the share ---------------------------------------------------------------

def _sparse_layer(cfg, seed=9):
    """Layer 1's leaves (the first sparse one) of ``cfg``'s seeded tree."""
    return model_mod.layer_params(init_params(jax.random.PRNGKey(seed), cfg), 1, cfg)


def _reference_mlp(y, lp_all, cfg, held):
    """The reference's sparse MLP on ``y`` with ALL experts' weights at
    hand (``lp_all``: the uncut layer's leaves), adding those in ``held``."""
    im = cfg.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        w = reference.routing_weights(
            y, lp_all["w_router"], n_group=cfg.n_group, topk_group=cfg.topk_group,
            top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor)
        out = jnp.zeros_like(y)
        for e in range(*held):
            out = out + w[:, e, None] * reference.mlp_block(
                y, lp_all["w_gu"][e, :, :im], lp_all["w_gu"][e, :, im:], lp_all["w_down"][e])
        shared = reference.mlp_block(
            y, lp_all["shared_wgu"][:, :im], lp_all["shared_wgu"][:, im:],
            lp_all["shared_down"])
    return out, shared


@pytest.mark.parametrize("rows", [24, 300], ids=["all-rows", "grouped"])
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up(rows):
    """Four chips hold four experts each of the sixteen: the routed parts
    of all four shares, with the shared expert counted once, are the
    uncut reference layer; so is the uncut layer as the program runs it."""
    whole = dataclasses.replace(CFG, experts_held=None)
    lp_all = _sparse_layer(whole)
    y = jnp.asarray(np.random.RandomState(2).randn(rows, 64), jnp.float32)
    routed, shared = _reference_mlp(y, lp_all, whole, (0, 16))
    total = jnp.zeros_like(y)
    for rank in range(4):
        cfg = dataclasses.replace(CFG, experts_held=(rank, 4))
        lp = _sparse_layer(cfg)
        np.testing.assert_array_equal(            # a share of one seed is a share of one model
            np.asarray(lp["w_gu"]), np.asarray(lp_all["w_gu"][4 * rank: 4 * rank + 4]))
        mine = model_mod._shared_sparse_mlp(y, lp, cfg)
        want, _ = _reference_mlp(y, lp_all, whole, (4 * rank, 4 * rank + 4))
        np.testing.assert_allclose(np.asarray(mine - shared), np.asarray(want), atol=2e-5)
        total = total + (mine - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(routed + shared),
                               atol=5e-5)
    uncut = model_mod._shared_sparse_mlp(y, lp_all, whole)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(routed + shared), atol=5e-5)
    assert float(np.abs(np.asarray(routed)).mean()) > 0.05      # the routed part is no rounding


@pytest.mark.parametrize("rows", [40, 300], ids=["all-rows", "grouped"])
def test_nothing_is_dropped_when_every_token_goes_to_one_held_expert(rows):
    """A router rigged to score expert 2 (held) highest for every token
    (and expert 3 beside it, so that their group is always kept): every
    (token, expert 2) pair is computed, where a capacity of ``ceil(N k f /
    E)`` = N/2 would have dropped half of them."""
    lp = dict(_sparse_layer(CFG))
    y = jnp.abs(jnp.asarray(np.random.RandomState(3).randn(rows, 64), jnp.float32)) + 0.1
    rig = np.asarray(lp["w_router"]).copy()
    rig[:, 2], rig[:, 3] = 1.0, 0.9      # y > 0: their logits are the largest by far
    lp["w_router"] = jnp.asarray(rig)
    stats: list = []
    got = model_mod._shared_sparse_mlp(y, lp, CFG, expert_stats=stats)
    touched, steps, pairs_held, pairs_routed, rows_computed = (int(n) for n in stats[0])
    assert steps == 1 and pairs_routed == rows * 4 and touched >= 1
    # 4 held experts on every row, or (a wave) the rows of the pairs held and no others
    assert rows_computed == (4 * rows if rows <= model_mod._EXPERTS_ALL_ROWS_MAX else pairs_held)
    _, chosen = model_mod.route_sigmoid(y, lp["w_router"], CFG)
    assert bool(chosen[:, 2].all()) and pairs_held == int(chosen[:, :4].sum()) >= rows
    assert model_mod._moe_capacity(rows, dataclasses.replace(
        CFG, router_scoring="softmax", n_group=1, topk_group=1, first_dense_layers=0,
        moe_intermediate_size=0, routed_scaling_factor=1.0, num_shared_experts=0,
        experts_held=None)) == rows // 2
    im = CFG.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        w = reference.routing_weights(y, lp["w_router"], n_group=4, topk_group=2, top_k=4,
                                      scale=2.5)
        want = reference.mlp_block(y, lp["shared_wgu"][:, :im], lp["shared_wgu"][:, im:],
                                   lp["shared_down"])
        for e in range(4):
            want = want + w[:, e, None] * reference.mlp_block(
                y, lp["w_gu"][e, :, :im], lp["w_gu"][e, :, im:], lp["w_down"][e])
    assert float(w[:, 2].min()) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_padding_rows_route_nowhere_and_are_not_counted():
    lp = _sparse_layer(CFG)
    y = jnp.asarray(np.random.RandomState(5).randn(300, 64), jnp.float32)
    valid = jnp.arange(300) < 120
    stats: list = []
    got = model_mod._shared_sparse_mlp(y, lp, CFG, row_valid=valid, expert_stats=stats)
    alone = model_mod._shared_sparse_mlp(y[:120], lp, CFG)
    np.testing.assert_allclose(np.asarray(got[:120]), np.asarray(alone), atol=2e-5)
    assert int(stats[0][3]) == 120 * 4 and 0 < int(stats[0][2]) < 120 * 4


def test_a_decode_step_runs_every_held_expert_whatever_the_router_favours():
    """The decode widths stay under the all-rows limit, and that path's
    program has no branch on the routing: the same bytes and operations
    for every seed (PERF.md, PR 32)."""
    assert axk1_ep16().num_experts_held == 12
    assert model_mod._EXPERTS_ALL_ROWS_MAX >= 128
    lp = _sparse_layer(CFG)
    y = jnp.zeros((128, 64), jnp.float32)
    text = str(jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, lp, CFG))(y))
    # one loop of a fixed number of turns over the held experts (PR 35), no test of a value
    assert "cond[" not in text and "while[" not in text and text.count("scan[") == 1
    # a wave follows the load through ONE grouped product (PR 36), and says so
    from dynamo_tpu.ops import grouped_matmul

    before = grouped_matmul.traced_calls()
    wave = str(jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, lp, CFG))(
        jnp.zeros((512, 64), jnp.float32)))
    after = grouped_matmul.traced_calls()
    assert {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)} == {
        ("wave", "grouped/ragged_dot"): 1}
    # its one loop: the combine's passes over a row's places, as many as the fullest row holds
    assert "cond[" not in wave and "scan[" not in wave and wave.count("while[") == 1
    assert wave.count("ragged_dot_general[") == 2
    assert grouped_matmul.traced_impl("wave") == "grouped/ragged_dot"
    assert grouped_matmul.traced_impl("step") == "all_rows"


@pytest.mark.parametrize("rows", [300, 2048])
def test_a_wave_of_the_cells_share_computes_the_few_pairs_it_holds(rows):
    """12 of 192 experts held, 8 a token in 4 of 8 groups (the cell's
    counts at a toy width): most chosen pairs fall on experts this chip does
    not hold and belong to no group; the grouped product over the rest is
    every held expert on every row."""
    cfg = dataclasses.replace(
        CFG, num_experts=192, num_experts_per_tok=8, n_group=8, topk_group=4,
        experts_held=(0, 16), hidden_size=32, moe_intermediate_size=16)
    rs = np.random.RandomState(rows)
    lp = {"w_router": jnp.asarray(rs.randn(32, 192), jnp.float32),
          "w_gu": jnp.asarray(rs.randn(12, 32, 32) * 0.2, jnp.float32),
          "w_down": jnp.asarray(rs.randn(12, 16, 32) * 0.2, jnp.float32)}
    y = jnp.asarray(rs.randn(rows, 32), jnp.float32)
    stats: list = []
    got = model_mod._shared_sparse_mlp(y, lp, cfg, expert_stats=stats)
    weights, chosen = model_mod.route_sigmoid(y, lp["w_router"], cfg)
    want = model_mod._experts_all_rows(y, weights[:, :12], lp["w_gu"], lp["w_down"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    _, _, held, routed, computed = (int(n) for n in stats[0])
    assert routed == rows * 8 and 0 < held == computed == int(chosen[:, :12].sum()) < routed // 4
    assert int((~chosen[:, :12].any(axis=1)).sum()) > rows // 4    # rows with no pair here


# -- faults -------------------------------------------------------------------

def _fault_softmax_for_sigmoid(monkeypatch):
    monkeypatch.setattr(reference.jax.nn, "sigmoid", lambda z: jax.nn.softmax(z, axis=-1))


def _fault_no_group_limit(monkeypatch):
    real = reference.routing_weights
    monkeypatch.setattr(reference, "routing_weights",
                        lambda y, w, **kw: real(y, w, **{**kw, "n_group": 1, "topk_group": 1}))


def _fault_scale_dropped(monkeypatch):
    real = reference.routing_weights
    monkeypatch.setattr(reference, "routing_weights",
                        lambda y, w, **kw: real(y, w, **{**kw, "scale": 1.0}))


def _fault_shared_expert_dropped(monkeypatch):
    real = arch.published_layout

    def layout(params, l, mf, mlp_blocks=8):
        w_attn, norm, mlp = real(params, l, mf, mlp_blocks)
        return w_attn, norm, (mlp if mlp[0] == "dense" else (*mlp[:3], iter(())))

    monkeypatch.setattr(arch, "published_layout", layout)


def _fault_kr_without_rope(monkeypatch):
    real = reference.rope
    monkeypatch.setattr(reference, "rope", lambda x, pos, theta, scaling: (
        x if x.shape[1] == 1 else real(x, pos, theta, scaling)))


def _fault_yarn_scale_dropped(monkeypatch):
    monkeypatch.setattr(reference, "softmax_scale", lambda dn, dr, scaling: (dn + dr) ** -0.5)


@pytest.mark.parametrize("fault", [
    "softmax_for_sigmoid", "no_group_limit", "scale_dropped", "shared_expert_dropped",
    "kr_without_rope", "yarn_scale_dropped"])
def test_a_fault_in_the_layer_is_caught(served, fault, monkeypatch):
    core, got = served
    globals()[f"_fault_{fault}"](monkeypatch)
    seqs = [check.score_probe(FILE, core.params, PROMPT, probe, vocab_chunks=3)
            for probe in got["served"]]
    verdict = check.compare(got["served"], {"sequences": seqs})
    assert not verdict["ok"] and verdict["max_abs_diff"] > check.LOGPROB_ATOL, verdict


def test_a_router_scored_in_bfloat16_chooses_other_experts():
    """Router logits rounded to bfloat16 change which experts are chosen
    for some token (near-ties at the top-k edge), and with them the layer's
    output by far more than any tolerance: the program scores in float32."""
    lp = _sparse_layer(dataclasses.replace(CFG, experts_held=None))
    y = jnp.asarray(np.random.RandomState(8).randn(2048, 64), jnp.float32)
    _, exact = model_mod.route_sigmoid(y, lp["w_router"], CFG)
    rounded = lp["w_router"].astype(jnp.bfloat16).astype(jnp.float32)
    _, coarse = model_mod.route_sigmoid(
        y.astype(jnp.bfloat16).astype(jnp.float32), rounded, CFG)
    flipped = int(jnp.sum(jnp.any(exact != coarse, axis=1)))
    assert 0 < flipped < 2048 // 4, flipped
    with jax.default_matmul_precision("highest"):
        want = reference.routing_weights(y, lp["w_router"], n_group=4, topk_group=2,
                                         top_k=4, scale=2.5)
    got, chosen = model_mod.route_sigmoid(y, lp["w_router"], CFG)
    assert bool(jnp.all((want > 0) == chosen))      # the program's choice is the exact one
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- a block that leaves the device carries the latent page -------------------

def _held_prefill(core, rid):
    pre = _req(PROMPT, rid, max_tokens=1, ignore_eos=True)
    pre.kv_transfer_params = {"do_remote_decode": True}
    run_to_completion(core, [core.add_request(pre)])


@pytest.mark.parametrize("how", ["wire", "direct"])
def test_disagg_payload_carries_the_latent_page(how):
    want = check.run_probe(make_core(), PROMPT, 17, 5, "whole")
    p_core = make_core()
    _held_prefill(p_core, "pf")
    d_core = EngineCore(CFG, tiny_engine(), seed=5, params=p_core.params)
    if how == "direct":
        n = d_core.import_blocks_direct(p_core, "pf").imported
    else:
        descs = p_core.export_descriptors("pf")
        assert descs[0]["shape"] == [3, 20, 16] == list(p_core.kv_page_shape)
        pages = p_core.read_held_pages("pf", 0, len(descs))
        assert all(len(p) == 3 * 8 * 40 * 4 for p in pages)
        n = d_core.import_blocks([dict(d, kv=kv) for d, kv in zip(descs, pages)]).imported
    p_core.release_held("pf")
    assert n == 5
    got = check.run_probe(d_core, PROMPT, 17, 5, "imported")
    assert got["cached_tokens"] == 32 and got["tokens"] == want["tokens"]
    scored = check.score_probe(FILE, d_core.params, PROMPT, got, vocab_chunks=3)
    verdict = check.compare([got], {"sequences": [scored]})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict


def test_a_page_of_another_geometry_is_refused_at_import():
    p_core = make_core()
    _held_prefill(p_core, "pf")
    descs = p_core.export_descriptors("pf")
    pages = p_core.read_held_pages("pf", 0, len(descs))
    from dynamo_tpu.engine.config import tiny_model

    dense = EngineCore(tiny_model(), tiny_engine(), seed=5)
    with pytest.raises(ValueError, match="geometry"):
        dense.import_blocks([dict(d, kv=kv) for d, kv in zip(descs, pages)])


def test_host_and_disk_tiers_carry_the_latent_page(tmp_path):
    core = make_core(num_kv_blocks=24, host_kv_blocks=6, max_model_len=128,
                     disk_kv_dir=str(tmp_path), disk_kv_blocks=64)
    want = check.run_probe(core, PROMPT, 17, 5, "before")
    rng = np.random.RandomState(3)
    for i in range(8):      # distinct content pushes the prompt's blocks out of HBM
        run_to_completion(core, [core.add_request(
            _req([int(t) for t in rng.randint(1, 300, size=40)], f"noise-{i}", max_tokens=4))])
    core.offload.flush()
    assert core.host_pool.stats.offloads > 0 and core.disk_pool.stats.offloads > 0
    got = check.run_probe(core, PROMPT, 17, 5, "after")
    assert core.host_pool.stats.onboards + core.disk_pool.stats.onboards > 0
    assert got["cached_tokens"] > 0 and got["tokens"] == want["tokens"]
    scored = check.score_probe(FILE, core.params, PROMPT, got, vocab_chunks=3)
    verdict = check.compare([got], {"sequences": [scored]})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict


def _ragged_prefill(cfg, params, ids):
    """One prompt through ``forward_hidden`` on a small cache of its own."""
    eng = EngineConfig(num_kv_blocks=8, block_size=8, max_num_seqs=2, max_model_len=64,
                       prefill_buckets=(64,), decode_buckets=(2,))
    n, bs = len(ids), 8
    pos = np.arange(n, dtype=np.int32)
    table = np.full((1, eng.max_blocks_per_seq), eng.garbage_block, np.int32)
    table[0, : -(-n // bs)] = np.arange(-(-n // bs))
    return forward_hidden(
        params, init_cache(cfg, eng), jnp.asarray(ids, jnp.int32), jnp.asarray(pos),
        jnp.asarray(pos // bs), jnp.asarray(pos % bs), jnp.asarray([n], jnp.int32),
        jnp.asarray(table), jnp.asarray([0, n], jnp.int32), jnp.asarray([1], jnp.int32),
        cfg, eng)


def test_embeddings_path_runs_the_latent_layers():
    core = make_core()
    a = core.embed(PROMPT)
    hidden, _ = _ragged_prefill(CFG, core.params, PROMPT)
    np.testing.assert_allclose(a, np.asarray(hidden).mean(0), atol=1e-5)


# -- refusals and counts -------------------------------------------------------

@pytest.mark.parametrize("option,build", [
    ("kv_dtype", lambda: make_core(kv_dtype="int8")),
    ("tp", lambda: EngineCore(CFG, tiny_engine(), seed=5, mesh=object())),
    ("pp", lambda: EngineCore(CFG, tiny_engine(), seed=5, pp_mesh=object())),
    ("ring_prefill", lambda: EngineCore(CFG, tiny_engine(), seed=5, sp_mesh=object())),
    ("ring_prefill", lambda: make_core(ring_prefill_threshold=64)),
    ("spec_decode", lambda: make_core(spec_decode="ngram")),
], ids=["int8-kv", "tp", "pp", "sp-mesh", "ring-threshold", "speculation"])
def test_an_option_the_latent_page_does_not_carry_is_refused_at_start_up(option, build):
    with pytest.raises(UnsupportedModelOption, match=option) as e:
        build()
    assert e.value.option == option and "tiny-axk1" in str(e.value)
    assert isinstance(e.value, NotImplementedError)


def test_int8_weights_and_a_mesh_rule_are_refused_by_name():
    from dynamo_tpu.parallel.sharding import param_partition_specs

    with pytest.raises(NotImplementedError, match="tiny-axk1"):
        model_mod.init_params_quantized(jax.random.PRNGKey(0), CFG)
    with pytest.raises(NotImplementedError, match="unquantised"):
        CFG.quantized_param_bytes()
    with pytest.raises(NotImplementedError, match="latent page has no heads"):
        init_cache(CFG, tiny_engine(kv_dtype="int8"))
    with pytest.raises(UnsupportedModelOption, match="tp"):
        param_partition_specs(CFG, 2)


@pytest.mark.parametrize("change,error", [
    ({"attention": "mha"}, ValueError),
    ({"kv_lora_rank": 0}, ValueError),
    ({"attn_qkv_bias": True}, NotImplementedError),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, NotImplementedError),
    ({"router_scoring": "tanh"}, ValueError),
    ({"n_group": 5}, ValueError),
    ({"topk_group": 1, "num_experts_per_tok": 8}, ValueError),
    ({"norm_topk_prob": False}, NotImplementedError),
    ({"moe_dispatch": "alltoall"}, NotImplementedError),
    ({"experts_held": (4, 4)}, ValueError),
    ({"experts_held": (0, 3)}, ValueError),
    ({"first_dense_layers": 3}, ValueError),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_a_field_that_does_not_apply_raises(change, error):
    with pytest.raises(error):
        dataclasses.replace(CFG, **change)


def test_the_latent_fields_mean_nothing_to_a_dense_model():
    from dynamo_tpu.engine.config import tiny_model, tiny_moe

    for stray in ({"kv_lora_rank": 32}, {"rope_scaling": SCALING}, {"experts_held": (0, 2)},
                  {"num_shared_experts": 1}, {"routed_scaling_factor": 2.5}):
        with pytest.raises(ValueError):
            dataclasses.replace(tiny_model(), **stray)
    with pytest.raises(ValueError):
        dataclasses.replace(tiny_moe(), experts_held=(0, 2))     # the mixtral path's experts


def test_counts_of_the_published_size_by_hand():
    a = axk1_ep16()
    attn = (7168 * 1536 + 1536 + 1536 * 64 * 192 + 7168 * 576 + 512
            + 512 * 64 * 256 + 64 * 128 * 7168)
    assert attn == 101_122_048 + 2048 and a._attn_params() == attn
    expert = 3 * 7168 * 2048
    sparse = 7168 * 192 + 13 * expert
    dense = 3 * 7168 * 18432
    total = (20480 * 7168 * 2 + 7 * (attn + 2 * 7168) + dense + 6 * sparse + 7168)
    assert a.param_bytes() == 2 * total == 9_682_663_424
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), a))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) - 1   # fuse_tp
    assert n == total
    assert [a.shape for a in params["moe"]["w_gu"]] == [(12, 7168, 4096)] * 6
    assert params["moe"]["w_router"].shape == (6, 7168, 192)      # the router's full width
    assert params["dense_mlp"]["wgu"].shape == (1, 7168, 2 * 18432)
    eng = EngineConfig(num_kv_blocks=16384, block_size=32)
    shapes = jax.eval_shape(lambda: init_cache(a, eng))
    assert len(shapes) == 7 and {s.shape for s in shapes} == {(16385, 144, 128)}
    assert 144 * 128 == 32 * 576        # a page holds 576 values a token and no padding
    # the whole model, for what the share is a share of
    whole = dataclasses.replace(a, experts_held=None, num_layers=61, vocab_size=163840)
    assert 5.1e11 < whole.param_bytes() / 2 < 5.3e11        # "519B"


def test_counters_and_gauges_of_the_share(served):
    core, _ = served
    st = core.scheduler_stats()
    assert st["kv_cache_layers"] == 3 and st["kv_bytes_per_token"] == 3 * 40 * 4
    assert core.kv_cache_stats()["bytes_per_block"] == 8 * st["kv_bytes_per_token"]
    assert st["attention"] == "mla" and st["experts_held"] == 4
    decode, prefill = st["expert_stats"]["decode"], st["expert_stats"]["prefill"]
    # two probes: a prefill wave and two megasteps of 8 each, two sparse layers
    assert decode[1] == 2 * 2 * 8 * 2 and prefill[1] == 2 * 2
    assert decode[3] == 2 * 16 * 4 * 2            # 16 live token-steps a probe x k x layers
    assert prefill[3] == (40 + 8) * 4 * 2         # the prompt, then its uncached last block
    assert 0 < decode[2] < decode[3] and 0 < decode[0] <= 4 * decode[1]
    from dynamo_tpu.runtime.status_server import EXPERT_COUNTERS, SCHEDULER_GAUGES

    assert [n for n, _ in EXPERT_COUNTERS] == [
        "engine_experts_touched", "engine_expert_steps", "engine_expert_pairs_held",
        "engine_expert_pairs_routed", "engine_expert_rows_computed"]
    # every one of the 4 held experts on every row (padding too) of a step and of these short waves
    assert decode[4] % (4 * decode[1]) == 0 and decode[4] >= 4 * decode[3] // 4
    assert prefill[4] % (4 * prefill[1]) == 0 and prefill[4] >= 4 * prefill[3] // 4
    assert "experts_held" in SCHEDULER_GAUGES
    assert axk1_ep16().kv_unit_values * 2 * 7 == 8064


# -- the checkpoint's names ---------------------------------------------------

def test_loader_takes_the_checkpoints_names(tmp_path):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    h, v, L, H, dn, dr, dv, rq, rkv, im, E = 64, 384, 3, 4, 16, 8, 16, 48, 32, 32, 16
    rng = np.random.RandomState(11)
    mat = lambda out, inp: (rng.randn(out, inp) * inp ** -0.5).astype(np.float32)  # noqa: E731
    norm = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": norm(h),
          "lm_head.weight": mat(v, h)}
    for l in range(L):
        p = f"model.layers.{l}."
        for name, (out, inp) in {
                "self_attn.q_a_proj": (rq, h), "self_attn.q_b_proj": (H * (dn + dr), rq),
                "self_attn.kv_a_proj_with_mqa": (rkv + dr, h),
                "self_attn.kv_b_proj": (H * (dn + dv), rkv), "self_attn.o_proj": (h, H * dv),
        }.items():
            sd[p + name + ".weight"] = mat(out, inp)
        sd[p + "self_attn.q_a_layernorm.weight"] = norm(rq)
        sd[p + "self_attn.kv_a_layernorm.weight"] = norm(rkv)
        sd[p + "input_layernorm.weight"] = norm(h)
        sd[p + "post_attention_layernorm.weight"] = norm(h)
        if l == 0:
            mlps = {"mlp": 160}
        else:
            sd[p + "mlp.gate.weight"] = mat(E, h)
            mlps = {"mlp.shared_experts": im, **{f"mlp.experts.{e}": im for e in range(E)}}
        for prefix, width in mlps.items():
            sd[p + prefix + ".gate_proj.weight"] = mat(width, h)
            sd[p + prefix + ".up_proj.weight"] = mat(width, h)
            sd[p + prefix + ".down_proj.weight"] = mat(h, width)
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items() if k not in ("name", "torch_dtype", "experts_held")}
    hf["n_routed_experts"] = E                       # a checkpoint states the whole model
    (tmp_path / "config.json").write_text(json.dumps(hf))

    cfg, params = load_hf_llama(tmp_path, dtype=jnp.float32, experts_held=(1, 4))
    assert cfg == dataclasses.replace(CFG, name="axk1", dtype="bfloat16", experts_held=(1, 4))
    assert [a.shape for a in params["moe"]["w_gu"]] == [(4, h, 2 * im)] * 2
    np.testing.assert_array_equal(          # expert 5 of layer 2 is held expert 1 of sparse 1
        params["moe"]["w_gu"][1][1, :, :im], sd["model.layers.2.mlp.experts.5.gate_proj.weight"].T)
    np.testing.assert_array_equal(
        params["moe"]["w_down"][0][3], sd["model.layers.1.mlp.experts.7.down_proj.weight"].T)
    np.testing.assert_array_equal(
        params["moe"]["shared_wgu"][0, :, im:],
        sd["model.layers.1.mlp.shared_experts.up_proj.weight"].T)
    np.testing.assert_array_equal(params["layers"]["q_norm"][2],
                                  sd["model.layers.2.self_attn.q_a_layernorm.weight"])
    # the rope columns arrive interleaved and leave half-split: pair (2i, 2i+1) -> (i, i+4)
    theirs = sd["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].T
    np.testing.assert_array_equal(params["layers"]["wkv_a"][0][:, rkv + 1], theirs[:, rkv + 2])
    np.testing.assert_array_equal(params["layers"]["wkv_a"][0][:, rkv + 4], theirs[:, rkv + 1])
    q_b = sd["model.layers.1.self_attn.q_b_proj.weight"].T.reshape(rq, H, dn + dr)
    ours = np.asarray(params["layers"]["wq_b"][1]).reshape(rq, H, dn + dr)
    np.testing.assert_array_equal(ours[:, 3, dn + 5], q_b[:, 3, dn + 3])
    np.testing.assert_array_equal(ours[..., :dn], q_b[..., :dn])

    cfg = dataclasses.replace(cfg, dtype="float32")
    params = jax.device_put(params)
    ids = PROMPT[:24]
    hidden, _ = _ragged_prefill(cfg, params, ids)
    want = arch.reference_logits(params, dataclasses.asdict(cfg), ids, list(range(24)),
                                 vocab_chunks=3)
    np.testing.assert_allclose(np.asarray(hidden @ params["lm_head"]), np.asarray(want),
                               atol=5e-5)
    (tmp_path / "config.json").write_text(json.dumps({**hf, "topk_method": "noaux_tc"}))
    with pytest.raises(NotImplementedError, match="topk_method"):
        load_hf_llama(tmp_path, dtype=jnp.float32)
