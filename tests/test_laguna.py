"""Laguna's layers (``ModelConfig.layer_types`` with "sliding_attention",
``heads_per_layer``, ``rope_by_kind``, ``attn_gate``) at a tiny size on the
CPU in float32: full attention with YaRN over half of each head beside
window-8 attention over whole heads, 4 and 6 query heads on 2 KV heads, a
sigmoid gate a head, one dense layer then four sparse ones holding half of 8
sigmoid-routed experts beside a shared one. The engine is held to the plain
reference (``chipbench/reference/laguna.py``) through prefill and decode in
BOTH pools at contexts of 3 to 10 windows, chunk boundaries inside a window,
window blocks released and handed to another lane, a preemption and a
resume; the shares of the experts add up to the uncut layer; faults must
fail the comparison; every option the two-pool cache does not carry is
refused by name. The pools under pressure (cuts, released blocks,
preemption, what a lane holds) are in ``tests/test_laguna_pools.py``
(split off in PR 45: ROADMAP D17)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import laguna as arch
from chipbench.configs import load_config, model_fields
from chipbench.reference import check
from chipbench.reference import laguna as reference
from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    PRESETS,
    UnsupportedModelOption,
    laguna_s21_ep8_9l,
    tiny_laguna,
    tiny_model,
)
from dynamo_tpu.engine.model import init_cache, init_params
from dynamo_tpu.ops import ragged_attention
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_laguna()
FILE = load_config("tiny-laguna-rehearsal")
MF = model_fields(FILE)
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=96)]
TIGHT = 1e-4   # float32 on both sides: the readings are 2e-6 to 6e-6
WINDOW, BLOCK = 8, 4


def make_core(cfg=CFG, **engine) -> EngineCore:
    engine = {"block_size": BLOCK, "num_kv_blocks": 128, "max_model_len": 256, **engine}
    return EngineCore(cfg, tiny_engine(**engine), seed=5)


def held_to_reference(core, n: int, max_tokens: int = 25, file=FILE):
    got = check.score_request(
        core, file, {"prompt_ids": PROMPT[:n], "max_tokens": max_tokens, "top": 5})
    return check.compare(got["served"], got["scored"]), got


def test_the_preset_is_the_file():
    assert dataclasses.replace(ModelConfig(**MF), name="tiny-laguna") == CFG
    assert CFG.windowed and CFG.layer_groups and CFG.shared_sparse and CFG.attn_gate
    assert not CFG.hybrid and not CFG.latent and not CFG.kv_head_pairs
    assert CFG.layers_of("attention") == (0, 4) and CFG.layers_of("window") == (1, 2, 3)
    assert [CFG.heads_of(l) for l in range(5)] == [4, 6, 6, 6, 4]
    assert CFG.dense_mlp_layers == (0,) and CFG.num_experts_held == 4
    # the same page in both pools; a count and a pool each
    assert CFG.kv_page_tail(BLOCK) == CFG.kv_page_tail(BLOCK, "window") == (4, 4, 16)
    assert CFG.cache_layer_counts == {"attention": 2, "conv": 0, "window": 3}
    assert CFG.num_cache_layers == 2 and CFG.state_bytes_per_block() == 0
    assert CFG.window_bytes_per_sequence(BLOCK) == 3 * 3 * 4 * 2 * 2 * 16 * 4
    full, window = CFG.rope_of("full_attention"), CFG.rope_of("sliding_attention")
    assert (full["rope_type"], full["partial_rotary_factor"], full["rope_theta"]) == (
        "yarn", 0.5, 500000)
    assert window == {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    assert "tiny-laguna" in PRESETS and "laguna-s-2.1-ep8-9l" in PRESETS
    assert not tiny_model().windowed and tiny_model().cache_layer_counts == {
        "attention": 2, "conv": 0}
    assert tiny_model().rope_of("full_attention")["rope_theta"] == tiny_model().rope_theta


def test_the_published_size_by_hand():
    big = laguna_s21_ep8_9l()
    attention = 3 * (3072 * (48 + 16) * 128 + 48 * 128 * 3072 + 3072 * 48) + 6 * (
        3072 * (72 + 16) * 128 + 72 * 128 * 3072 + 3072 * 72)
    sparse = 8 * (3072 * 256 + 33 * 3 * 3072 * 1024)
    total = (attention + 9 * 2 * 3072 + 3 * 3072 * 12288 + sparse + 3072
             + 2 * 12544 * 3072)
    assert big.param_bytes() == 2 * total and 6.39e9 < 2 * total < 6.41e9
    assert big.cache_layer_counts == {"attention": 3, "conv": 0, "window": 6}
    assert big.kv_page_tail(32, "window") == (32, 16, 128)
    assert big.window_bytes_per_sequence(32) == 6 * 17 * 32 * 4096
    eng = EngineConfig(block_size=32, max_num_seqs=48, prefill_buckets=(256, 2048))
    # a decode span: 512 keys and 8 queries wherever they lie in their blocks
    assert eng.window_span_blocks(512, 8) == 18 and eng.window_span_blocks(512, 1) == 17
    assert eng.window_table_blocks(512) == 82 <= 512 // 32 + 1 + -(-(2048 + 8) // 32)
    assert eng.window_blocks_auto(512) == 48 * 18 + 64 + 16


# -- the engine against the reference, through both pools ----------------------

@pytest.fixture(scope="module")
def served():
    """The default engine (megastep k = 8, the one-step-ahead loop) sent a
    probe of ten windows twice."""
    core = make_core()
    return core, held_to_reference(core, 80, 25)


def test_prefill_and_decode_through_both_pools_agree_with_reference(served):
    core, (verdict, got) = served
    assert core.engine.megastep == 8 and core.pipelined
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert verdict["compared"] == 2 * 25 * 5 and verdict["argmax_mismatches"] == 0
    first, repeat = got["served"]
    assert first["tokens"] == repeat["tokens"] and len(first["tokens"]) == 25
    # DECIDED: a window block is not content-addressed, so nothing is a hit
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0
    assert core.engine.enable_prefix_caching is False


def test_the_counters_and_the_annotation_say_what_the_pools_hold(served):
    core, _ = served
    st = core.scheduler_stats()
    assert st["cache_layers"] == {"attention": 2, "conv": 0, "window": 3}
    assert st["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4 and st["kv_cache_layers"] == 2
    assert st["window_bytes_per_sequence"] == CFG.window_bytes_per_sequence(BLOCK)
    assert st["prefix_caching"] is False
    # both probes ended: nothing is held in either pool, and every window block
    # of 2 x (80 + 24) positions was given back while its probe went on or at its end
    assert st["window_blocks_in_use"] == 0 == core.allocator.used_blocks
    assert st["window_blocks"] == core.engine.num_window_blocks == (
        core.engine.window_blocks_auto(WINDOW))
    assert st["window_blocks_released"] >= 2 * (80 - WINDOW) // BLOCK
    assert st["window_table_blocks"] == core.engine.window_table_blocks(WINDOW)
    cache = core.cache
    assert [c.shape[0] for c in cache] == [129, st["window_blocks"] + 1] + [
        st["window_blocks"] + 1] * 2 + [129]
    from dynamo_tpu.runtime.status_server import ENGINE_COUNTERS, SCHEDULER_GAUGES

    assert {"window_bytes_per_sequence", "window_blocks_in_use"} <= set(SCHEDULER_GAUGES)
    assert "window_blocks_released" in ENGINE_COUNTERS
    calls = ragged_attention.traced_calls()
    for shape in ("decode", "ragged", "window-decode", "window-ragged"):
        assert calls[(shape, "reference")] >= 1
    assert core._window_traced("megastep") == {
        "window": 8, "heads": "4/6", "attn_window": "reference"}
    assert make_core(tiny_model())._window_traced("megastep") == {}


@pytest.mark.parametrize("n", [24, 41, 57])
@pytest.mark.parametrize("engine", [
    {"megastep_k": 1},
    {"async_exec": False},
    {"prefill_buckets": (16, 32)},
    {"scheduling": "chunked", "max_num_batched_tokens": 32, "prefill_buckets": (16, 32)},
], ids=["single-step", "synchronous", "chunked-waves", "mixed-steps"])
def test_other_step_shapes_and_contexts_agree_with_reference(engine, n):
    """Contexts of 3 to 7 windows at the prompt's end and 6 to 10 at the
    stream's; a prompt cut into waves of 32 and 16 tokens crosses a window
    (8) at every cut; mixed steps cut at whole blocks."""
    verdict, got = held_to_reference(make_core(**engine), n)
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert verdict["argmax_mismatches"] == 0


# -- the attention entry: a window is a table that starts later -----------------

def _window_case(rs, decode: bool, n_q=6, n_kv=2, d=16, bs=4, window=8):
    S = 3
    ctx = np.asarray([37, 9, 22], np.int32)
    q_lens = np.ones(S, np.int32) if decode else np.asarray([7, 9, 5], np.int32)
    P = 12
    n_pages = S * P + 1
    kv = jnp.asarray(rs.randn(n_pages, bs, 2 * n_kv, d), jnp.float32)
    tables = np.arange(S * P, dtype=np.int32).reshape(S, P)
    T = int(q_lens.sum())
    q = jnp.asarray(rs.randn(T, n_q, d), jnp.float32)
    cu = None if decode else jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    return q, kv, ctx, tables, cu, q_lens, S, bs, window


@pytest.mark.parametrize("decode", [True, False], ids=["decode-shape", "ragged"])
def test_a_window_call_on_the_shifted_table_is_the_masked_call_on_the_whole(decode):
    """What ``model.dense_layer`` hands the entry for a window layer (the
    table from the page of the oldest visible key, ``kv_lens`` less the
    tokens before it) gives what the whole table with the same mask gives,
    and both are the softmax over the last ``window`` keys computed by hand."""
    rs = np.random.RandomState(3)
    q, kv, ctx, tables, cu, q_lens, S, bs, window = _window_case(rs, decode)
    ns = jnp.asarray([S], jnp.int32)
    whole = ragged_attention.ragged_paged_attention(
        q, kv, jnp.asarray(ctx), jnp.asarray(tables), cu, ns, sm_scale=0.25, window=window)
    first = np.maximum(0, ctx - q_lens - window + 1) // bs     # of each lane's first query
    W = 6
    shifted = np.stack([np.pad(tables[s, f:f + W], (0, max(0, f + W - tables.shape[1])))
                        for s, f in enumerate(first)])
    cut = ragged_attention.ragged_paged_attention(
        q, kv, jnp.asarray(ctx - bs * first), jnp.asarray(shifted), cu, ns,
        sm_scale=0.25, window=window)
    assert float(jnp.abs(whole - cut).max()) < 1e-6
    unmasked = ragged_attention.ragged_paged_attention(
        q, kv, jnp.asarray(ctx), jnp.asarray(tables), cu, ns, sm_scale=0.25)
    assert float(jnp.abs(whole - unmasked).max()) > 1e-2
    # by hand, for each lane's last query: keys p - 7 .. p of its own pages
    flat = np.asarray(kv).reshape(-1, 4, 16)
    ends = np.cumsum(q_lens) - 1
    for s in range(S):
        p = ctx[s] - 1
        keys = [tables[s, j // bs] * bs + j % bs for j in range(max(0, p - window + 1), p + 1)]
        for h in range(6):
            k, v = flat[keys, 2 * (h // 3)], flat[keys, 2 * (h // 3) + 1]
            w = jax.nn.softmax(jnp.asarray(k @ np.asarray(q[ends[s], h])) * 0.25)
            assert float(jnp.abs(w @ v - whole[ends[s], h]).max()) < 1e-5


def test_a_window_call_is_counted_and_announced_under_its_own_shape():
    rs = np.random.RandomState(4)
    before = ragged_attention.traced_calls()
    for decode in (True, False):
        q, kv, ctx, tables, cu, *_ = _window_case(rs, decode)
        jax.make_jaxpr(lambda *a, cu=cu: ragged_attention.ragged_paged_attention(
            *a, cu, jnp.asarray([3], jnp.int32), sm_scale=0.25, window=8))(
            q, kv, jnp.asarray(ctx), jnp.asarray(tables))
    after = ragged_attention.traced_calls()
    for shape in ("window-decode", "window-ragged"):
        assert after[(shape, "reference")] == before.get((shape, "reference"), 0) + 1
    assert ragged_attention.traced_impl("window-decode") == "reference"


def test_a_wave_goes_to_the_kernel_in_pieces_and_a_decode_step_does_not(monkeypatch):
    """Every layer of a wave, full and window, states ``query_chunk`` (a
    quarter window: 2 of 8 here, 128 of 512 published) and is split
    (``split_query_chunks``); a decode step and a model without window
    layers are not."""
    calls = []
    real = ragged_attention.split_query_chunks
    monkeypatch.setattr(
        ragged_attention, "split_query_chunks",
        lambda rows, *a, **kw: calls.append((rows, kw["chunk"], kw["window"])) or real(
            rows, *a, **kw))
    assert (CFG.wave_query_chunk, laguna_s21_ep8_9l().wave_query_chunk) == (2, 128)
    for cfg in (CFG, tiny_model()):
        core = make_core(cfg) if cfg is CFG else EngineCore(cfg, tiny_engine(), seed=5)
        run_to_completion(core, [core.add_request(_req(PROMPT[:24], "r", max_tokens=6))])
    kinds = [WINDOW if CFG.layer_kind(l) == "window" else None for l in range(CFG.num_layers)]
    assert calls and len(calls) % len(kinds) == 0     # whole programs, traced once each
    assert [w for _, _, w in calls[:len(kinds)]] == kinds
    assert all(chunk == 2 and rows > 2 for rows, chunk, _ in calls)


@pytest.mark.parametrize("rows,heads,block,pages", [
    (2048, 72, 32, 32), (2048, 48, 32, 32), (2048, 32, 128, 8), (2048, 28, 128, 8),
    (40, 72, 8, 8), (96, 72, 32, 32), (96, 28, 96, 8),
])
def test_a_wave_of_many_heads_gets_a_smaller_query_block_and_no_other_call_does(
        monkeypatch, rows, heads, block, pages):
    """48 and 72 query heads in one 128-query block neither fit Mosaic's
    default scoped VMEM nor compile in less than minutes, and their KV
    block is 1,024 tokens (a pass of the kernel's body costs what it costs
    at 256); up to 32 heads (every other cell) the call is handed what it
    was before this PR."""
    import jax.experimental.pallas.ops.tpu.ragged_paged_attention as library

    handed = []
    monkeypatch.setattr(library, "ragged_paged_attention",
                        lambda q, *a, **kw: handed.append(kw) or q)
    ragged_attention.pallas_ragged_attention(
        jnp.zeros((rows, heads, 128), jnp.bfloat16), jnp.zeros((9, 32, 16, 128), jnp.bfloat16),
        jnp.ones((2,), jnp.int32), jnp.zeros((2, 82), jnp.int32),
        jnp.asarray([0, rows // 2, rows], jnp.int32), jnp.asarray([2], jnp.int32),
        sm_scale=0.1, window=512 if heads == 72 else None)
    assert handed == [{"sm_scale": 0.1, "sliding_window": 512 if heads == 72 else None,
                       "num_kv_pages_per_block": pages, "num_queries_per_block": block}]


# -- the shares add up ----------------------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """One sparse layer: what ranks 0-3 of 4 add (the engine's layer on each
    share's own parameters, the shared expert taken out of all but one) is
    what the uncut reference gives for the whole layer, as is the engine's
    own uncut layer."""
    rs = np.random.RandomState(7)
    y = jnp.asarray(rs.randn(21, 64), jnp.float32)
    layer = 2
    uncut = tiny_laguna(experts_held=None)
    params = init_params(jax.random.PRNGKey(5), uncut)
    lp = model_mod.layer_params(params, layer, uncut)
    im = uncut.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        w = reference.routing_weights(y, lp["w_router"], top_k=3, scale=2.5)
        assert int((w > 0).sum()) == 21 * 3
        want = sum(w[:, e, None] * reference.mlp_block(
            y, lp["w_gu"][e][:, :im], lp["w_gu"][e][:, im:], lp["w_down"][e]) for e in range(8))
        shared = reference.mlp_block(
            y, lp["shared_wgu"][:, :im], lp["shared_wgu"][:, im:], lp["shared_down"])
        whole = model_mod._shared_sparse_mlp(y, lp, uncut)
        assert float(jnp.abs(whole - (want + shared)).max()) < TIGHT
        total = 0
        for rank in range(4):
            cfg = tiny_laguna(experts_held=(rank, 4))
            mine = init_params(jax.random.PRNGKey(5), cfg)     # expert e from key e
            lp_r = model_mod.layer_params(mine, layer, cfg)
            assert lp_r["w_gu"].shape[0] == 2
            assert float(jnp.abs(lp_r["w_gu"] - lp["w_gu"][2 * rank:2 * rank + 2]).max()) == 0
            part = model_mod._shared_sparse_mlp(y, lp_r, cfg)
            total = total + (part if rank == 0 else part - shared)
            assert float(jnp.abs(part - shared).max()) > 1e-3      # the share adds something
        assert float(jnp.abs(total - (want + shared)).max()) < TIGHT


def test_the_references_share_is_the_engines():
    mf = dict(MF)
    core = make_core()
    ids, rows = PROMPT[:30], [29]
    mine = np.asarray(arch.reference_logits(core.params, mf, ids, rows))
    none = np.asarray(arch.reference_logits(core.params, mf, ids, rows, held=(0, 0)))
    bare = np.asarray(arch.reference_logits(core.params, mf, ids, rows, held=(0, 0),
                                            shared=False))
    assert float(np.abs(mine - none).max()) > 1e-3 < float(np.abs(none - bare).max())


# -- faults that must fail ------------------------------------------------------

def _trim_window_heads(mp):
    real = arch.published_layout

    def layout(params, l, mf, *a, **kw):
        kind, w, norm, mlp = real(params, l, mf, *a, **kw)
        if kind == "sliding_attention":   # 4 heads where the layer has 6
            w = dict(w, wq=w["wq"][:, :64], wg=w["wg"][:, :4], wo=w["wo"][:64])
        return kind, w, norm, mlp

    mp.setattr(arch, "published_layout", layout)


def _rope(mp, kind, **change):
    real = arch.reference_logits

    def logits(params, mf, *a, **kw):
        rope = {k: dict(v) for k, v in dict(mf["rope_by_kind"]).items()}
        rope[kind].update(change)
        return real(params, dict(mf, rope_by_kind=rope), *a, **kw)

    mp.setattr(arch, "reference_logits", logits)


def _window(mp, by):
    real = arch.reference_logits
    mp.setattr(arch, "reference_logits", lambda params, mf, *a, **kw: real(
        params, dict(mf, sliding_window=mf["sliding_window"] + by), *a, **kw))


def _faults(mp, *names):
    real = arch.reference_logits
    mp.setattr(arch, "reference_logits",
               lambda *a, **kw: real(*a, faults=names, **kw))


@pytest.mark.parametrize("fault", [
    lambda mp: _window(mp, 1), lambda mp: _window(mp, -1),
    lambda mp: _faults(mp, "window"), lambda mp: _faults(mp, "gate"),
    lambda mp: _rope(mp, "full_attention", partial_rotary_factor=1),
    lambda mp: _rope(mp, "full_attention", attention_factor=1.0),
    lambda mp: _rope(mp, "full_attention", rope_type="default"),
    lambda mp: _rope(mp, "sliding_attention", rope_theta=500000),
    _trim_window_heads,
], ids=["window-one-more", "window-one-less", "window-ignored", "gate-dropped",
        "full-rope-on-a-full-layer", "attention-factor-dropped", "yarn-dropped",
        "full-layers-theta-on-a-window-layer", "four-heads-on-a-window-layer"])
def test_a_fault_in_the_layers_is_caught(served, fault, monkeypatch):
    core, (sound, got) = served
    assert sound["ok"]
    fault(monkeypatch)
    probe = got["served"][0]
    scored = check.score_probe(FILE, core.params, PROMPT[:80], probe)
    verdict = check.compare([probe], {"sequences": [scored]})
    assert not verdict["ok"] and verdict["max_abs_diff"] > 100 * TIGHT, verdict


def test_a_window_table_that_starts_a_block_late_is_caught(monkeypatch):
    """The engine's own fault: were a block given back one block early (the
    table's first column one past the oldest visible key), the comparison
    fails. So what passes above shows the blocks held are the ones seen."""
    real = EngineCore._hold_window

    def early(self, seq, p0, n_tokens):
        return real(self, seq, p0 + BLOCK, max(1, n_tokens - BLOCK))

    core = make_core(async_exec=False, megastep_k=1)
    monkeypatch.setattr(EngineCore, "_hold_window", early)
    verdict, _ = held_to_reference(core, 40, 9)
    assert not verdict["ok"], verdict


# -- refusals -------------------------------------------------------------------

@pytest.mark.parametrize("option,build", [
    ("prefix_caching", lambda: make_core(enable_prefix_caching=True)),
    ("kv_dtype", lambda: make_core(kv_dtype="int8")),
    ("host_kv_blocks", lambda: make_core(host_kv_blocks=8)),
    ("disk_kv_dir", lambda: make_core(host_kv_blocks=0, disk_kv_dir="/nowhere")),
    ("tp", lambda: EngineCore(CFG, tiny_engine(block_size=BLOCK), seed=5, mesh=object())),
    ("pp", lambda: EngineCore(CFG, tiny_engine(block_size=BLOCK), seed=5, pp_mesh=object())),
    ("ring_prefill", lambda: EngineCore(CFG, tiny_engine(block_size=BLOCK), seed=5,
                                        sp_mesh=object())),
    ("ring_prefill", lambda: make_core(ring_prefill_threshold=64)),
    ("spec_decode", lambda: make_core(spec_decode="ngram")),
], ids=["prefix-caching", "int8-kv", "host-tier", "disk-tier", "tp", "pp", "sp-mesh",
        "ring-threshold", "speculation"])
def test_an_option_the_two_pool_cache_does_not_carry_is_refused_at_start_up(option, build):
    with pytest.raises(UnsupportedModelOption, match=option) as e:
        build()
    assert e.value.option == option and "tiny-laguna" in str(e.value)
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
        assert e.value.option == option and "pool of their own" in str(e.value)


def test_prefix_caching_asked_off_or_left_alone_is_off_and_no_event_is_published():
    stored, removed = [], []
    for asked in (None, False):
        core = EngineCore(CFG, tiny_engine(block_size=BLOCK, enable_prefix_caching=asked),
                          seed=5, on_stored=lambda h, p: stored.append(h),
                          on_removed=lambda h: removed.append(h))
        assert core.engine.enable_prefix_caching is False
        seq = core.add_request(_req(PROMPT[:33], "s0", max_tokens=9, ignore_eos=True))
        run_to_completion(core, [seq])
    assert stored == [] == removed
    # every other model: None is on, as the default has always been
    dense = EngineCore(tiny_model(), tiny_engine(), seed=5, on_stored=lambda h, p: stored.append(h))
    assert dense.engine.enable_prefix_caching is True and dense.window_allocator is None
    seq = dense.add_request(_req(PROMPT[:33], "s0", max_tokens=9, ignore_eos=True))
    run_to_completion(dense, [seq])
    assert stored
    with pytest.raises(ValueError, match="num_window_blocks"):
        EngineCore(tiny_model(), tiny_engine(num_window_blocks=8), seed=5)


def test_int8_weights_and_int8_pages_are_refused_by_name():
    from dynamo_tpu.backends.jax.main import build_engine

    with pytest.raises(UnsupportedModelOption, match="quant") as e:
        build_engine("tiny-laguna", {"num_kv_blocks": 16, "block_size": 4}, quant="int8")
    assert e.value.option == "quant"
    with pytest.raises(NotImplementedError, match="tiny-laguna"):
        model_mod.init_params_quantized(jax.random.PRNGKey(0), CFG)
    with pytest.raises(NotImplementedError, match="unquantised"):
        CFG.quantized_param_bytes()
    with pytest.raises(NotImplementedError, match="window pool"):
        init_cache(CFG, tiny_engine(block_size=BLOCK, kv_dtype="int8", num_window_blocks=8))


@pytest.mark.parametrize("change,error", [
    ({"sliding_window": 0}, ValueError),
    ({"layer_types": ("full_attention",) * 5}, ValueError),
    ({"layer_types": ("full_attention", "linear_attention") + ("sliding_attention",) * 3},
     ValueError),
    ({"heads_per_layer": (4, 6, 6, 6)}, ValueError),
    ({"heads_per_layer": (4, 5, 6, 6, 4)}, ValueError),
    ({"rope_by_kind": {"conv": {"rope_theta": 1.0}}}, ValueError),
    ({"rope_by_kind": {"full_attention": {"rope_theta": 1.0, "rope_type": "llama3"}}},
     ValueError),
    ({"attn_qkv_bias": True}, NotImplementedError),
    ({"qk_norm": True}, NotImplementedError),
    ({"sandwich_norm": True}, NotImplementedError),
], ids=["no-window", "no-window-layer", "unknown-kind", "a-layer-short", "heads-not-a-multiple",
        "rope-for-a-conv-layer", "unknown-rope-type", "qkv-bias", "qk-norm", "sandwich"])
def test_a_field_that_does_not_apply_raises(change, error):
    with pytest.raises(error):
        dataclasses.replace(CFG, **change)


def test_the_window_fields_mean_nothing_to_a_model_without_window_layers():
    for change in ({"sliding_window": 8}, {"attn_gate": True}, {"heads_per_layer": (4, 4)},
                   {"rope_by_kind": {"full_attention": {"rope_theta": 1.0}}}):
        with pytest.raises(ValueError):
            dataclasses.replace(tiny_model(), **change)


# -- the loader -----------------------------------------------------------------

def test_loader_takes_the_checkpoints_names(tmp_path):
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    h, v, d, im, inter = 64, 384, 16, 32, 160
    rng = np.random.RandomState(11)
    mat = lambda out, inp: (rng.randn(out, inp) * inp ** -0.5).astype(np.float32)  # noqa: E731
    norm = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": norm(h),
          "lm_head.weight": mat(v, h)}
    for l, heads in enumerate(CFG.heads_per_layer):
        p = f"model.layers.{l}."
        sd[p + "input_layernorm.weight"] = norm(h)
        sd[p + "post_attention_layernorm.weight"] = norm(h)
        for name, out in (("q_proj", heads * d), ("k_proj", 2 * d), ("v_proj", 2 * d),
                          ("g_proj", heads)):
            sd[p + f"self_attn.{name}.weight"] = mat(out, h)
        sd[p + "self_attn.o_proj.weight"] = mat(h, heads * d)
        if l == 0:
            ffns = {"mlp": inter}
        else:
            sd[p + "mlp.gate.weight"] = mat(8, h)
            ffns = {f"mlp.experts.{e}": im for e in range(8)}
            ffns["mlp.shared_expert"] = im
        for prefix, width in ffns.items():
            sd[p + prefix + ".gate_proj.weight"] = mat(width, h)
            sd[p + prefix + ".up_proj.weight"] = mat(width, h)
            sd[p + prefix + ".down_proj.weight"] = mat(h, width)
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items()
          if k not in ("name", "torch_dtype", "serve", "source", "deployment", "reduced",
                       "assumed", "experts_held")}
    hf["num_experts"] = 8          # a checkpoint's config.json gives the published count
    (tmp_path / "config.json").write_text(json.dumps(hf))

    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32, experts_held=(1, 2))
    assert cfg == dataclasses.replace(CFG, name="laguna", dtype="bfloat16", experts_held=(1, 2))
    assert loaded["attn"]["wqkv"].shape == (2, h, (4 + 4) * d)
    assert loaded["attn_window"]["wqkv"].shape == (3, h, (6 + 4) * d)
    assert loaded["attn_window"]["wg"].shape == (3, h, 6)
    assert [a.shape for a in loaded["moe"]["w_gu"]] == [(4, h, 2 * im)] * 4
    np.testing.assert_array_equal(   # layer 2 is window layer 1; rank 1 of 2 holds experts 4-7
        loaded["attn_window"]["wg"][1], sd["model.layers.2.self_attn.g_proj.weight"].T)
    np.testing.assert_array_equal(
        loaded["attn"]["wqkv"][1][:, :4 * d], sd["model.layers.4.self_attn.q_proj.weight"].T)
    np.testing.assert_array_equal(
        loaded["moe"]["w_gu"][2][1, :, im:], sd["model.layers.3.mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(
        loaded["moe"]["shared_down"][0], sd["model.layers.1.mlp.shared_expert.down_proj.weight"].T)
    # the loaded tree is the tree the engine serves: the reference reads it through the same map
    core = make_core(dataclasses.replace(cfg, dtype="float32"), async_exec=False)
    core.params = jax.device_put(loaded)
    file = dict(FILE, experts_held={"rank": 1, "of": 2, "published": 8})
    verdict, _ = held_to_reference(core, 30, 9, file=file)
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
