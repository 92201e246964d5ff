"""MiMo's layers (``ModelConfig.v_head_dim`` beside "gqa": the wide key;
``window_kv_heads``, ``attn_sinks``, ``attn_value_scale``) at a tiny size on
the CPU in float32, every ratio kept: keys 24 wide beside values 16 wide, 32
query heads on 2 KV heads of a full layer and 4 of a window-8 layer with a
sink a head, a third of each head rotated, one dense layer then four sparse
ones holding a quarter of 16 bias-chosen sigmoid experts, no shared one. The
engine is held to the plain reference (``chipbench/reference/mimo_v2.py``)
through prefill and decode in BOTH pools, whose pages differ in bytes, at
contexts of 3 to 13 windows, chunked waves, a preemption and a resume, both
pools running out; the sixteen experts' four shares add up to the uncut
layer; each ``assumed`` switch changes the logits and each fault fails the
comparison; every option the wide-key page does not carry is refused by
name. The op alone is in ``tests/test_mimo_attention.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import mimo_v2 as arch
from chipbench.configs import load_config, model_fields
from chipbench.reference import check
from chipbench.reference import mimo_v2 as reference
from chipbench.reference.lfm2_moe import routing_weights
from chipbench.reference.qwen2 import mlp_block
from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    PRESETS,
    UnsupportedModelOption,
    mimo_v25_ep16_7l,
    tiny_mimo,
    tiny_model,
)
from dynamo_tpu.engine.model import init_cache, init_params
from dynamo_tpu.ops import ragged_attention
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_mimo()
FILE = load_config("tiny-mimo-rehearsal")
MF = model_fields(FILE)
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=110)]
# float32 on both sides: the readings are 2e-6 to 3e-6 (a softmax in chunks
# against one in a piece, sums in another order); a precision below float32
# reads 1e-3 and more
TIGHT = 1e-4
WINDOW, BLOCK = 8, 4


def make_core(cfg=CFG, **engine) -> EngineCore:
    engine = {"block_size": BLOCK, "num_kv_blocks": 128, "max_model_len": 256, **engine}
    return EngineCore(cfg, tiny_engine(**engine), seed=5)


def held_to_reference(core, n: int, max_tokens: int = 25, file=FILE):
    got = check.score_request(
        core, file, {"prompt_ids": PROMPT[:n], "max_tokens": max_tokens, "top": 5})
    return check.compare(got["served"], got["scored"]), got


def test_the_preset_is_the_file():
    assert dataclasses.replace(ModelConfig(**MF), name="tiny-mimo") == CFG
    assert CFG.windowed and CFG.layer_groups and CFG.shared_sparse and CFG.wide_key
    assert not CFG.hybrid and not CFG.latent and not CFG.kv_head_pairs and not CFG.attn_gate
    assert CFG.layers_of("attention") == (0, 3) and CFG.layers_of("window") == (1, 2, 4)
    assert (CFG.kv_heads_of("attention"), CFG.kv_heads_of("window")) == (2, 4)
    assert CFG.value_dim == 16 and CFG.head_dim == 24
    assert CFG.has_sink("window") and not CFG.has_sink("attention")
    assert CFG.dense_mlp_layers == (0,) and CFG.num_experts_held == 4
    assert CFG.num_shared_experts == 0 and CFG.router_bias
    # a page a kind: 2.5 n_kv tiles of [block, 16]; the window pool's blocks are twice as wide
    assert CFG.kv_page_tail(BLOCK) == (20, 16) and CFG.kv_page_tail(BLOCK, "window") == (40, 16)
    assert CFG.kv_unit_values == 2 * 40 and CFG.kv_unit_values_of("window") == 4 * 40
    assert CFG.cache_layer_counts == {"attention": 2, "conv": 0, "window": 3}
    assert CFG.bytes_per_block(BLOCK, "attention") == 2 * 20 * 16 * 4
    assert CFG.bytes_per_block(BLOCK, "window") == 3 * 40 * 16 * 4
    assert CFG.window_bytes_per_sequence(BLOCK) == 3 * 3 * BLOCK * 160 * 4
    for kind, theta in (("full_attention", 10000000), ("sliding_attention", 10000)):
        rp = CFG.rope_of(kind)
        assert rp["rope_theta"] == theta and int(24 * rp["partial_rotary_factor"]) == 8
    assert "tiny-mimo" in PRESETS and "mimo-v2.5-ep16-7l" in PRESETS
    assert tiny_model().value_dim == 16 and not tiny_model().wide_key
    assert tiny_model().kv_heads_of("window") == 2


def test_the_published_size_and_the_bytes_a_block_by_hand():
    """ISSUE 46's sums: 89.13M a full layer's attention, 94.37M a window
    layer's, 6.86 GB in all; 2,560 B a token a full layer and 5,120 B a token
    a window layer, so a block of 32 tokens is 160 KB in the full pool (two
    layers) and 800 KB in the window pool (five): no value is padded."""
    big = mimo_v25_ep16_7l()
    assert big == dataclasses.replace(
        ModelConfig(**model_fields(load_config("mimo-v2.5-ep16-7l-bf16"))),
        name="mimo-v2.5-ep16-7l")
    full = 4096 * (64 * 192 + 4 * 192 + 4 * 128) + 64 * 128 * 4096
    window = 4096 * (64 * 192 + 8 * 192 + 8 * 128) + 64 * 128 * 4096 + 64
    assert (full, window) == (89128960, 94371904)
    sparse = 4096 * 256 + 256 + 16 * 3 * 4096 * 2048
    total = (2 * full + 5 * window + 7 * 2 * 4096 + 3 * 4096 * 16384 + 6 * sparse
             + 4096 + 2 * 19072 * 4096)
    assert big.param_bytes() == 2 * total and 6.85e9 < 2 * total < 6.87e9
    assert big.kv_page_tail(32) == (320, 128) and big.kv_page_tail(32, "window") == (640, 128)
    assert big.kv_unit_values * 2 == 2560 and big.kv_unit_values_of("window") * 2 == 5120
    assert big.bytes_per_block(32, "attention") == 160 * 1024
    assert big.bytes_per_block(32, "window") == 800 * 1024
    assert big.cache_layer_counts == {"attention": 2, "conv": 0, "window": 5}
    assert big.window_bytes_per_sequence(32) == 5 * 5 * 32 * 5120
    assert [int(192 * big.rope_of(k)["partial_rotary_factor"])
            for k in ("full_attention", "sliding_attention")] == [64, 64]


def test_a_window_shorter_than_a_waves_chunk_sizes_its_pool_and_its_table():
    """Window 128 = four blocks of 32, shorter than any prefill bucket: a
    lane's decode span is 6 blocks, a 2,048-token wave's 70, the pool what 32
    lanes and one widest wave hold; a piece of a wave is 32 queries."""
    eng = EngineConfig(block_size=32, max_num_seqs=32, max_model_len=14912,
                       prefill_buckets=(256, 512, 1024, 2048), decode_buckets=(16, 32))
    assert eng.window_span_blocks(128, 8) == 6 and eng.window_span_blocks(128, 1) == 5
    assert eng.window_table_blocks(128) == 70 == eng.window_span_blocks(128, 2048 + 7)
    assert eng.window_blocks_auto(128) == 32 * 6 + 64 + 16
    assert eng.max_blocks_per_seq == 466 and 32 * 466 <= 15360
    assert mimo_v25_ep16_7l().wave_query_chunk == 32 and CFG.wave_query_chunk == 2
    # the least the engine accepts: one sequence's widest dispatch beside a decoding lane
    least = eng.window_table_blocks(128) + eng.window_span_blocks(128, 8)
    assert least == 76 < eng.window_blocks_auto(128)


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
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0
    assert core.engine.enable_prefix_caching is False


def test_health_and_metrics_give_the_cache_by_kind(served):
    core, _ = served
    st = core.scheduler_stats()
    assert st["cache_layers"] == {"attention": 2, "conv": 0, "window": 3}
    assert st["cache_page_shape"] == {"attention": [20, 16], "window": [40, 16]}
    assert st["cache_bytes_per_block"] == {"attention": 2 * 1280, "window": 3 * 2560}
    assert st["kv_bytes_per_token"] == 2 * 2 * 40 * 4 and st["kv_cache_layers"] == 2
    assert st["window_bytes_per_sequence"] == CFG.window_bytes_per_sequence(BLOCK)
    assert st["window_blocks_in_use"] == 0 == core.allocator.used_blocks
    assert st["window_blocks_released"] >= 2 * (80 - WINDOW) // BLOCK
    assert [c.shape for c in core.cache] == [
        (129, 20, 16), (st["window_blocks"] + 1, 40, 16), (st["window_blocks"] + 1, 40, 16),
        (129, 20, 16), (st["window_blocks"] + 1, 40, 16)]
    calls = ragged_attention.traced_calls()
    for shape in ("gqa-decode", "gqa-ragged", "window-gqa-decode", "window-gqa-ragged"):
        assert calls[(shape, "jnp")] >= 1
    assert core._window_traced("megastep") == {
        "window": 8, "heads": "32/32", "attn_window": "jnp"}
    # every other model says one kind, its own page, and the bytes it always said
    dense = make_core(tiny_model()).scheduler_stats()
    assert dense["cache_page_shape"] == {"attention": [4, 4, 16]}
    assert dense["cache_bytes_per_block"] == {"attention": dense["kv_bytes_per_token"] * BLOCK}
    from dynamo_tpu.runtime.status_server import _EngineCounters

    families = {f.name: f for f in _EngineCounters(lambda: {}, core.scheduler_stats).collect()}
    by_kind = {s.labels["kind"]: s.value
               for s in families["dynamo_engine_cache_bytes_per_block"].samples}
    assert by_kind == {"attention": 2560.0, "window": 7680.0}
    shapes = {(s.labels["kind"], s.labels["shape"]): s.value
              for s in families["dynamo_engine_cache_page_values"].samples}
    assert shapes == {("attention", "20x16"): 320.0, ("window", "40x16"): 640.0}
    traced = {(s.labels["shape"], s.labels["impl"])
              for s in families["dynamo_engine_attention_calls_traced"].samples}
    assert {("gqa-decode", "jnp"), ("window-gqa-decode", "jnp"),
            ("window-gqa-ragged", "jnp")} <= traced


@pytest.mark.parametrize("n", [24, 57, 101])
@pytest.mark.parametrize("engine", [
    {"megastep_k": 1},
    {"async_exec": False},
    {"prefill_buckets": (16, 32)},
    {"scheduling": "chunked", "max_num_batched_tokens": 32, "prefill_buckets": (16, 32)},
], ids=["single-step", "synchronous", "chunked-waves", "mixed-steps"])
def test_other_step_shapes_and_contexts_agree_with_reference(engine, n):
    """Contexts of 3 to 13 windows at the prompt's end; a prompt cut into
    waves of 32 and 16 tokens crosses a window (8) at every cut; a prompt
    longer than two windows goes on through released blocks."""
    verdict, _ = held_to_reference(make_core(**engine), n)
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert verdict["argmax_mismatches"] == 0


def _streams(prompts, max_tokens, **engine):
    core = make_core(**engine)
    seqs = [core.add_request(_req(p, f"s{i}", max_tokens=m, ignore_eos=True))
            for i, (p, m) in enumerate(zip(prompts, max_tokens))]
    done, _ = run_to_completion(core, seqs, max_steps=4000)
    return done, core


@pytest.mark.parametrize("pool", ["full", "window"])
def test_either_pool_running_out_preempts_and_the_resumed_stream_is_the_unpressed(pool):
    prompts = [PROMPT[17 * i:17 * i + 17] for i in range(5)]
    roomy, _ = _streams(prompts, [33] * 5, num_kv_blocks=80, max_model_len=64)
    tight = {"full": {"num_kv_blocks": 40}, "window": {"num_window_blocks": 18}}[pool]
    pressed, core = _streams(prompts, [33] * 5, **{
        "num_kv_blocks": 80, "max_model_len": 64, "prefill_buckets": (16, 32), **tight})
    assert core.sched_stats["preemptions"] >= 1
    assert pressed == roomy and all(len(v) == 33 for v in pressed.values())
    assert core.window_allocator.used_blocks == 0 == core.allocator.used_blocks


def test_a_resumed_stream_recomputes_what_it_gave_back():
    want = _streams([PROMPT[:21]], [30], async_exec=False)[0]["s0"]
    core = make_core(async_exec=False)
    seq = core.add_request(_req(PROMPT[:21], "s0", max_tokens=30, ignore_eos=True))
    got = []
    while seq.generated < 17:
        for _, out in core.step():
            got += list(out.token_ids)
    with core._step_lock:
        core._preempt(seq)
    assert seq.win_ids == [] and core.window_allocator.used_blocks == 0
    done, _ = run_to_completion(core, [seq])
    assert got + done["s0"] == want and core.sched_stats["preemptions"] == 1


# -- the share ------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """One sparse layer: what ranks 0-3 of 4 add (the engine's layer on each
    share's own parameters; nothing is computed by all alike: no shared
    expert) is what the uncut reference gives for the whole layer, as is the
    engine's own uncut layer."""
    rs = np.random.RandomState(7)
    y = jnp.asarray(rs.randn(21, 64), jnp.float32)
    layer = 2
    uncut = tiny_mimo(experts_held=None)
    params = init_params(jax.random.PRNGKey(5), uncut)
    lp = model_mod.layer_params(params, layer, uncut)
    im = uncut.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        w = routing_weights(y, lp["w_router"], lp["expert_bias"], top_k=4, scale=1.0,
                            norm_eps=0.0)
        assert int((w > 0).sum()) == 21 * 4
        want = sum(w[:, e, None] * mlp_block(
            y, lp["w_gu"][e][:, :im], lp["w_gu"][e][:, im:], lp["w_down"][e])
            for e in range(16))
        whole = model_mod._shared_sparse_mlp(y, lp, uncut)
        assert float(jnp.abs(whole - want).max()) < TIGHT
        total = 0
        for rank in range(4):
            cfg = tiny_mimo(experts_held=(rank, 4))
            mine = init_params(jax.random.PRNGKey(5), cfg)     # expert e from key e
            lp_r = model_mod.layer_params(mine, layer, cfg)
            assert lp_r["w_gu"].shape[0] == 4
            assert float(jnp.abs(lp_r["w_gu"] - lp["w_gu"][4 * rank:4 * rank + 4]).max()) == 0
            part = model_mod._shared_sparse_mlp(y, lp_r, cfg)
            assert float(jnp.abs(part).max()) > 1e-4           # the share adds something
            total = total + part
        assert float(jnp.abs(total - want).max()) < TIGHT


def test_the_references_share_is_the_engines():
    core = make_core()
    ids, rows = PROMPT[:30], [29]
    mine = np.asarray(arch.reference_logits(core.params, dict(MF), ids, rows))
    none = np.asarray(arch.reference_logits(core.params, dict(MF), ids, rows, held=(0, 0)))
    assert float(np.abs(mine - none).max()) > 1e-4


# -- each assumed switch changes the logits, and each fault fails ------------------

def _faults(mp, *names):
    real = arch.reference_logits
    mp.setattr(arch, "reference_logits", lambda *a, **kw: real(*a, faults=names, **kw))


def _layout(mp, change):
    real = arch.published_layout

    def layout(params, l, mf, *a, **kw):
        kind, w, norm, mlp = real(params, l, mf, *a, **kw)
        return kind, change(kind, dict(w)), norm, mlp

    mp.setattr(arch, "published_layout", layout)


def _sink_of_the_next_head(kind, w):
    if w["sink"] is not None:
        w["sink"] = jnp.roll(w["sink"], 1)
    return w


def _mf(mp, **change):
    real = arch.reference_logits
    mp.setattr(arch, "reference_logits",
               lambda params, mf, *a, **kw: real(params, {**mf, **change}, *a, **kw))


@pytest.mark.parametrize("fault", [
    lambda mp: _faults(mp, "sink"), lambda mp: _faults(mp, "v_scale"),
    lambda mp: _faults(mp, "window"), lambda mp: _faults(mp, "wide_key"),
    lambda mp: _layout(mp, _sink_of_the_next_head),
    lambda mp: _mf(mp, sliding_window=WINDOW + 1), lambda mp: _mf(mp, sliding_window=WINDOW - 1),
    lambda mp: _mf(mp, rope_by_kind={k: {**dict(v), "partial_rotary_factor": 1.0}
                                     for k, v in dict(MF["rope_by_kind"]).items()}),
    lambda mp: _mf(mp, rope_by_kind={k: dict(dict(MF["rope_by_kind"])["sliding_attention"])
                                     for k in dict(MF["rope_by_kind"])}),
    lambda mp: _mf(mp, attn_value_scale=0.5),
], ids=["sink-dropped", "value-scale-dropped", "window-ignored", "keys-last-third-dropped",
        "sink-from-the-next-head", "window-one-more", "window-one-less",
        "whole-head-rotated", "window-layers-theta-on-a-full-layer", "another-value-scale"])
def test_a_fault_in_the_layers_is_caught(served, fault, monkeypatch):
    core, (sound, got) = served
    assert sound["ok"]
    fault(monkeypatch)
    probe = got["served"][0]
    scored = check.score_probe(FILE, core.params, PROMPT[:80], probe)
    verdict = check.compare([probe], {"sequences": [scored]})
    assert not verdict["ok"] and verdict["max_abs_diff"] > 100 * TIGHT, verdict


def test_a_sink_on_the_full_layers_or_on_none_is_another_model():
    """The sinks are leaves of the kinds that have one, and of no other."""
    params = init_params(jax.random.PRNGKey(5), CFG)
    assert params["attn_window"]["sink"].shape == (3, 32) and "sink" not in params["attn"]
    assert params["attn_window"]["sink"].dtype == jnp.float32
    assert params["attn"]["wqkv"].shape == (2, 64, 32 * 24 + 2 * 24 + 2 * 16)
    assert params["attn_window"]["wqkv"].shape == (3, 64, 32 * 24 + 4 * 24 + 4 * 16)
    assert params["attn"]["wo"].shape == (2, 32 * 16, 64)
    both = dataclasses.replace(CFG, attn_sinks=("full_attention", "sliding_attention"))
    assert init_params(jax.random.PRNGKey(5), both)["attn"]["sink"].shape == (2, 32)
    none = dataclasses.replace(CFG, attn_sinks=())
    assert "sink" not in init_params(jax.random.PRNGKey(5), none)["attn_window"]
    # a sink takes a visible share of a full window's mass: about a quarter, every one
    # of the 96 between a twentieth and six tenths
    sink = np.asarray(params["attn_window"]["sink"])
    share = np.exp(sink) / (np.exp(sink) + WINDOW * np.exp(0.5))
    assert 0.05 < share.min() and share.max() < 0.6 and 0.2 < share.mean() < 0.35


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
def test_an_option_the_wide_key_page_does_not_carry_is_refused_at_start_up(option, build):
    with pytest.raises(UnsupportedModelOption, match=option) as e:
        build()
    assert e.value.option == option and "tiny-mimo" in str(e.value)
    assert isinstance(e.value, NotImplementedError)


@pytest.mark.parametrize("option,leave", [
    ("disagg", lambda core: core.kv_page_shape),
    ("disagg", lambda core: core.export_descriptors("nobody")),
    ("disagg", lambda core: core.import_blocks([])),
    ("peer_kv", lambda core: core.read_cached_pages([1, 2])),
], ids=["page-shape", "export", "import", "peer-pull"])
def test_a_block_does_not_leave_the_device(served, option, leave):
    with pytest.raises(UnsupportedModelOption, match=option) as e:
        leave(served[0])
    assert e.value.option == option and "pool of their own" in str(e.value)


def test_a_requests_embedding_rows_are_refused_by_name(served):
    """The text model alone is served: a multimodal request's rows are
    refused as it is admitted, and every other model takes them as before."""
    core, _ = served
    rows = np.zeros((2, 64), np.float32)
    mm = {"embeds": rows.tobytes(), "embeds_shape": [2, 64], "positions": [[1, 2]]}
    req = _req(PROMPT[:9], "mm0", max_tokens=3, ignore_eos=True)
    req.mm = mm
    with pytest.raises(UnsupportedModelOption, match="mm_embeds") as e:
        core.add_request(req)
    assert e.value.option == "mm_embeds" and "tiny-mimo" in str(e.value)
    dense = make_core(tiny_model())
    req = _req(PROMPT[:9], "mm1", max_tokens=3, ignore_eos=True)
    req.mm = mm
    assert dense.add_request(req).mm_embeds.shape == (2, 64)


def test_int8_weights_and_int8_pages_are_refused_by_name():
    from dynamo_tpu.backends.jax.main import build_engine

    with pytest.raises(UnsupportedModelOption, match="quant") as e:
        build_engine("tiny-mimo", {"num_kv_blocks": 16, "block_size": 4}, quant="int8")
    assert e.value.option == "quant"
    with pytest.raises(NotImplementedError, match="tiny-mimo"):
        model_mod.init_params_quantized(jax.random.PRNGKey(0), CFG)
    with pytest.raises(NotImplementedError, match="window pool"):
        init_cache(CFG, tiny_engine(block_size=BLOCK, kv_dtype="int8", num_window_blocks=8))


@pytest.mark.parametrize("change,error,match", [
    ({"v_head_dim": 24}, NotImplementedError, "1.5 values' width"),
    ({"window_kv_heads": 3}, NotImplementedError, "even"),
    ({"attn_gate": True}, NotImplementedError, "attn_gate"),
    ({"v_head_dim": 0}, NotImplementedError, "without v_head_dim"),
    ({"attn_sinks": ("conv",)}, ValueError, "attn_sinks"),
    ({"num_heads": 30}, ValueError, "multiple of its kind's KV heads"),
    ({"layer_types": ("full_attention",) * 5, "sliding_window": 0}, ValueError,
     "without a 'sliding_attention' layer"),
], ids=["equal-widths", "odd-kv-heads", "gate", "sink-without-a-wide-key", "sink-on-a-conv-layer",
        "heads-not-a-multiple-of-the-window-layers-kv-heads", "no-window-layer"])
def test_a_field_that_does_not_apply_raises(change, error, match):
    with pytest.raises(error, match=match):
        dataclasses.replace(CFG, **change)


def test_the_wide_key_fields_mean_nothing_to_the_other_models_and_their_pages_stay():
    dense = tiny_model()
    assert dense.kv_page_tail(BLOCK) == (4, 4, 16) and dense.kv_unit_values == 2 * 2 * 16
    with pytest.raises(ValueError, match="without a 'sliding_attention' layer"):
        dataclasses.replace(dense, attn_value_scale=0.5)
    with pytest.raises(ValueError, match="without a 'sliding_attention' layer"):
        dataclasses.replace(dense, v_head_dim=8)
    from dynamo_tpu.engine.config import laguna_s21_ep8_9l

    laguna = laguna_s21_ep8_9l()
    assert not laguna.wide_key and laguna.kv_page_tail(32, "window") == (32, 16, 128)
    assert laguna.kv_heads_of("window") == 8 == laguna.kv_heads_of("attention")


def test_loader_takes_the_checkpoints_names(tmp_path):
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    h, v, dk, dv, im, inter, H, E = 64, 384, 24, 16, 32, 160, 32, 16
    rng = np.random.RandomState(11)
    mat = lambda out, inp: (rng.randn(out, inp) * inp ** -0.5).astype(np.float32)  # noqa: E731
    norm = lambda n: (1.0 + 0.1 * rng.randn(n)).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": norm(h),
          "lm_head.weight": mat(v, h)}
    for l, kind in enumerate(CFG.layer_types):
        p = f"model.layers.{l}."
        n_kv = 4 if kind == "sliding_attention" else 2
        sd[p + "input_layernorm.weight"] = norm(h)
        sd[p + "post_attention_layernorm.weight"] = norm(h)
        for name, out in (("q_proj", H * dk), ("k_proj", n_kv * dk), ("v_proj", n_kv * dv)):
            sd[p + f"self_attn.{name}.weight"] = mat(out, h)
        sd[p + "self_attn.o_proj.weight"] = mat(h, H * dv)
        if kind == "sliding_attention":
            sd[p + "self_attn.attention_sink_bias"] = (1.5 + rng.randn(H)).astype(np.float32)
        if l == 0:
            ffns = {"mlp": inter}
        else:
            sd[p + "mlp.gate.weight"] = mat(E, h)
            sd[p + "mlp.gate.e_score_correction_bias"] = (0.05 * rng.randn(E)).astype(np.float32)
            ffns = {f"mlp.experts.{e}": im for e in range(E)}
        for prefix, width in ffns.items():
            sd[p + prefix + ".gate_proj.weight"] = mat(width, h)
            sd[p + prefix + ".up_proj.weight"] = mat(width, h)
            sd[p + prefix + ".down_proj.weight"] = mat(h, width)
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items()
          if k not in ("name", "torch_dtype", "serve", "source", "deployment", "reduced",
                       "assumed", "experts_held", "probe")}
    hf["n_routed_experts"] = E      # a checkpoint's config.json gives the published count
    (tmp_path / "config.json").write_text(json.dumps(hf))

    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32, experts_held=(1, 4))
    assert cfg == dataclasses.replace(CFG, name="mimo_v2", dtype="bfloat16", experts_held=(1, 4))
    assert loaded["attn"]["wqkv"].shape == (2, h, H * dk + 2 * (dk + dv))
    assert loaded["attn_window"]["wqkv"].shape == (3, h, H * dk + 4 * (dk + dv))
    assert loaded["attn_window"]["sink"].shape == (3, H) and "sink" not in loaded["attn"]
    assert [a.shape for a in loaded["moe"]["w_gu"]] == [(4, h, 2 * im)] * 4
    np.testing.assert_array_equal(   # layer 2 is window layer 1; rank 1 of 4 holds experts 4-7
        loaded["attn_window"]["sink"][1], sd["model.layers.2.self_attn.attention_sink_bias"])
    np.testing.assert_array_equal(
        loaded["attn"]["wqkv"][1][:, H * dk:H * dk + 2 * dk],
        sd["model.layers.3.self_attn.k_proj.weight"].T)
    np.testing.assert_array_equal(
        loaded["moe"]["w_gu"][2][1, :, im:], sd["model.layers.3.mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(
        loaded["moe"]["expert_bias"][0], sd["model.layers.1.mlp.gate.e_score_correction_bias"])
    # the loaded tree is the tree the engine serves: the reference reads it through the same map
    core = make_core(dataclasses.replace(cfg, dtype="float32"), async_exec=False)
    core.params = jax.device_put(loaded)
    file = dict(FILE, experts_held={"rank": 1, "of": 4, "published": 16})
    verdict, _ = held_to_reference(core, 30, 9, file=file)
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
