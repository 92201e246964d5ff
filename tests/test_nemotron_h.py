"""A model of one-sub-layer blocks (``tiny-nemotron-h``: Mamba-2 mixers whose
state lives in a slab a lane, attention without rope, un-gated ``relu^2``
experts of which half are held): the engine against the plain reference
(chipbench/reference/nemotron_h.py, token by token, float32) through prefill
and decode, the slab's rules under preemption, the share of the experts
against the uncut layer, refusals by name, and the parity of the models whose
paths the expert-activation and "has a slab" generalisations run through. The
recurrence alone: tests/test_ssm.py."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import nemotron_h as arch
from chipbench.configs import load_config, model_fields
from chipbench.reference import check
from chipbench.reference import nemotron_h as reference
from dynamo_tpu.engine import EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    PRESETS,
    UnsupportedModelOption,
    nemotron3_nano_ep2_14l,
    tiny_engine as tiny_engine_cfg,
    tiny_model,
    tiny_nemotron_h,
)
from dynamo_tpu.ops import expert_stream, grouped_matmul, ssm
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_nemotron_h()
FILE = load_config("tiny-nemotron-h-rehearsal")
MF = model_fields(FILE)
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=80)]
# a wave of 35 rows (two scan chunks of 16 and one of 3), then 11 recurrent steps
# from the slab, a block edge (8) crossed while decoding
BODY = {"prompt_ids": PROMPT[:35], "max_tokens": 11, "top": 5}
# float32 on both sides: a chunk of 16 rows is a handful of products where the
# reference takes 16 turns of the recurrence; the readings are ~5e-6. A state
# kept in bfloat16 reads 3e-3 and more (the controls below): 100 x this.
TIGHT = 5e-5


def make_core(cfg=CFG, **engine) -> EngineCore:
    return EngineCore(cfg, tiny_engine(**engine), seed=5)


def test_the_preset_is_the_file():
    assert dataclasses.replace(ModelConfig(**MF), name="tiny-nemotron-h") == CFG
    assert CFG.ssm and CFG.has_slab and CFG.single_sublayer and CFG.layer_groups
    assert not CFG.linear and not CFG.hybrid and not CFG.windowed and CFG.rope_theta is None
    assert CFG.layers_of("ssm") == (0, 2, 4) and CFG.layers_of("attention") == (5,)
    assert CFG.sparse_layers == CFG.layers_of("none") == (1, 3, 6)
    assert CFG.cache_layer_counts == {"attention": 1, "conv": 0, "ssm": 3, "none": 3}
    assert CFG.slab_shapes(9) == {"state": (9, 4, 16, 32), "conv": (9, 3, 1, 192)}
    assert CFG.state_bytes_per_sequence() == 3 * (4 * 16 * 32 * 4 + 3 * 192 * 4)
    # an un-gated expert 96 wide is STORED 128 wide; a gated one as it is
    assert not CFG.gated_mlp and CFG.expert_stored_width == 128 and CFG.shared_expert_width == 192
    assert tiny_model().gated_mlp and not tiny_model().has_slab
    assert "tiny-nemotron-h" in PRESETS and "nemotron-3-nano-30b-a3b-ep2-14l" in PRESETS


def test_counts_of_the_published_size_by_hand():
    big = nemotron3_nano_ep2_14l()
    mamba = 2688 * 10304 + 6144 * 4 + 6144 + 192 + 4096 + 4096 * 2688 + 2688
    attn = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    experts = 64 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688
    assert (mamba, attn) == (38_744_896, 23_399_040)
    total = 6 * mamba + 2 * attn + 6 * experts + 2 * 131072 * 2688 + 2688
    assert big.param_bytes() == 2 * total and round(big.param_bytes() / 1e9, 2) == 9.87
    assert big.state_bytes_per_sequence() == 6 * (2_097_152 + 36_864) == 12_804_096
    assert big.slab_shapes(129) == {"state": (129, 64, 64, 128), "conv": (129, 3, 48, 128)}
    assert big.expert_stored_width == 1920 and big.kv_page_tail(32) == (32, 4, 128)
    assert big.experts_held_range == (0, 64) and big.layers_of("attention") == (5, 12)


@pytest.fixture(scope="module")
def served():
    """The default engine (megastep k = 8, the one-step-ahead loop) sent the
    probe twice: a prefill wave, then decode through cache and slab; nothing
    of the first send is found by the second."""
    core = make_core()
    return core, check.score_request(core, FILE, BODY)


def test_prefill_then_decode_through_cache_and_slab_agree_with_one_full_forward(served):
    core, got = served
    assert core.engine.megastep == 8 and core.pipelined
    verdict = check.compare(got["served"], got["scored"], atol=TIGHT)
    assert verdict["ok"] and verdict["compared"] == 2 * 11 * 5, verdict
    assert verdict["argmax_mismatches"] == 0
    first, repeat = got["served"]
    assert first["tokens"] == repeat["tokens"] and len(first["tokens"]) == 11
    # no block holds a mamba layer's state: prefix caching is off, nothing is found
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0
    assert core.engine.enable_prefix_caching is False


def test_the_slab_the_empty_entries_and_the_counters_are_on_the_status_surface(served):
    core, _ = served
    st = core.scheduler_stats()
    assert st["cache_layers"] == {"attention": 1, "conv": 0, "ssm": 3, "none": 3}
    assert st["state_bytes_per_sequence"] == CFG.state_bytes_per_sequence()
    assert st["state_slots"] == {"held": 0, "free": 8} and st["state_replayed_tokens"] == 0
    assert st["kv_bytes_per_token"] == 2 * 1 * 128 * 4 and list(st["cache_page_shape"]) == ["attention"]
    assert [jax.tree.structure(c).num_leaves for c in core.cache] == [2, 0, 2, 0, 2, 1, 0]
    assert core.cache[0]["state"].dtype == jnp.float32 and core.cache[1] == {}
    calls = ssm.traced_calls()
    assert calls["step", "jnp"] >= 3 and calls["scan", "jnp"] >= 3
    assert grouped_matmul.traced_calls()["step", "all_rows"] >= 3      # the CPU's expert path
    from dynamo_tpu.runtime.status_server import ENGINE_COUNTERS

    assert "state_replayed_tokens" in ENGINE_COUNTERS


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_of_the_references_faults_moves_it_off_the_engine(fault, served):
    """The comparison is tight enough that the state kept in bfloat16, the
    reference a precision lower, or any one mechanism left out, fails it."""
    core, got = served
    assert set(reference.FAULTS) >= {"state_bf16", "conv_bias", "skip_d", "gate_after_norm",
                                     "relu", "scale", "choice_bias", "fp8"}
    probe = got["served"][0]
    scored = check.score_probe(FILE, core.params, BODY["prompt_ids"], probe, faults=(fault,))
    verdict = check.compare([probe], {"sequences": [scored]}, atol=TIGHT)
    assert not verdict["ok"] and verdict["max_abs_diff"] > 20 * TIGHT, (fault, verdict)


def test_a_preempted_sequence_replays_from_position_0_to_the_same_stream():
    """No block holds a mamba layer's state: a sequence preempted (by hand, 9
    tokens in) is re-admitted into ANOTHER slot whose state and rows hold NaN,
    replays all it had run from position 0, and goes on to the stream it gives
    unpressed."""
    core = make_core(async_exec=False)
    first = core.add_request(_req(PROMPT[:21], "unpressed", max_tokens=20, ignore_eos=True))
    want = run_to_completion(core, [first])[0]["unpressed"]
    assert sorted(core._free_slots) == list(range(8))
    core.cache = tuple(jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), c)
                       if isinstance(c, dict) else c for c in core.cache)
    seq = core.add_request(_req(PROMPT[:21], "s0", max_tokens=20, ignore_eos=True))
    got = []
    while seq.generated < 9:
        for _, out in core.step():
            got += list(out.token_ids)
    held = seq.slot
    with core._step_lock:
        core._preempt(seq)
        core._free_slots.insert(0, core._free_slots.pop())   # the next taker gets another slot
    assert seq.slot == -1 and held >= 0
    assert core.exec_stats["state_replayed_tokens"] == 21 + seq.generated - 1
    done, _ = run_to_completion(core, [seq])
    assert got + done["s0"] == want and seq.num_cached_tokens == 0
    assert sorted(core._free_slots) == list(range(8))


def test_the_shares_of_two_ranks_add_up_to_the_uncut_references_expert_layer():
    """Guide section 4: the routed parts that ranks 0 and 1 give, with the shared
    expert counted once, are the uncut reference's whole expert layer."""
    x = jax.random.normal(jax.random.PRNGKey(1), (9, CFG.hidden_size), jnp.float32)
    outs, leaves = {}, {}
    for held in ((0, 2), (1, 2), None):
        cfg = tiny_nemotron_h(experts_held=held)
        lp = model_mod.layer_params(model_mod.init_params(jax.random.PRNGKey(5), cfg), 1, cfg)
        outs[held], leaves[held] = model_mod._shared_sparse_mlp(x, lp, cfg), lp
    whole = leaves[None]
    np.testing.assert_array_equal(                    # the shares are shares of ONE model
        jnp.concatenate([leaves[0, 2]["w_gu"], leaves[1, 2]["w_gu"]]), whole["w_gu"])
    shared = reference.relu2_mlp(x, whole["shared_wgu"], whole["shared_down"])
    with jax.default_matmul_precision("highest"):
        weights = reference.routing_weights(x, whole["w_router"], whole["expert_bias"],
                                            top_k=2, scale=2.5)
        uncut = shared + sum(
            weights[:, e, None] * reference.relu2_mlp(x, whole["w_gu"][e, :, :96], whole["w_down"][e, :96])
            for e in range(8))
    np.testing.assert_allclose(outs[0, 2] + outs[1, 2] - shared, uncut, atol=2e-5)
    np.testing.assert_allclose(outs[None], uncut, atol=2e-5)
    assert float(jnp.abs(outs[0, 2] - outs[1, 2]).max()) > 1e-2      # and the two shares differ


@pytest.mark.parametrize("rows,gated", [
    (20, False), pytest.param(12, True, marks=pytest.mark.slow)])   # tests/test_expert_stream.py holds the gated kernels
def test_the_stream_kernels_honour_the_activation(rows, gated):
    """Both kernels of ops/expert_stream.py (``interpret=True``) against the loop
    of products that defines them, for an un-gated expert stored with zero
    columns and for a gated one."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    Eh, h, im = 3, 128, 128
    x = jax.random.normal(k[0], (rows, h), jnp.float32)
    w_gu = jax.random.normal(k[1], (Eh, h, (2 if gated else 1) * im), jnp.float32) * h ** -0.5
    w_down = jax.random.normal(k[2], (Eh, im, h), jnp.float32) * im ** -0.5
    if not gated:   # the stored pad: zeros behind the published width
        w_gu, w_down = w_gu.at[:, :, 96:].set(0.0), w_down.at[:, 96:].set(0.0)
    chosen = jax.random.bernoulli(k[3], 0.6, (rows, Eh))
    w_held = jnp.where(chosen, jax.random.uniform(k[4], (rows, Eh)), 0.0)
    want = model_mod._experts_all_rows(x, w_held, w_gu, w_down, gated)
    got = expert_stream.expert_stream(x, w_held, w_gu, w_down, gated=gated, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = jnp.sum(chosen, axis=0, dtype=jnp.int32)
    padded = -(-counts // 16) * 16
    start = jnp.cumsum(padded) - padded
    P = int(-(-(rows * Eh + 15 * Eh) // 64) * 64)
    sorted_rows = np.zeros(P, np.int32)
    for e in range(Eh):
        idx = np.flatnonzero(np.asarray(chosen[:, e]))
        sorted_rows[int(start[e]):int(start[e]) + len(idx)] = idx
    y = expert_stream.expert_stream_grouped(x[sorted_rows], start, counts, w_gu, w_down,
                                            gated=gated, interpret=True)
    for e in range(Eh):
        a, n = int(start[e]), int(counts[e])
        np.testing.assert_allclose(
            y[a:a + n], model_mod._swiglu(x[sorted_rows[a:a + n]], w_gu[e], w_down[e], gated),
            atol=2e-5)


def _parents_swiglu(x, w_gu, w_down):
    """``model._swiglu`` as PR 53 left it, kept here un-generalised."""
    gu = jnp.dot(x, w_gu, preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    act = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.dot(act, w_down, preferred_element_type=jnp.float32)


def _wave_logits(name: str) -> np.ndarray:
    """Last-row logits of a ragged wave of two sequences (13 and 9 rows) through
    ``forward_tokens`` on the preset's seeded weights: what
    tests/data/parity_logits_pr53.json holds from the parent commit."""
    cfg = PRESETS[name]()
    eng = tiny_engine_cfg(block_size=8, num_kv_blocks=16, max_model_len=64, max_num_seqs=4)
    params = model_mod.init_params(jax.random.PRNGKey(7), cfg)
    cache = model_mod.init_cache(cfg, eng)
    T, lens, P = 24, (13, 9), eng.max_blocks_per_seq
    rng = np.random.RandomState(3)
    tokens, positions, write_offs = (np.zeros(T, np.int32) for _ in range(3))
    write_pages = np.full(T, eng.garbage_block, np.int32)
    width = P + (1 if cfg.has_slab else 0)
    table = np.full((4, width), eng.garbage_block, np.int32)
    table[:, P:] = eng.garbage_slot
    at = 0
    for s, n in enumerate(lens):
        ids, pos = np.arange(2 * s, 2 * s + 2, dtype=np.int32), np.arange(n, dtype=np.int32)
        tokens[at:at + n], positions[at:at + n] = rng.randint(1, 380, size=n), pos
        write_pages[at:at + n], write_offs[at:at + n] = ids[pos // 8], pos % 8
        table[s, :2] = ids
        table[s, P:] = s
        at += n
    logits, _ = jax.jit(lambda *a: model_mod.forward_tokens(*a, cfg, eng))(
        params, cache, *(jnp.asarray(a) for a in (
            tokens, positions, write_pages, write_offs, np.array([13, 9, 0, 0], np.int32), table,
            np.array([0, 13, 22, 22, 22], np.int32), np.array([2], np.int32),
            np.array([12, 21, 0, 0], np.int32))))
    return np.asarray(logits[:2, :24], np.float32)


@pytest.mark.parametrize("name", [
    "tiny-moe", "swiglu",
    *(pytest.param(n, marks=pytest.mark.slow)    # 3-6 s each: outside the tier-1 budget
      for n in ("tiny-axk1", "tiny-lfm2", "tiny-olmo-hybrid"))])
def test_the_old_paths_give_the_parents_logits(name):
    """mixtral's, A.X-K1's, LFM2's and Olmo-Hybrid's tiny presets after the
    expert-activation and "has a slab" generalisations: the parent commit's
    logits on one seed (the stored values were computed op by op outside pytest
    and were bit-equal there on both commits, where the lowered programs also
    hash alike: ``tools/lowered_hashes.py``; 2e-5 of room here for the jitted
    program's and another CPU's order of float32 sums, on logits of order 1),
    and the gated expert BIT FOR BIT against the un-generalised function."""
    if name == "swiglu":
        k = jax.random.split(jax.random.PRNGKey(4), 3)
        x, w_gu, w_down = (jax.random.normal(k[0], (5, 64)), jax.random.normal(k[1], (64, 96)),
                           jax.random.normal(k[2], (48, 64)))
        np.testing.assert_array_equal(model_mod._swiglu(x, w_gu, w_down),
                                      _parents_swiglu(x, w_gu, w_down))
        return
    stored = json.loads((Path(__file__).parent / "data" / "parity_logits_pr53.json").read_text())
    np.testing.assert_allclose(_wave_logits(name), np.asarray(stored[name], np.float32),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("change,error", [
    ({"layer_types": ("moe", "full_attention") * 3 + ("moe",)}, NotImplementedError),  # no 'mamba'
    ({"layer_types": ("mamba", "moe") * 3 + ("conv",)}, NotImplementedError),
    ({"layer_types": ("mamba", "full_attention") * 3 + ("mamba",)}, NotImplementedError),  # no 'moe'
    ({"ssm_state_size": 0}, ValueError),
    ({"ssm_n_groups": 3}, ValueError),
    ({"rope_theta": 10000.0}, NotImplementedError),
    ({"post_norm": True}, NotImplementedError),
    ({"qk_norm": True}, NotImplementedError),
    ({"router_scoring": "softmax", "router_bias": False, "routed_scaling_factor": 1.0},
     NotImplementedError),
    ({"first_dense_layers": 1}, NotImplementedError),
    ({"mlp_activation": "gelu"}, ValueError),
    ({"moe_intermediate_size": 0, "num_shared_experts": 0, "shared_expert_intermediate_size": 0,
      "routed_scaling_factor": 1.0, "experts_held": None, "router_bias": False,
      "mlp_activation": "swiglu", "router_scoring": "softmax"}, NotImplementedError),  # mixtral's MLP
])
def test_a_combination_no_test_compares_raises_by_name(change, error):
    with pytest.raises(error):
        dataclasses.replace(CFG, **change)


def test_the_ssm_fields_mean_nothing_to_a_model_without_such_layers():
    with pytest.raises(ValueError, match="ssm_num_heads"):
        dataclasses.replace(tiny_model(), ssm_num_heads=4)
    with pytest.raises(ValueError, match="mlp_activation"):
        dataclasses.replace(tiny_model(), mlp_activation="relu2")
    with pytest.raises(NotImplementedError, match="mamba"):
        dataclasses.replace(PRESETS["tiny-olmo-hybrid"](),
                            layer_types=("mamba",) + PRESETS["tiny-olmo-hybrid"]().layer_types[1:])


@pytest.mark.parametrize("option,build", [
    ("prefix_caching", lambda: make_core(enable_prefix_caching=True)),
    ("kv_dtype", lambda: make_core(kv_dtype="int8")),
    ("host_kv_blocks", lambda: make_core(host_kv_blocks=8)),
    ("spec_decode", lambda: make_core(spec_decode="ngram")),
    ("ring_prefill", lambda: make_core(ring_prefill_threshold=64)),
])
def test_an_option_a_per_lane_state_does_not_carry_is_refused_at_start_up(option, build):
    with pytest.raises(UnsupportedModelOption) as e:
        build()
    assert e.value.option == option and "tiny-nemotron-h" in str(e.value)


def test_loader_takes_the_checkpoints_names(tmp_path):
    """A checkpoint written under the family's names (``backbone.layers.N.mixer.*``)
    loads into the tree ``init_params`` draws, the held experts stored with their
    zero columns and rows."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    params = model_mod.init_params(jax.random.PRNGKey(9), tiny_nemotron_h(experts_held=None))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    sd = {"backbone.embeddings.weight": f32(params["embed"]),
          "backbone.norm_f.weight": f32(params["final_norm"]),
          "lm_head.weight": f32(params["lm_head"]).T}
    at = {"mamba": 0, "full_attention": 0, "moe": 0}
    for l, kind in enumerate(CFG.layer_types):
        pre, i = f"backbone.layers.{l}.", at[kind]
        at[kind] += 1
        sd[pre + "norm.weight"] = f32(params["layers"]["attn_norm"][l])
        kind_w, w = arch.published_layout(params, l, {**MF, "experts_held": None})
        assert kind_w == kind
        if kind == "mamba":
            m = params["ssm"]
            sd[pre + "mixer.in_proj.weight"] = f32(w["in_proj"]).T
            sd[pre + "mixer.conv1d.weight"] = f32(m["conv_w"][i]).T[:, None, :]
            sd[pre + "mixer.conv1d.bias"] = f32(m["conv_b"][i])
            for name in ("A_log", "D", "dt_bias"):
                sd[pre + f"mixer.{name}"] = f32(m[name][i])
            sd[pre + "mixer.norm.weight"] = f32(m["ssm_norm"][i])
            sd[pre + "mixer.out_proj.weight"] = f32(m["w_out"][i]).T
        elif kind == "full_attention":
            for name in ("q", "k", "v", "o"):
                sd[pre + f"mixer.{name}_proj.weight"] = f32(w[f"w{name}"]).T
        else:
            sd[pre + "mixer.gate.weight"] = f32(w["w_router"]).T
            sd[pre + "mixer.gate.e_score_correction_bias"] = f32(w["bias"])
            for e, w_up, w_down in w["experts"]:
                sd[pre + f"mixer.experts.{e}.up_proj.weight"] = f32(w_up).T
                sd[pre + f"mixer.experts.{e}.down_proj.weight"] = f32(w_down).T
            m = params["moe"]
            sd[pre + "mixer.shared_experts.up_proj.weight"] = f32(m["shared_wgu"][i]).T
            sd[pre + "mixer.shared_experts.down_proj.weight"] = f32(m["shared_down"][i]).T
    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
              str(tmp_path / "model.safetensors"))
    hf = {k: v for k, v in FILE.items() if k not in ("serve", "name", "experts_held")}
    hf["n_routed_experts"] = 8
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32, experts_held=(1, 2))
    assert dataclasses.replace(cfg, name=CFG.name, dtype="float32") == tiny_nemotron_h(
        experts_held=(1, 2))
    want = model_mod.init_params(jax.random.PRNGKey(9), tiny_nemotron_h(experts_held=(1, 2)))
    flat_want, tree = jax.tree.flatten(want)
    flat_got, tree_got = jax.tree.flatten(jax.tree.map(jnp.asarray, loaded))
    assert tree == tree_got
    for a, b in zip(flat_want, flat_got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_smoke_holds_a_tpu_worker_to_the_ssm_kernel():
    import chip_smoke

    chip_smoke.judge_ssm_traced("w", {"step/pallas": 6.0, "scan/jnp": 6.0}, "tpu")
    chip_smoke.judge_ssm_traced("w", {"step/jnp": 3.0}, "cpu")
    with pytest.raises(chip_smoke.PhaseFailed, match="jnp path on a TPU"):
        chip_smoke.judge_ssm_traced("w", {"step/jnp": 3.0}, "tpu")
