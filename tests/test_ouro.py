"""The looped stack (Ouro, ``ModelConfig.ut_steps`` > 1) at a tiny size on
the CPU in float32: 3 layers run 3 times a token, sandwich norms, a K/V
plane per pass. The engine is held to the plain reference
(``chipbench/reference/ouro.py``) through prefill, decode through the
cache, a megastep, a prefix hit, a preemption and every way a block leaves
the device and comes back; faults in the loop must fail the comparison."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.architectures import ouro as arch
from chipbench.reference import check
from chipbench.reference import ouro as reference
from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import PRESETS, qwen2_7b, tiny_loop, tiny_model
from dynamo_tpu.engine.kv_quant import kv_page_bytes
from dynamo_tpu.engine.model import forward_hidden, init_cache, init_params
from tests.test_engine_core import _req, run_to_completion

CFG = tiny_loop()
# what chipbench's check reads of a configuration file, for this preset
FILE = {"model_type": "ouro", "name": "tiny-loop", "vocab_size": 384, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "torch_dtype": "float32",
        "total_ut_steps": 3, "early_exit_threshold": 1}
PROMPT = [int(t) for t in np.random.RandomState(0).randint(1, 380, size=40)]
BODY = {"prompt_ids": PROMPT, "max_tokens": 17, "top": 5}
TIGHT = 1e-4   # float32 on both sides: the readings are 1e-6 to 1e-5


def make_core(**engine) -> EngineCore:
    return EngineCore(CFG, tiny_engine(**engine), seed=5)


def held_to_reference(core, got=None, cfg=FILE):
    got = got or check.score_request(core, cfg, BODY)
    return check.compare(got["served"], got["scored"]), got


def test_the_preset_is_the_file():
    from chipbench.configs import model_fields

    assert ModelConfig(**model_fields(dict(FILE))) == CFG
    assert CFG.num_cache_layers == 9 and CFG.num_layers == 3


@pytest.fixture(scope="module")
def served():
    """The default engine (megastep k = 8, the one-step-ahead loop) sent
    the probe twice: prefill, decode through three planes, a prefix hit."""
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


@pytest.mark.parametrize("engine", [
    {"megastep_k": 1, "async_exec": False},           # a dispatch a token, synchronous
    {"megastep_k": 2},                                # another megastep length
    {"scheduling": "chunked", "prefill_chunk": 16},   # the prompt in chunks, mixed steps
    {"kv_dtype": "int8"},                             # quantised planes
], ids=["k1-sync", "k2", "chunked", "int8-kv"])
def test_other_step_shapes_agree_with_reference(engine, served):
    core = make_core(**engine)
    verdict, got = held_to_reference(core)
    if engine.get("kv_dtype") == "int8":   # K/V rounded to 8 bits: near, not equal
        assert verdict["ok"] and verdict["max_abs_diff"] < 0.1, verdict
        return
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict
    assert got["served"][0]["tokens"] == served[1]["served"][0]["tokens"]


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


def _ragged_prefill(cfg, params, ids, want_gates=False):
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
        cfg, eng, want_gates=want_gates)


def test_exit_gate_probabilities_match_reference():
    params = init_params(jax.random.PRNGKey(7), CFG)
    assert params["exit_gate"]["w"].shape == (64,) and params["exit_gate"]["b"].shape == ()
    # a gate that says something: the seeded bias is zero
    params = {**params, "exit_gate": {"w": params["exit_gate"]["w"] * 2.0,
                                      "b": jnp.asarray(-0.3, jnp.float32)}}
    ids = PROMPT[:24]
    hidden, _, gates = _ragged_prefill(CFG, params, ids, want_gates=True)
    rows = list(range(len(ids)))
    logits, want = arch.reference_logits(
        params, dataclasses.asdict(CFG), ids, rows, vocab_chunks=3, every_pass=True)
    assert gates.shape == want.shape == (3, 24) and logits.shape == (3, 24, 384)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), atol=2e-5)
    assert 0.02 < float(want.min()) and float(want.max()) < 0.98   # not saturated
    assert float(np.asarray(want).std()) > 0.05                     # and not flat
    assert float(np.abs(np.asarray(want[0] - want[2])).max()) > 1e-3   # and it moves by pass
    # the hidden states the serving path returns are the last pass's
    last = arch.reference_logits(params, dataclasses.asdict(CFG), ids, rows, vocab_chunks=3)
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[2]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(hidden @ params["lm_head"]), np.asarray(last), atol=5e-5)


def _skip_between_passes():
    """The reference's norm after a pass, applied after the last pass only."""
    calls = {"n": 0}

    def pass_norm(x, weight, eps):
        calls["n"] += 1
        return reference.rms_norm(x, weight, eps) if calls["n"] % CFG.ut_steps == 0 else x

    return pass_norm


@pytest.mark.parametrize("fault", ["every_pass_on_plane_0", "no_norm_between_passes",
                                   "no_output_norms", "one_pass_fewer"])
def test_a_fault_in_the_loop_is_caught(served, fault, monkeypatch):
    core, got = served
    cfg = FILE
    if fault == "every_pass_on_plane_0":
        # the engine's side: no plane offset, so pass u reads what the last
        # pass of the step before wrote
        monkeypatch.setattr(model_mod, "cache_pages", lambda cache_l: 0)
        core = make_core()
        got = check.score_request(core, FILE, BODY)
    elif fault == "no_norm_between_passes":
        monkeypatch.setattr(reference, "pass_norm", _skip_between_passes())
    elif fault == "no_output_norms":
        monkeypatch.setattr(reference, "output_norm", lambda x, weight, eps: x)
    else:
        cfg = {**FILE, "total_ut_steps": FILE["total_ut_steps"] - 1}
    seqs = [check.score_probe(cfg, core.params, PROMPT, probe, vocab_chunks=3)
            for probe in got["served"]]
    verdict = check.compare(got["served"], {"sequences": seqs})
    assert not verdict["ok"] and verdict["max_abs_diff"] > 2 * check.LOGPROB_ATOL, verdict


# -- a block that leaves the device carries every plane ---------------------

def _held_prefill(core, rid):
    pre = _req(PROMPT, rid, max_tokens=1, ignore_eos=True)
    pre.kv_transfer_params = {"do_remote_decode": True}
    run_to_completion(core, [core.add_request(pre)])


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("how", ["wire", "direct"])
def test_disagg_payload_reproduces_the_logits_on_all_planes(how, kv_dtype):
    p_core = make_core(kv_dtype=kv_dtype)
    want = check.run_probe(p_core, PROMPT, 17, 5, "whole")
    p_core2 = EngineCore(CFG, tiny_engine(kv_dtype=kv_dtype), seed=5)
    _held_prefill(p_core2, "pf")
    d_core = EngineCore(CFG, tiny_engine(kv_dtype=kv_dtype), seed=5, params=p_core2.params)
    if how == "direct":
        n = d_core.import_blocks_direct(p_core2, "pf").imported
    else:
        descs = p_core2.export_descriptors("pf")
        assert descs[0]["shape"][0] == CFG.num_cache_layers == 9
        pages = p_core2.read_held_pages("pf", 0, len(descs))
        slot = 8 * 2 * 4 * (16 + 4 if kv_dtype == "int8" else 16 * 4)
        assert all(len(p) == 9 * slot for p in pages)
        n = d_core.import_blocks([dict(d, kv=kv) for d, kv in zip(descs, pages)]).imported
    p_core2.release_held("pf")
    assert n == 5
    got = check.run_probe(d_core, PROMPT, 17, 5, "imported")
    assert got["cached_tokens"] == 32
    assert got["tokens"] == want["tokens"]
    np.testing.assert_allclose(got["top_lps"], want["top_lps"], atol=1e-5)
    if kv_dtype == "bf16":   # and both are the reference's
        scored = check.score_probe(FILE, d_core.params, PROMPT, got, vocab_chunks=3)
        verdict = check.compare([got], {"sequences": [scored]})
        assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT, verdict


def test_a_dropped_plane_in_the_payload_is_caught():
    """The same round trip with planes 1 and 2 of every block zeroed on
    the wire: the prefix hit then reads wrong K/V on passes 1 and 2."""
    p_core = make_core()
    _held_prefill(p_core, "pf")
    descs = p_core.export_descriptors("pf")
    pages = p_core.read_held_pages("pf", 0, len(descs))
    plane = len(pages[0]) // CFG.ut_steps
    broken = [p[:plane] + bytes(len(p) - plane) for p in pages]
    d_core = EngineCore(CFG, tiny_engine(), seed=5, params=p_core.params)
    d_core.import_blocks([dict(d, kv=kv) for d, kv in zip(descs, broken)])
    got = check.run_probe(d_core, PROMPT, 17, 5, "broken")
    assert got["cached_tokens"] == 32
    scored = check.score_probe(FILE, d_core.params, PROMPT, got, vocab_chunks=3)
    verdict = check.compare([got], {"sequences": [scored]})
    assert not verdict["ok"] and verdict["max_abs_diff"] > 2 * check.LOGPROB_ATOL


def test_host_and_disk_tiers_reproduce_the_logits_on_all_planes(tmp_path):
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


def test_the_passes_are_rolled_so_a_program_holds_one_body_per_layer():
    """Trace, lower and compile time follow num_layers, not num_layers x
    ut_steps: the stack is one loop over the passes around the unrolled
    layers, so three passes trace as many matrix products as one."""
    params = init_params(jax.random.PRNGKey(0), CFG)

    def traced(cfg):
        return str(jax.make_jaxpr(lambda p: _ragged_prefill(cfg, p, PROMPT[:16])[0])(params))

    once = traced(dataclasses.replace(CFG, ut_steps=1)).count("dot_general")
    looped = traced(CFG)
    assert looped.count("dot_general") == once >= 4 * CFG.num_layers
    assert traced(dataclasses.replace(CFG, ut_steps=7)).count("dot_general") == once
    # and the loop's body branches on nothing: a value chosen by a test on its
    # index came out wrong on the v5e (model._run_stack; PERF.md, PR 27)
    assert ("scan[" in looped or "while[" in looped) and "cond[" not in looped


def test_embeddings_path_runs_the_loop():
    core = make_core()
    a = core.embed(PROMPT)
    hidden, _ = _ragged_prefill(CFG, core.params, PROMPT)
    np.testing.assert_allclose(a, np.asarray(hidden).mean(0), atol=1e-5)


# -- what a single-pass model keeps ------------------------------------------

def test_ut_steps_1_leaves_cache_and_geometry_as_they_were():
    q = qwen2_7b()
    assert (q.ut_steps, q.sandwich_norm, q.num_cache_layers) == (1, False, 28)
    eng = EngineConfig(num_kv_blocks=3072, block_size=32)
    shapes = jax.eval_shape(lambda: init_cache(q, eng))
    assert len(shapes) == 28 and {s.shape for s in shapes} == {(3073, 32, 8, 128)}
    assert kv_page_bytes(q.num_cache_layers, 32, q.num_kv_heads, q.head_dim, "bf16") == 1_835_008
    h, i, v = q.hidden_size, q.intermediate_size, q.vocab_size   # the count before ut_steps
    per_layer = h * (q.q_size + 2 * q.kv_size) + q.q_size * h + 3 * h * i + 2 * h
    assert q.param_bytes() == 2 * (v * h + 28 * per_layer + h + h * v) == 15_230_974_976
    assert q.quantized_param_bytes() == (
        28 * (per_layer - 2 * h) + h * v + 2 * (v * h + 2 * h * 28 + h))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), q))
    assert "exit_gate" not in params and "attn_post_norm" not in params["layers"]

    core = EngineCore(tiny_model(), tiny_engine(), seed=0)
    assert core._page_geometry() == (2, 8, 2, 16)
    assert core.kv_cache_stats()["bytes_per_block"] == 2 * 8 * 2 * 2 * 16 * 4
    st = core.scheduler_stats()
    assert st["kv_cache_layers"] == 2 and st["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4


def test_the_published_size_counts_its_planes():
    o = PRESETS["ouro-2.6b"]()
    assert (o.num_layers, o.ut_steps, o.num_cache_layers) == (48, 4, 192)
    assert o.param_bytes() == 2 * 2_667_974_657
    assert kv_page_bytes(o.num_cache_layers, 32, 16, 128, "bf16") == 50_331_648
    eng = EngineConfig(num_kv_blocks=168, block_size=32)
    shapes = jax.eval_shape(lambda: init_cache(o, eng))
    assert len(shapes) == 48 and {s.shape for s in shapes} == {(4 * 169, 32, 32, 128)}
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), o))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) - 1   # fuse_tp
    assert n == 2_667_974_657
    assert params["layers"]["attn_post_norm"].shape == (48, 2048)


def test_counters_and_gauges_of_the_loop(served):
    core, _ = served
    st = core.scheduler_stats()
    assert st["kv_cache_layers"] == 9 and st["kv_bytes_per_token"] == 9 * 2 * 4 * 16 * 4
    assert core.kv_cache_stats()["bytes_per_block"] == 8 * st["kv_bytes_per_token"]
    # two probes: a prefill (1 lane x 1) and two megasteps (1 lane x 8) each
    assert st["layer_passes"] == 2 * (1 + 2 * 8) * 3
    assert st["layer_passes"] == 3 * st["committed_tokens"]
    from dynamo_tpu.runtime.status_server import ENGINE_COUNTERS, SCHEDULER_GAUGES

    assert ENGINE_COUNTERS["layer_passes"][0] == "engine_layer_passes"
    assert {"kv_cache_layers", "kv_bytes_per_token"} <= set(SCHEDULER_GAUGES)


def test_pp_and_adaptive_exit_are_refused():
    from dynamo_tpu.backends.jax.main import build_engine
    from dynamo_tpu.parallel.pipeline import pp_param_specs

    with pytest.raises(ValueError, match="looped"):
        build_engine(preset="tiny-loop", pp=2)
    with pytest.raises(ValueError, match="ut_steps"):
        pp_param_specs(dataclasses.replace(CFG, num_layers=4), 2)
    with pytest.raises(NotImplementedError, match="adaptive exit"):
        dataclasses.replace(CFG, early_exit_threshold=0.9)
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, ut_steps=0)
    # under 1 means nothing for a single pass
    assert ModelConfig(early_exit_threshold=0.5).ut_steps == 1


def test_tp_shards_the_looped_stack_like_any_dense_model(served):
    from dynamo_tpu.parallel.sharding import make_mesh, param_partition_specs

    specs = param_partition_specs(CFG, 2)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG, 2))
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict)) == (
        jax.tree.structure(params))
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    core = EngineCore(CFG, tiny_engine(), seed=5, mesh=make_mesh(dp=1, tp=2))
    got = check.run_probe(core, PROMPT, 17, 5, "tp2")   # the same weights in tp=2 column
    want = served[1]["served"][0]                        # order: held to the tp=1 engine
    assert got["tokens"] == want["tokens"]
    np.testing.assert_allclose(got["top_lps"], want["top_lps"], atol=1e-4)


# -- the checkpoint's names ---------------------------------------------------

def test_loader_takes_the_checkpoints_names(tmp_path):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    h, i, v, L, q = 64, 128, 384, 3, 64
    rng = np.random.RandomState(11)
    mat = lambda out, inp: (rng.randn(out, inp) * inp ** -0.5).astype(np.float32)  # noqa: E731
    norm = lambda: (1.0 + 0.1 * rng.randn(h)).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": norm(),
          "lm_head.weight": mat(v, h),
          "model.early_exit_gate.weight": mat(1, h),
          "model.early_exit_gate.bias": np.asarray([0.25], np.float32)}
    for l in range(L):
        p = f"model.layers.{l}."
        for name, (out, inp) in {"self_attn.q_proj": (q, h), "self_attn.k_proj": (q, h),
                                 "self_attn.v_proj": (q, h), "self_attn.o_proj": (h, q),
                                 "mlp.gate_proj": (i, h), "mlp.up_proj": (i, h),
                                 "mlp.down_proj": (h, i)}.items():
            sd[p + name + ".weight"] = mat(out, inp)
        for name in ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
                     "post_attention_layernorm_2"):
            sd[p + name + ".weight"] = norm()
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items() if k not in ("name", "torch_dtype")}
    (tmp_path / "config.json").write_text(json.dumps(hf))

    cfg, params = load_hf_llama(tmp_path, dtype=jnp.float32)
    assert (cfg.ut_steps, cfg.sandwich_norm, cfg.attn_qkv_bias) == (3, True, False)
    assert cfg.early_exit_threshold == 1 and cfg.num_cache_layers == 9
    np.testing.assert_array_equal(params["layers"]["attn_post_norm"][2],
                                  sd["model.layers.2.input_layernorm_2.weight"])
    np.testing.assert_array_equal(params["layers"]["mlp_post_norm"][0],
                                  sd["model.layers.0.post_attention_layernorm_2.weight"])
    np.testing.assert_array_equal(params["exit_gate"]["w"],
                                  sd["model.early_exit_gate.weight"][0])
    assert params["exit_gate"]["b"].shape == () and float(params["exit_gate"]["b"]) == 0.25

    cfg = dataclasses.replace(cfg, dtype="float32")
    params = jax.device_put(params)
    ids = PROMPT[:24]
    hidden, _ = _ragged_prefill(cfg, params, ids)
    want = arch.reference_logits(params, dataclasses.asdict(cfg), ids, list(range(24)),
                                 vocab_chunks=3)
    np.testing.assert_allclose(np.asarray(hidden @ params["lm_head"]), np.asarray(want),
                               atol=5e-5)
    (tmp_path / "config.json").write_text(json.dumps({**hf, "early_exit_threshold": 0.5}))
    with pytest.raises(NotImplementedError):
        load_hf_llama(tmp_path, dtype=jnp.float32)
