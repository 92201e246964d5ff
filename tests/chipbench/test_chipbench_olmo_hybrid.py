"""The ninth architecture, added as files only: ``model_type`` "olmo_hybrid"
(gated-delta-rule layers whose float32 state lives in a slab a lane beside the
paged K/V of multi-head layers without rope). Its key map pinned for the
cell's configuration, the published keys unchanged but the two cuts, its
counts by hand and against the program's leaves and slab, the cell as ISSUE
50 sizes it, its reference on the engine's own tree at the tiny size with the
four faults its controls name, the new reader on a canned trace, and the
whole command on its rehearsal configuration."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import olmo_hybrid
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.readers import scope_roofline, state_roofline
from chipbench.reference import check
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_olmo_hybrid.json"
NAME, CELL = "olmo-hybrid-7b-pp2-16l-bf16", "olmo-hybrid-7b-reason-decode"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": PERIOD * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
CUT = {"num_hidden_layers": 16, "layer_types": PERIOD * 4}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "lfm2_moe", "olmo_hybrid"} <= set(architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is olmo_hybrid
    assert all(hasattr(olmo_hybrid, name) for name in architectures.SURFACE)
    mf = model_fields(cfg)
    assert mf == dict(
        vocab_size=100352, hidden_size=3840, intermediate_size=11008, num_layers=16,
        num_heads=30, num_kv_heads=30, head_dim=128, rope_theta=None, rms_norm_eps=1e-06,
        tie_embeddings=False, dtype="bfloat16", layer_types=CUT["layer_types"], qk_norm=True,
        qk_norm_over="projection", post_norm=True, linear_num_key_heads=30,
        linear_num_value_heads=30, linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import olmo_hybrid_7b_pp2_16l

    model = ModelConfig(**mf)
    assert model == dataclasses.replace(olmo_hybrid_7b_pp2_16l(), name=NAME)
    assert model.param_bytes() == 8_201_577_888 and model.linear and not model.hybrid
    # a value the equations do not cover is refused, not ignored
    with pytest.raises(ValueError, match="olmo_hybrid"):
        model_fields({**cfg, "rope_parameters": {"rope_theta": 500000.0}})
    with pytest.raises(ValueError, match="olmo_hybrid"):
        model_fields({**cfg, "attention_bias": True})
    with pytest.raises(ValueError, match="olmo_hybrid"):
        model_fields({**cfg, "num_hidden_layers": 15})
    with pytest.raises(NotImplementedError, match="linear_num_key_heads"):
        ModelConfig(**model_fields({**cfg, "linear_num_key_heads": 15}))


def test_the_file_holds_the_published_keys_unchanged_but_the_two_cuts():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    # the cut keeps four whole periods, the published 3:1, in the published order
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:16]
    assert cfg["serve"]["quant"] is None and cfg["torch_dtype"] == "bfloat16"
    assert cfg["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    for words in ("2 stages of 16 layers", "stage 0", "final norm and the head",
                  "--pp is not engaged", "twice a deployment's"):
        assert words in cfg["deployment"], words
    assert {"norm_placement", "qk_norm", "nope", "head_dim", "linear_attention", "torch_dtype",
            "parameter_names", "weights", "serve"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    # the guide's floors: a whole period, at least four layers, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= max(4, len(PERIOD)) and cfg["num_hidden_layers"] % 4 == 0
    assert cfg["vocab_size"] == PUBLISHED["vocab_size"]
    # no width in reduced
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cfg["reduced"])
    # the probe the configuration sizes: two scan chunks and a block edge crossed while decoding
    assert set(cfg.get("probe", {})) <= {"prompt_tokens", "max_tokens"}


def test_counts_by_hand_and_against_the_programs_leaves_and_slab():
    import jax

    from dynamo_tpu.engine import EngineConfig, ModelConfig
    from dynamo_tpu.engine import model as model_mod

    cfg = load_config(NAME)
    mf = model_fields(cfg)
    h, H, dk, dv = 3840, 30, 96, 192
    assert olmo_hybrid.linear_channels(mf) == 2 * H * dk + H * dv == 11520
    assert olmo_hybrid.linear_matrix_params(mf) == 2 * h * H * dk + 3 * h * H * dv + 2 * h * H
    assert olmo_hybrid.attention_params(mf) == 4 * h * h + 2 * h == 58_990_080
    # what a decode step of the program reads: EVERY leaf but the embedding table and the
    # layout marker (A_log and dt_bias are float32, the rest bf16)
    model = ModelConfig(**mf)
    params = jax.eval_shape(lambda: model_mod.init_params(jax.random.PRNGKey(0), model))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    want = olmo_hybrid.decode_weight_bytes(mf, None)
    assert want == leaves - 4 - 100352 * h * 2 == 7_430_875_968
    assert olmo_hybrid.decode_weight_bytes(mf, None, architectures.Observed(3.0)) == want
    with pytest.raises(ValueError, match="unquantised"):
        olmo_hybrid.decode_weight_bytes(mf, "int8")
    # 61,440 B a token: four full layers of 2 x 30 x 128 values; the linear layers hold none
    assert olmo_hybrid.kv_bytes_per_token(mf) == 4 * 2 * 30 * 128 * 2 == 61_440
    # the slab: the program's own arrays at 49 lane slots, and what a sequence holds of them
    engine = EngineConfig(**engine_overrides(cfg))
    cache = jax.eval_shape(lambda: model_mod.init_cache(model, engine))
    slab = [c for c in cache if isinstance(c, dict)]
    assert len(slab) == 12 and engine.state_slots == 49
    assert {k: (v.shape, str(v.dtype)) for k, v in slab[0].items()} == {
        "state": ((49, H // 2, dk, 2 * dv), "float32"),
        "conv": ((49, 3, 90, 128), "bfloat16")}
    per_slot = sum(v.size * v.dtype.itemsize for c in slab for v in c.values()) // 49
    assert per_slot == olmo_hybrid.state_bytes_per_sequence(mf) == 27_371_520
    assert per_slot == model.state_bytes_per_sequence()
    # ONE linear layer's step: every live lane's float32 state read once and written once
    assert olmo_hybrid.state_step_bytes_per_layer(48, mf) == 2 * H * dk * dv * 4 * 48
    assert 12 * olmo_hybrid.state_step_bytes_per_layer(48, mf) == 2_548_039_680   # 2.55 GB a step
    # ONE full layer's call: 48 lanes at ~1090 tokens, 35 blocks each of 32 x 15,360 B
    assert olmo_hybrid.attn_decode_bytes_per_layer([1090] * 48, mf, 32) == 48 * 35 * 32 * 15360
    assert olmo_hybrid.forward_flops_per_token(mf, 1000) == int(
        2 * (12 * (olmo_hybrid.linear_matrix_params(mf) + 4 * 11520) + 4 * 4 * h * h
             + 16 * 3 * h * 11008 + h * 100352)
        + 12 * 6 * H * dk * dv + 4 * 4 * 30 * 128 * 1000)


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == [] and len(man["workloads"]) >= 9
    assert [w["name"] for w in man["workloads"]].count(CELL) == 1
    assert [c["name"] for c in man["configs"]].count(NAME) == 1
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "hybrid-reason-decode", 1)
    assert manifest.topology_of(cell) == "one-worker"
    # the rules of form a driver holds every entry to
    for entry in man["configs"] + man["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200 and entry.get(key, "x").isprintable()
    assert len(json.dumps(man)) < 64 * 1024 and len(man["per_layer"]) <= 128
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    for reader in (
            "tokens_per_dispatch", "decode_step_device_ms", "decode_weight_floor_share",
            "decode_step_mfu", "device_idle_share", "hbm_peak_share", "closed_loop_ttft_ms_p50",
            "host_ms_per_dispatch", "decode_lane_occupancy", "preemptions_per_kdispatch",
            "lm_head_time_share", "unscoped_time_share", "prefill_wave_fill",
            "prefill_device_ms_per_ktok", "decode_ms_per_token", "prefill_stall_ms_per_token",
            "host_stall_ms_per_token", "device_starved_share", "device_account_error",
            "attn_kernel_time_share", "attn_decode_roofline", "warmup_s", "compile_s",
            "trace_lower_s", "correct_check_s"):
        assert layer_entry(man, reader, CELL) is not None, reader
    # the five new entries: data over readers that were there, and one new reader
    for name, reader, args in (
            ("linear_attn_time_share", "scope_share",
             {"scope": "linear", "module": "_megastep_body"}),
            ("linear_state_time_share", "scope_share",
             {"scope": "state_step", "module": "_megastep_body"}),
            ("linear_scan_time_share", "scope_share",
             {"scope": "state_scan", "module": "_prefill_and_sample"}),
            ("linear_state_roofline", "state_roofline",
             {"scope": "state_step", "module": "_megastep_body"})):
        entry = layer_entry(man, name, CELL)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_ms_p50"
        spec = json.loads(manifest.metric_file("per_layer", entry["name"]).read_text())
        assert spec["reader"] == reader and spec["args"] == args
    guard = layer_entry(man, "state_replayed_tokens_per_ktok", CELL)
    assert guard["moves"] == "output_tokens_per_s" and guard["workloads"] == [CELL]
    assert layer_entry(man, "linear_state_roofline", CELL)["unit"] == "%"
    # the traffic, letter for letter
    traffic = generators.load_traffic(cell["traffic"])
    assert {k: traffic[k] for k in ("kind", "clients", "pool_per_client", "prompt_tokens",
                                    "output_tokens", "output_quantum", "ramp_seconds")} == {
        "kind": "closed_loop", "clients": 48, "pool_per_client": 8,
        "prompt_tokens": {"dist": "uniform", "lo": 128, "hi": 512},
        "output_tokens": {"dist": "uniform", "lo": 1024, "hi": 2048},
        "output_quantum": 8, "ramp_seconds": 15}
    assert "temperature" not in traffic and "think" not in " ".join(traffic)
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["clients"] == engine["max_num_seqs"] == engine["decode_buckets"][-1] == 48
    worst = traffic["prompt_tokens"]["hi"] + 16 + traffic["output_tokens"]["hi"] + 1 + 16
    assert worst <= engine["max_model_len"] == 4096 and engine["block_size"] == 32
    # the widest wave holds two of the longest prompts; a 2,048-row wave's temporaries
    # (1.07 GB by the compiler's count) are what would put the peak over 90%
    assert engine["prefill_buckets"][-1] == 1024 >= 2 * (traffic["prompt_tokens"]["hi"])
    # the mean context in flight fits the pool four standard deviations over: no preemption
    mean_blocks = 48 * (320 + 16 + 768) / 32
    assert mean_blocks + 3 * 100 < engine["num_kv_blocks"]
    # weights, slab and pages: 80-90% of the chip with a wave's temporaries (~1.1 GB) beside them
    mf = model_fields(load_config(NAME))
    held = (8_201_577_888 + 49 * olmo_hybrid.state_bytes_per_sequence(mf)
            + (engine["num_kv_blocks"] + 1) * 2 ** 21)
    assert 0.75 * 16.9e9 < held < 0.85 * 16.9e9
    # the same work for every seed: the lengths are fixed quantiles, permuted
    plans = [generators.generate(traffic, seed, 45) for seed in (3999999979, 17)]
    lengths = [[r.max_tokens for c in p.clients for r in c[1:]] for p in plans]
    assert all(n % 8 == 1 and 1025 <= n <= 2049 for ns in lengths for n in ns)
    # (all but each client's first, which is cut to stagger the clients: which 48 of the
    # 384 quantiles those are follows the seed)
    assert len(lengths[0]) == 48 * 7 and abs(sum(lengths[0]) - sum(lengths[1])) < 0.02 * sum(
        lengths[0])
    assert sorted(r.max_tokens for c in plans[0].clients for r in c[1:]) != [] and sorted(
        len(r.prompt) for c in plans[0].clients for r in c) == sorted(
        len(r.prompt) for c in plans[1].clients for r in c)
    prompts = [sorted(len(r.prompt) for c in p.clients for r in c) for p in plans]
    assert prompts[0] == prompts[1] and 128 <= prompts[0][0] and prompts[0][-1] <= 512


@pytest.fixture(scope="module")
def tiny():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-olmo-hybrid-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=5)
    body = {"prompt_ids": [int(t) for t in np.random.RandomState(0).randint(1, 380, size=70)],
            "max_tokens": 17, "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_reference_on_the_engines_tree_agrees_through_slab_and_pages(tiny):
    cfg, core, _, got = tiny
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    assert set(core.params) >= {"linear", "attn", "layers", "final_norm", "lm_head"}
    assert set(core.params["linear"]) == {"w_qkv", "w_z", "w_ba", "conv_w", "A_log", "dt_bias",
                                          "o_norm", "w_out"}
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0    # no block holds the state


@pytest.mark.parametrize("fault,least", [("fp8", 0.15), ("decay", 0.15), ("neg_eigval", 0.15),
                                         ("state_bf16", 1e-2)])
def test_each_fault_the_controls_name_is_caught_at_the_tiny_size(tiny, fault, least):
    """``control.py`` drives ``fp8`` through ``lowered`` and the others through
    ``score_probe(faults=...)``: here both ways at the tiny size, in float32.
    Three read far over the benchmark's tolerance; ``state_bf16`` (the state
    one precision below what the configuration states for it) reads ~0.08,
    under it, and is held at 1e-4 (PERF.md section 7)."""
    from chipbench.reference import control

    cfg, core, body, got = tiny
    assert fault in __import__("chipbench.reference.olmo_hybrid", fromlist=["FAULTS"]).FAULTS
    answered = control.answer(core, cfg, body, fault, got)
    verdict = check.compare(answered["served"], answered["scored"], atol=1e-4)
    assert not verdict["ok"] and verdict["max_abs_diff"] > least, verdict
    with pytest.raises(ValueError, match="unknown faults"):
        check.score_probe(cfg, core.params, body["prompt_ids"], got["served"][0],
                          faults=("window",))


def test_the_reference_needs_every_piece_it_is_given(tiny):
    cfg, core, body, _ = tiny
    mf = model_fields(cfg)
    ids, rows = body["prompt_ids"], [10, 69]
    mine = np.asarray(olmo_hybrid.reference_logits(core.params, mf, ids, rows, vocab_chunks=3))
    same = np.asarray(olmo_hybrid.reference_logits(core.params, mf, ids, rows, vocab_chunks=5))
    np.testing.assert_allclose(mine, same, atol=1e-5)
    for group, leaf in (("linear", "conv_w"), ("linear", "A_log"), ("linear", "o_norm"),
                        ("attn", "k_layernorm"), ("layers", "mlp_norm")):
        changed = {**core.params, group: {**core.params[group],
                                          leaf: core.params[group][leaf] * 0 + 1.0}}
        other = np.asarray(olmo_hybrid.reference_logits(changed, mf, ids, rows, vocab_chunks=3))
        assert float(np.abs(mine - other).max()) > 1e-3, (group, leaf)


def _trace(ops):
    """``phases.load``'s shape: ops [name, start, dur, module, tf_op]."""
    return {"ops": ops, "modules": [["jit__megastep_body(1)", 0.0, 1000.0, "7"],
                                    ["jit__prefill_and_sample(2)", 2000.0, 500.0, "8"]]}


def test_state_roofline_reads_the_scope_whatever_implements_it():
    step = "jit(_megastep_body)/while/body/attn/linear/state_step/"
    ops = [
        ["%while.1", 0.0, 1000.0, "", "jit(_megastep_body)/while/body"],
        ["%gdn_step_kernel.1", 100.0, 300.0, "", step + "pallas_call"],         # the kernel
        ["%fusion.2", 400.0, 100.0, "", step + "transpose"],                     # XLA around it
        ["%fusion.3", 500.0, 300.0, "", "jit(_megastep_body)/while/body/attn/linear/gate_norm/mul"],
        ["%fusion.4", 2000.0, 500.0, "", "jit(_prefill_and_sample)/attn/linear/state_scan/dot"],
    ]
    seconds = scope_roofline.scope_seconds(_trace(ops), "state_step", "_megastep_body")
    assert seconds == pytest.approx(400e-9)       # the kernel AND what stands around it; no scan
    mf = model_fields(load_config(NAME))
    need = olmo_hybrid.state_step_bytes_per_layer(48, mf)
    assert need == 212_336_640
    # 12 calls an iteration x 8 iterations x 1 execution = 96 calls in `seconds`
    assert state_roofline.share(need, 96 * 0.4e-3, 96, 819e9) == pytest.approx(
        100 * (need / 819e9) / 0.4e-3)
    assert 60 < state_roofline.share(need, 96 * 0.4e-3, 96, 819e9) < 70

    class Ctx:
        trace = None
        cell = {"name": "no-such-cell"}
        config = load_config(NAME)
        records: list = []

    args = {"scope": "state_step", "module": "_megastep_body"}
    assert state_roofline.read(Ctx(), **args) is None                  # an untraced run
    Ctx.trace = {"devices": 1, "modules": {}}
    assert state_roofline.read(Ctx(), **args) is None                  # no such program
    Ctx.trace = {"devices": 1, "modules": {"_megastep_body": {"count": 3, "seconds": 1.0}}}
    assert state_roofline.read(Ctx(), **args) is None                  # no trace file to read
    Ctx.config = load_config("lfm2-24b-a2b-10l-bf16")                  # counts no such bytes
    assert state_roofline.read(Ctx(), **args) is None


def test_whole_command_on_the_cpu_on_the_linear_attention_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-olmo-hybrid-closed-1",
         "--seed", "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY,
         "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "state_replayed_tokens_per_ktok"} <= set(
        result["metrics"]), result["metrics"]
    assert result["metrics"]["state_replayed_tokens_per_ktok"]["value"] == 0.0
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    run = ROOT / "chipbench_out" / "tiny-olmo-hybrid-closed-1" / "run.json"
    record = json.loads(run.read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    assert record["reference"]["second_send_cached_tokens"] == 0
    startup = record["startup"][0]
    assert startup["cache_layers"] == {"attention": 1, "conv": 0, "linear": 4}
    assert startup["state_slots"] == 8 and startup["prefix_caching"] is False
    assert startup["state_bytes_per_sequence"] == 4 * (2 * 32 * 64 * 4 + 3 * 256 * 4)
