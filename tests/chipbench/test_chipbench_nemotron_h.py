"""The tenth architecture, added as files only: ``model_type`` "nemotron_h"
(Mamba-2 mixers whose float32 state lives in a slab a lane, blocks of ONE
sub-layer, un-gated ``relu^2`` experts of which this chip holds half). Its key
map pinned for the cell's configuration, the published keys unchanged but the
three cuts, its counts by hand and against the program's leaves and slab, the
cell as ISSUE 54 sizes it, the new reader on a canned trace, and the whole
command on its rehearsal configuration."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import nemotron_h
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.readers import scope_roofline, slab_state_roofline, state_roofline
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_nemotron_h.json"
WAITING = "tests/chipbench/data/waiting_entries_nemotron_h.json"
NAME, CELL = "nemotron-3-nano-30b-a3b-ep2-14l-bf16", "nemotron3-nano-ep2-decode"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 2688, "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_hidden_layers": 14, "hybrid_override_pattern": PATTERN[:14], "n_routed_experts": 64}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "olmo_hybrid", "nemotron_h"} <= set(architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is nemotron_h
    assert all(hasattr(nemotron_h, name) for name in architectures.SURFACE)
    assert hasattr(nemotron_h, "state_step_bytes_per_layer")
    mf = model_fields(cfg)
    period = ("mamba", "moe", "mamba", "moe", "mamba", "full_attention", "moe")
    assert mf == dict(
        vocab_size=131072, hidden_size=2688, intermediate_size=1856, num_layers=14, num_heads=32,
        num_kv_heads=2, head_dim=128, rope_theta=None, rms_norm_eps=1e-05, tie_embeddings=False,
        dtype="bfloat16", layer_types=2 * period, ssm_num_heads=64,
        ssm_head_dim=64, ssm_state_size=128, ssm_n_groups=8, ssm_conv_kernel=4,
        ssm_chunk_size=128, ssm_conv_bias=True, num_experts=128, num_experts_per_tok=6,
        moe_intermediate_size=1856, shared_expert_intermediate_size=3712, num_shared_experts=1,
        router_scoring="sigmoid", n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, experts_held=(0, 2), router_bias=True,
        mlp_activation="relu2", name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import nemotron3_nano_ep2_14l

    model = ModelConfig(**mf)
    assert model == dataclasses.replace(nemotron3_nano_ep2_14l(), name=NAME)
    assert model.param_bytes() == 9_874_450_944 and model.ssm and not model.linear
    # a value the equations do not cover is refused, not ignored
    for change in ({"mlp_hidden_act": "silu"}, {"attention_bias": True}, {"num_hidden_layers": 13},
                   {"hybrid_override_pattern": "ME-EM*EMEMEM*E"}, {"n_routed_experts": 32},
                   {"sliding_window": 4096}):
        with pytest.raises(ValueError, match="nemotron_h"):
            model_fields({**cfg, **change})


def test_the_file_holds_the_published_keys_unchanged_but_the_three_cuts():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    assert cfg["experts_held"] == {"rank": 0, "of": 2, "published": 128}
    # two whole periods MEMEM*E in the published order: 3 : 3 : 1
    assert cfg["hybrid_override_pattern"] == "MEMEM*E" * 2 == PATTERN[:14]
    assert cfg["serve"]["quant"] is None and cfg["torch_dtype"] == "bfloat16"
    assert cfg["source"] == ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
                             "blob/main/config.json")
    for words in ("v5e-8", "TWO chips share each layer's routed experts", "FOUR pipeline stages",
                  "expert rank 0 of stage 0", "final norm and the head", "No exchange", "3.7 x"):
        assert words in cfg["deployment"], words
    assert {"state_float32", "nope", "d_inner", "dt_clamp", "gate_before_norm", "router",
            "expert_layout", "parameter_names", "weights", "serve", "probe"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    # the guide's floors: a whole period + 4 layers, >= 8 experts, the whole vocabulary
    assert cfg["num_hidden_layers"] >= 7 + 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] == PUBLISHED["vocab_size"]
    # no width in reduced
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cfg["reduced"])
    # a probe whose wave spans more than one scan chunk of 128 and crosses a block edge decoding
    assert cfg["probe"] == {"prompt_tokens": 320, "max_tokens": 33}


def test_counts_by_hand_and_against_the_programs_leaves_and_slab():
    import jax

    from dynamo_tpu.engine import EngineConfig, ModelConfig
    from dynamo_tpu.engine import model as model_mod

    cfg = load_config(NAME)
    mf = model_fields(cfg)
    h = 2688
    assert nemotron_h.ssm_channels(mf) == 4096 + 2 * 8 * 128 == 6144
    assert nemotron_h.ssm_matrix_params(mf) == h * 10304 + 4096 * h
    assert nemotron_h.attention_params(mf) == h * 4096 + 2 * h * 256 + 4096 * h == 23_396_352
    assert nemotron_h.expert_params(mf) == 2 * h * 1856 == 9_977_856
    # what a decode step reads, at the PUBLISHED widths: every leaf but the embedding table;
    # the program's own leaves hold 64 zero columns and rows more an expert (1,920 stored)
    model = ModelConfig(**mf)
    params = jax.eval_shape(lambda: model_mod.init_params(jax.random.PRNGKey(0), model))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    pad = 6 * 64 * 2 * h * 64 * 2
    want = nemotron_h.decode_weight_bytes(mf, None)
    assert want == leaves - pad - 4 - 131072 * h * 2 == 9_169_811_712          # 9.17 GB a step
    assert want == model.param_bytes() - 131072 * h * 2 + 6 * 3 * 64 * 2 + 6 * 128 * 2
    assert nemotron_h.decode_weight_bytes(mf, None, architectures.Observed(3.0)) == want
    with pytest.raises(ValueError, match="unquantised"):
        nemotron_h.decode_weight_bytes(mf, "int8")
    # 2,048 B a token: two attention layers of 2 x 2 x 128 values; the other twelve hold none
    assert nemotron_h.kv_bytes_per_token(mf) == 2 * 2 * 2 * 128 * 2 == 2_048
    # the slab: the program's own arrays at 129 lane slots, and what a sequence holds of them
    engine = EngineConfig(**engine_overrides(cfg))
    cache = jax.eval_shape(lambda: model_mod.init_cache(model, engine))
    slab = [c for c in cache if isinstance(c, dict) and c]
    assert len(slab) == 6 and engine.state_slots == 129 and sum(c == {} for c in cache) == 6
    assert {k: (v.shape, str(v.dtype)) for k, v in slab[0].items()} == {
        "state": ((129, 64, 64, 128), "float32"), "conv": ((129, 3, 48, 128), "bfloat16")}
    per_slot = sum(v.size * v.dtype.itemsize for c in slab for v in c.values()) // 129
    assert per_slot == nemotron_h.state_bytes_per_sequence(mf) == 12_804_096
    assert per_slot == model.state_bytes_per_sequence()
    # ONE mamba layer's step: every live lane's float32 state read once and written once
    assert nemotron_h.state_step_bytes_per_layer(128, mf) == 2 * 64 * 64 * 128 * 4 * 128
    assert 6 * nemotron_h.state_step_bytes_per_layer(128, mf) == 3_221_225_472     # 3.2 GB a step
    assert nemotron_h.attn_decode_bytes_per_layer([1000] * 128, mf, 32) == 128 * 32 * 32 * 1024
    assert nemotron_h.forward_flops_per_token(mf, 1000) == int(
        2 * (6 * (nemotron_h.ssm_matrix_params(mf) + 4 * 6144) + 2 * 23_396_352
             + 6 * (h * 128 + 6 * 9_977_856 + 2 * h * 3712) + h * 131072)
        + 6 * 6 * 64 * 64 * 128 + 2 * 4 * 32 * 128 * 1000)


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == [] and len(man["workloads"]) >= 10
    assert [w["name"] for w in man["workloads"]].count(CELL) == 1
    assert [c["name"] for c in man["configs"]].count(NAME) == 1
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "ep-decode", 1)
    assert manifest.topology_of(cell) == "one-worker"
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
            "attn_kernel_time_share", "attn_decode_roofline", "router_time_share",
            "experts_time_share", "shared_expert_time_share", "experts_touched_per_step",
            "expert_pairs_held_share", "warmup_s", "compile_s",
            "trace_lower_s", "correct_check_s"):
        assert layer_entry(man, reader, CELL) is not None, reader
    # four entries WAIT for a `benchmark` PR (test_chipbench_startup.py, a file of the benchmark,
    # pins the start-up block as the LAST of per_layer; a new entry may only go to the END):
    # their files are here, data over a reader that was there and one new reader, and
    # BENCHMARK.json with them appended is sound
    waiting = json.loads((ROOT / WAITING).read_text())
    with_them = {**man, "per_layer": man["per_layer"] + waiting}
    assert manifest.problems(with_them) == [] and len(with_them["per_layer"]) <= 128
    for entry, (name, reader, args) in zip(waiting, (
            ("ssm_time_share", "scope_share", {"scope": "ssm", "module": "_megastep_body"}),
            ("ssm_state_time_share", "scope_share",
             {"scope": "ssd_step", "module": "_megastep_body"}),
            ("ssm_scan_time_share", "scope_share",
             {"scope": "ssd_scan", "module": "_prefill_and_sample"}),
            ("ssm_state_roofline", "slab_state_roofline",
             {"layer_kind": "mamba", "scope": "ssd_step", "module": "_megastep_body"})), strict=True):
        assert layer_entry(with_them, name, CELL) == entry
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_ms_p50"
        spec = json.loads(manifest.metric_file("per_layer", entry["name"]).read_text())
        assert spec["reader"] == reader and spec["args"] == args
    assert waiting[-1]["unit"] == "%"
    # (the replay guard's list is pinned to Olmo's cell by that cell's own test, a file of the
    # benchmark: this cell exports the counter and does not join the list)
    assert layer_entry(man, "state_replayed_tokens_per_ktok", CELL) is None
    assert len(man["per_layer"]) >= 96 and len(man["configs"]) == len(man["workloads"]) == 10
    # the traffic is A.X-K1's cell's, letter for letter: the file is shared
    assert manifest.cell(man, "axk1-ep16-decode")["traffic"] == "ep-decode"
    traffic = generators.load_traffic(cell["traffic"])
    assert {k: traffic[k] for k in ("kind", "clients", "pool_per_client", "prompt_tokens",
                                    "output_tokens", "output_quantum", "ramp_seconds")} == {
        "kind": "closed_loop", "clients": 128, "pool_per_client": 8,
        "prompt_tokens": {"dist": "uniform", "lo": 256, "hi": 768},
        "output_tokens": {"dist": "uniform", "lo": 768, "hi": 1280},
        "output_quantum": 8, "ramp_seconds": 12}
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["clients"] == engine["max_num_seqs"] == engine["decode_buckets"][-1] == 128
    worst = traffic["prompt_tokens"]["hi"] + 16 + traffic["output_tokens"]["hi"] + 1 + 16
    assert worst <= engine["max_model_len"] == 4096 and engine["block_size"] == 32
    # every lane at its longest stream fits the pool: no preemption whatever the seed
    assert 128 * -(-worst // 32) <= engine["num_kv_blocks"]
    # weights as stored (1,920-wide experts), slab and pages: 75-85% of the chip before a wave's
    # temporaries
    mf = model_fields(load_config(NAME))
    held = (9_874_450_944 + 6 * 64 * 2 * 2688 * 64 * 2
            + 129 * nemotron_h.state_bytes_per_sequence(mf)
            + (engine["num_kv_blocks"] + 1) * 32 * nemotron_h.kv_bytes_per_token(mf))
    assert 0.70 * 16.9e9 < held < 0.85 * 16.9e9


def _trace(ops):
    """``phases.load``'s shape: ops [name, start, dur, module, tf_op]."""
    return {"ops": ops, "modules": [["jit__megastep_body(1)", 0.0, 1000.0, "7"],
                                    ["jit__prefill_and_sample(2)", 2000.0, 500.0, "8"]]}


def test_the_slab_roofline_reads_the_scope_for_the_layer_kind_it_is_given():
    step = "jit(_megastep_body)/while/body/attn/ssm/ssd_step/"
    ops = [
        ["%while.1", 0.0, 1000.0, "", "jit(_megastep_body)/while/body"],
        ["%ssd_step_kernel.1", 100.0, 300.0, "", step + "pallas_call"],          # the kernel
        ["%fusion.2", 400.0, 100.0, "", step + "transpose"],                      # XLA around it
        ["%fusion.3", 500.0, 300.0, "", "jit(_megastep_body)/while/body/attn/ssm/gate_norm/mul"],
        ["%fusion.4", 2000.0, 500.0, "", "jit(_prefill_and_sample)/attn/ssm/ssd_scan/dot"],
    ]
    seconds = scope_roofline.scope_seconds(_trace(ops), "ssd_step", "_megastep_body")
    assert seconds == pytest.approx(400e-9)       # the kernel AND what stands around it; no scan
    mf = model_fields(load_config(NAME))
    need = nemotron_h.state_step_bytes_per_layer(128, mf)
    assert need == 536_870_912
    # 6 calls an iteration x 8 iterations x 1 execution = 48 calls in `seconds`
    assert slab_state_roofline.share is state_roofline.share
    assert 70 < slab_state_roofline.share(need, 48 * 0.9e-3, 48, 819e9) < 75

    class Ctx:
        trace = None
        cell = {"name": "no-such-cell"}
        config = load_config(NAME)
        records: list = []

    args = {"layer_kind": "mamba", "scope": "ssd_step", "module": "_megastep_body"}
    assert slab_state_roofline.read(Ctx(), **args) is None                  # an untraced run
    Ctx.trace = {"devices": 1, "modules": {}}
    assert slab_state_roofline.read(Ctx(), **args) is None                  # no such program
    Ctx.trace = {"devices": 1, "modules": {"_megastep_body": {"count": 3, "seconds": 1.0}}}
    assert slab_state_roofline.read(Ctx(), **args) is None                  # no trace file
    Ctx.config = load_config("lfm2-24b-a2b-10l-bf16")                       # counts no such bytes
    assert slab_state_roofline.read(Ctx(), **args) is None
    Ctx.config = load_config("olmo-hybrid-7b-pp2-16l-bf16")                 # no layer of the kind
    assert slab_state_roofline.read(Ctx(), **args) is None


@pytest.mark.slow
def test_whole_command_on_the_cpu_on_the_state_space_configuration():
    """(slow: 70 s here, most of it the worker's warm-up; the same command is
    what the builder ran before the chip)."""
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-nemotron-h-closed-1",
         "--seed", "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY,
         "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "state_replayed_tokens_per_ktok",
            "experts_touched_per_step", "expert_pairs_held_share"} <= set(
        result["metrics"]), result["metrics"]
    assert result["metrics"]["state_replayed_tokens_per_ktok"]["value"] == 0.0
    record = json.loads(
        (ROOT / "chipbench_out" / "tiny-nemotron-h-closed-1" / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["second_send_cached_tokens"] == 0
    startup = record["startup"][0]
    assert startup["cache_layers"] == {"attention": 1, "conv": 0, "ssm": 3, "none": 3}
    assert startup["state_slots"] == 8 and startup["prefix_caching"] is False
    assert startup["experts_held"] == [0, 4]
