"""The seventh configuration, ``sdar-30b-a3b-6l-bf16``, and its cell: the file
against the published sizes, the counts by hand and against what a pass of
the program reads, the cell as the issue sizes it, and the yardstick's own
rule that a module's ``score_probe`` is what ``check`` scores with."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import sdar_moe
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from chipbench.run import probe_of
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
NAME, CELL = "sdar-30b-a3b-6l-bf16", "sdar30b-block-decode"
TINY = "tests/chipbench/data/tiny_manifest_sdar.json"
# the catalog's copy of config.json (model-configs guide, SDAR-30B-A3B-Chat)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_file_is_the_published_configuration_cut_in_depth_alone():
    cfg = load_config(NAME)
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6 and cfg["published"] == {"num_hidden_layers": 48}
    assert "stage 0" in cfg["deployment"] and "8 stages" in cfg["deployment"]
    # every size the catalog does not give is listed, and lives in ONE field of the program
    for key in ("block_length", "denoising_steps", "remasking", "mask_token_id", "clean_pass",
                "own_row", "prompt_tail", "qk_norm", "rope_pairing", "router", "weights", "serve"):
        assert cfg["assumed"][key], key
    mf = model_fields(cfg)
    assert (mf["block_length"], mf["denoising_steps"], mf["confidence_threshold"],
            mf["mask_token_id"]) == (4, 2, 0.9, 151669)
    assert mf["router_scoring"] == "softmax" and mf["qk_norm"] and not mf["tie_embeddings"]
    # ONE knob: what a block is served with is the model's, and the deployment states no other
    assert "denoising_steps" not in cfg["serve"]["engine"] and "4" in cfg["assumed"]["denoising_steps"]
    assert probe_of(cfg) == {"prompt_tokens": 98, "max_tokens": 17, "top": 5}
    with pytest.raises(ValueError, match="mlp_only_layers"):
        sdar_moe.derived(dict(cfg, mlp_only_layers=[0]))


def test_the_engine_builds_the_preset_from_the_file():
    from dynamo_tpu.engine import EngineConfig, ModelConfig
    from dynamo_tpu.engine.config import sdar_30b_a3b_6l
    from dynamo_tpu.engine.core import _resolve_block_megastep

    cfg = load_config(NAME)
    model = ModelConfig(**model_fields(cfg))
    assert model == ModelConfig(**dict(vars(sdar_30b_a3b_6l()), name=NAME))
    engine = _resolve_block_megastep(model, EngineConfig(**engine_overrides(cfg)))
    # two blocks of 2 denoising passes and a clean one a dispatch; eight blocks a page
    assert (model.denoising_steps, engine.megastep, engine.block_size) == (2, 6, 32)
    worst = 768 + 2049 + 32
    assert -(-worst // 32) == 90 and 128 * 90 == 11520 <= engine.num_kv_blocks
    assert worst <= engine.max_model_len
    assert sdar_moe.kv_bytes_per_token(model_fields(cfg)) * 32 == 393216      # 384 KB a page


def test_counts_by_hand_and_against_what_a_pass_reads():
    import jax

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine import model as model_mod

    mf = model_fields(load_config(NAME))
    assert sdar_moe.attention_params(mf) == 2 * 2048 * 4096 + 2 * 2048 * 512 + 256 == 18_874_624
    assert sdar_moe.expert_params(mf) == 3 * 2048 * 768 == 4_718_592
    layer = 18_874_624 + 2 * 2048 + 2048 * 128 + 128 * 4_718_592
    want = 2 * (6 * layer + 2048 + 2048 * 151936)
    assert sdar_moe.decode_weight_bytes(mf, None) == want == 8_099_781_632
    # what a pass reads: every leaf but the layout marker and the embedding table,
    # of which it looks up a row a place
    params = jax.eval_shape(lambda: model_mod.init_params(
        jax.random.PRNGKey(0), ModelConfig(**mf)))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert want == leaves - 4 - 2 * 2048 * 151936
    assert sdar_moe.decode_weight_bytes(
        mf, None, architectures.Observed(decode_lanes_mean=3.0)) == want
    # from shapes alone: the program's own counters do not move a roofline's bytes
    seen = architectures.Observed(counter=lambda name, labels=None: {
        "dynamo_engine_experts_touched_total": 3900.0,
        "dynamo_engine_expert_steps_total": 100.0}[name])
    assert sdar_moe.decode_weight_bytes(mf, None, seen) == want
    with pytest.raises(ValueError, match="unquantised"):
        sdar_moe.decode_weight_bytes(mf, "int8")
    assert sdar_moe.kv_bytes_per_token(mf) == 6 * 2 * 4 * 128 * 2 == 12288
    # ONE layer's call: 128 lanes at ~1300 tokens, 41 pages each of 32 x 2048 B, read once
    assert sdar_moe.attn_decode_bytes_per_layer([1300] * 128, mf, 32) == 128 * 41 * 32 * 2048
    # one LANE's pass: 4 rows through attention, router, 8 experts and the head
    assert sdar_moe.forward_flops_per_token(mf, 1000) == 4 * (
        2 * (6 * (18_874_624 + 2048 * 128 + 8 * 4_718_592) + 2048 * 151936)
        + 6 * 4 * 32 * 128 * 1000)
    # 512 rows of a pass lie past the line between the step's kernel and the wave's product
    assert model_mod.expert_call_shape(128 * 4) == "wave"


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == []
    assert len(man["configs"]) >= 7 and len(man["workloads"]) >= 7
    assert all(w["chips"] == 1 for w in man["workloads"])
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "block-decode", 1)
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == load_config(NAME)["source"]
    traffic = generators.load_traffic("block-decode")
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 128
    assert traffic["pool_per_client"] == 8 and traffic["ramp_seconds"] == 12
    assert (traffic["prompt_tokens"], traffic["output_tokens"]) == (
        {"dist": "uniform", "lo": 256, "hi": 768}, {"dist": "uniform", "lo": 1024, "hi": 2048})
    plan = generators.generate(traffic, 3000000019, 45)
    assert all(r.max_tokens % 8 == 1 and "temperature" not in traffic for r in plan.all_requests())
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    mine = {manifest.metric_file("per_layer", m["name"]).stem
            for m in manifest.metrics_of(man, "per_layer", CELL)}
    new = {"denoise_forwards_per_token", "block_attn_time_share", "unmask_time_share"}
    joined = {"decode_step_device_ms", "decode_weight_floor_share", "decode_step_mfu",
              "attn_kernel_time_share", "attn_decode_roofline", "router_time_share",
              "experts_time_share", "lm_head_time_share", "unscoped_time_share",
              "experts_touched_per_step", "prefill_device_ms_per_ktok", "prefill_wave_fill",
              "tokens_per_dispatch", "host_ms_per_dispatch", "decode_lane_occupancy",
              "preemptions_per_kdispatch", "device_idle_share", "hbm_peak_share",
              "closed_loop_ttft_ms_p50", "decode_ms_per_token", "prefill_stall_ms_per_token",
              "host_stall_ms_per_token", "device_starved_share", "device_account_error"}
    assert new | joined <= mine
    for name in new:
        m = layer_entry(man, name, CELL)
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p50" and m["name"] == name
    assert len(man["per_layer"]) <= 128 and len(json.dumps(man)) < 64 * 1024


def test_check_scores_this_architecture_with_its_own_score_probe(monkeypatch):
    """What the yardstick promises a module that brings one (``reference/check.py``):
    its ``score_probe`` in the default's place, and ``extra`` handed through."""
    from types import SimpleNamespace

    cfg = load_config("tiny-sdar-rehearsal")
    assert architectures.of(cfg) is sdar_moe and "score_probe" in architectures.OPTIONAL
    probe = {"tokens": [1], "top_ids": [[1]], "top_lps": [[-0.5]], "cached_tokens": 0,
             "extra": [{"block": 0, "step": 0, "place": 1}]}
    asked = []
    monkeypatch.setattr(check, "run_probe", lambda *a, extra: asked.append(extra) or dict(probe))
    monkeypatch.setattr(sdar_moe, "score_probe",
                        lambda cfg, params, prompt, p, **kw: {"mine": p["extra"]})
    core = SimpleNamespace(params=None, engine=SimpleNamespace(megastep=6))
    got = check.score_request(core, cfg, {"prompt_ids": [1], "max_tokens": 1, "top": 1})
    assert asked == [True, True] and got["megastep_k"] == 6
    assert got["scored"]["sequences"] == [{"mine": probe["extra"]}] * 2


@pytest.mark.slow
def test_whole_command_on_the_cpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-sdar-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"denoise_forwards_per_token", "tokens_per_dispatch",
            "experts_touched_per_step"} <= set(result["metrics"])
    assert 0.75 <= result["metrics"]["denoise_forwards_per_token"]["value"] < 1.0


def test_the_tiny_manifest_is_sound():
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
