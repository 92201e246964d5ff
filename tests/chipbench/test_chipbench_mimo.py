"""The eighth architecture, added as files only: ``model_type`` "mimo_v2"
(keys 192 wide beside values 128 wide in the pages, 4 and 8 KV heads by layer
kind, a sink logit on the window-128 layers, a chip's share of 256
bias-chosen sigmoid experts and no shared one). Its key map pinned for the
cell's configuration, the published keys unchanged but the cuts, its counts
by hand and against what the program reads, its reference on the engine's own
tree at the tiny size, the cell as ISSUE 46 sizes it, and the whole command
on its rehearsal configuration."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import mimo_v2
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_mimo.json"
NAME, CELL = "mimo-v2.5-ep16-7l-bf16", "mimo-v25-ep16-longctx"
PATTERN = [0] + 7 * [1, 1, 1, 1, 0, 1] + [1, 1, 1, 1, 0]

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128, "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv", "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192, "swa_v_head_dim": 128, "head_dim": 192,
    "hidden_act": "silu", "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": PATTERN, "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "model_type": "mimo_v2", "moe_intermediate_size": 2048,
    "moe_layer_freq": [0] + 47 * [1], "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": None, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334, "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152576}
CUT = {"num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
       "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 16, "vocab_size": 19072}
F, W = "full_attention", "sliding_attention"
ROPE = {F: {"rope_type": "default", "partial_rotary_factor": 0.334, "rope_theta": 10000000},
        W: {"rope_type": "default", "partial_rotary_factor": 0.334, "rope_theta": 10000}}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "mixtral", "ouro", "axk1", "lfm2_moe", "laguna", "sdar_moe",
            "mimo_v2"} <= set(architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is mimo_v2
    assert all(hasattr(mimo_v2, name) for name in architectures.SURFACE)
    assert not any(hasattr(mimo_v2, name) for name in architectures.OPTIONAL)
    mf = model_fields(cfg)
    assert mf == dict(
        vocab_size=19072, hidden_size=4096, intermediate_size=16384, num_layers=7, num_heads=64,
        num_kv_heads=4, window_kv_heads=8, head_dim=192, v_head_dim=128,
        attn_value_scale=0.707, rms_norm_eps=1e-05, tie_embeddings=False, attn_qkv_bias=False,
        dtype="bfloat16", sliding_window=128, moe_intermediate_size=2048, num_experts_per_tok=8,
        norm_topk_prob=True, n_group=1, topk_group=1,
        layer_types=(F, W, W, W, W, F, W), rope_by_kind=ROPE, attn_sinks=(W,),
        router_scoring="sigmoid", router_bias=True, first_dense_layers=1, num_experts=256,
        experts_held=(0, 16), name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import mimo_v25_ep16_7l

    model = ModelConfig(**mf)
    assert model == dataclasses.replace(mimo_v25_ep16_7l(), name=NAME)
    assert model.num_experts_held == 16 and model.experts_held_range == (0, 16)
    assert model.layers_of("attention") == (0, 5) and model.layers_of("window") == (1, 2, 3, 4, 6)
    assert model.wide_key and model.num_shared_experts == 0
    # a value the equations do not cover is refused, not ignored
    for change in ({"swa_head_dim": 128}, {"swa_v_head_dim": 192}, {"swa_num_attention_heads": 32},
                   {"hidden_act": "gelu"}, {"scoring_func": "softmax"}, {"topk_method": "greedy"},
                   {"n_shared_experts": 1}, {"routed_scaling_factor": 2.5},
                   {"sliding_window_size": 256}, {"hybrid_block_size": 4},
                   {"rope_scaling": {"rope_type": "yarn"}}, {"num_hidden_layers": 8},
                   {"n_routed_experts": 64}, {"moe_layer_freq": [0, 1, 0, 1, 1, 1, 1]}):
        with pytest.raises(ValueError, match="mimo_v2"):
            model_fields({**cfg, **change})


def test_the_file_holds_the_published_keys_unchanged_but_the_cuts():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    # ... which is the catalog's row, key for key
    rows = [json.loads(line) for line in Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").read_text().splitlines()
        if '"name": "MiMo-V2.5"' in line] if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else []
    for row in rows:
        assert row["config"] == PUBLISHED and row["source_url"] == cfg["source"]
    assert cfg["reduced"] == list(CUT)
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    assert cfg["experts_held"] == {"rank": 0, "of": 16, "published": 256}
    # the cut keeps the leading dense layer and one whole period, in the published order
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert cfg[key] == PUBLISHED[key][:7]
    assert PATTERN.count(0) == 9 and PATTERN.count(1) == 39 and len(PATTERN) == 48
    assert cfg["serve"]["quant"] is None and cfg["torch_dtype"] == "bfloat16"
    assert cfg["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    for words in ("7 pipeline stages x 16 chips", "rank 0 of 16", "16 experts",
                  "data-parallel attention", "nothing stands in for it",
                  "--tp and --pp are not engaged", "no vision or audio tower, no MTP layer"):
        assert words in cfg["deployment"], words
    assert {"value_scale", "sink", "sliding_window", "rope_pairing", "attention_chunk_size",
            "attention_projection_layout", "router", "qk_norm", "torch_dtype",
            "parameter_names", "towers_and_mtp", "weights"} <= set(cfg["assumed"])
    assert cfg["probe"] == {"prompt_tokens": 264, "max_tokens": 17}
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    # the guide's floors: a whole period (six layers) after the dense one, 8 experts,
    # an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - 1 >= 6 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # no width in reduced: every width, the router's 256 and its 8 a token as published
    assert not any(k.endswith(("_dim", "_rank", "_size")) or k == "num_experts_per_tok"
                   for k in cfg["reduced"] if k != "vocab_size")


def test_counts_by_hand_and_against_what_the_program_reads():
    import jax

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine import model as model_mod

    mf = model_fields(load_config(NAME))
    full = 4096 * (64 * 192 + 4 * 320) + 64 * 128 * 4096
    window = 4096 * (64 * 192 + 8 * 320) + 64 * 128 * 4096
    assert mimo_v2.attention_params(mf, 0) == mimo_v2.attention_params(mf, 5) == full == 89_128_960
    assert mimo_v2.attention_params(mf, 1) == window == 94_371_840
    assert mimo_v2.expert_params(mf) == 3 * 4096 * 2048 == 25_165_824
    assert mimo_v2.experts_read_per_step(mf) == 16
    sparse = 4096 * 256 + 16 * 25_165_824
    want = 2 * (2 * full + 5 * window + 7 * 2 * 4096 + 3 * 4096 * 16384 + 6 * sparse
                + 4096 + 4096 * 19072) + 4 * (5 * 64 + 6 * 256)
    assert mimo_v2.decode_weight_bytes(mf, None) == want == 6_703_676_672
    # what a decode step of the program reads: EVERY leaf but the layout marker and the
    # embedding table (a row a lane); all 16 held experts, on every row
    params = jax.eval_shape(lambda: model_mod.init_params(
        jax.random.PRNGKey(0), ModelConfig(**mf)))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert want == leaves - 4 - 2 * 19072 * 4096
    # (param_bytes counts the float32 sinks and choice biases at the model's two bytes)
    assert leaves - 4 - 2 * (5 * 64 + 6 * 256) == ModelConfig(**mf).param_bytes() == 6_859_910_784
    assert load_config(NAME)["serve"]["engine"]["max_num_seqs"] <= model_mod._EXPERTS_ALL_ROWS_MAX
    seen = architectures.Observed(decode_lanes_mean=3.0)        # the traffic has no say
    assert mimo_v2.decode_weight_bytes(mf, None, seen) == want
    with pytest.raises(ValueError, match="unquantised"):
        mimo_v2.decode_weight_bytes(mf, "int8")
    # 5,120 B a token: two full layers of 4 x (192 + 128) values; a window layer holds 5
    # blocks a sequence whatever the context
    assert mimo_v2.kv_values(mf, F) * 2 == 2560 and mimo_v2.kv_values(mf, W) * 2 == 5120
    assert mimo_v2.kv_bytes_per_token(mf) == 2 * 2560 == 5120
    assert mimo_v2.window_bytes_per_sequence(mf, 32) == 5 * 5 * 32 * 5120 == 4_096_000
    # the MEAN of a step's seven calls, and the LEAST each reads: every cached token of a
    # full layer, the newest 128 of a window layer; block edges NOT counted
    assert mimo_v2.attn_decode_bytes_per_layer([10000] * 32, mf, 32) == (
        (2 * 32 * 10000 * 2560 + 5 * 32 * 128 * 5120) // 7)
    assert mimo_v2.attn_decode_bytes_per_layer([10001], mf, 32) == (
        mimo_v2.attn_decode_bytes_per_layer([10001], mf, 4)) == (
        (2 * 10001 * 2560 + 5 * 128 * 5120) // 7)
    assert mimo_v2.attn_decode_bytes_per_layer([100], mf, 32) == (
        (2 * 100 * 2560 + 5 * 100 * 5120) // 7)                    # inside the window
    routed = 8 * 16 / 256
    assert mimo_v2.forward_flops_per_token(mf, 9000) == int(
        2 * (2 * full + 5 * window + 3 * 4096 * 16384
             + 6 * (4096 * 256 + routed * 25_165_824) + 4096 * 19072)
        + 2 * 2 * 64 * 320 * 9000 + 5 * 2 * 64 * 320 * 128)


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == [] and len(man["workloads"]) >= 8
    assert not any(w["chips"] == 4 for w in man["workloads"])
    assert [w["name"] for w in man["workloads"]].count(CELL) == 1
    assert [c["name"] for c in man["configs"]].count(NAME) == 1
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "longctx-agents-12k", 1)
    assert manifest.topology_of(cell) == "one-worker"
    assert "32 clients, prompts 6144-12288, outputs 1536-2560 (8m+1)" in cell["why"]
    assert "attention 16x its share" in cell["why"]
    for e in man["configs"] + man["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(e.get(key, "x")) <= 200 and e.get(key, "x").isprintable()
    assert len(json.dumps(man)) < 64 * 1024 and len(man["per_layer"]) <= 128
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    mine = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    # every entry Laguna's cell lists but the library kernel's two, the gate's and the
    # shared expert's, and the two of the wide-key kernel beside them
    theirs = {m["name"] for m in manifest.metrics_of(man, "per_layer", "laguna-s21-longctx-agents")}
    assert theirs - mine == {"attn_decode_roofline", "attn_kernel_time_share",
                             "attn_gate_time_share", "shared_expert_time_share"}
    assert mine - theirs == {"attn_decode_roofline.mimo", "attn_kernel_time_share.mimo"}
    assert {"window_attn_time_share.laguna", "window_blocks_released_per_ktok",
            "decode_step_mfu", "hbm_peak_share", "experts_time_share"} <= mine
    for reader, module in (("attn_decode_roofline.mimo", "scope_roofline"),
                           ("attn_kernel_time_share.mimo", "scope_share")):
        entry = layer_entry(man, reader, CELL)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_ms_p50"
        spec = json.loads(manifest.metric_file("per_layer", entry["name"]).read_text())
        assert spec["reader"] == module
        assert spec["args"] == {"scope": "gqa_paged_attention", "module": "_megastep_body"}
    # the traffic, letter for letter
    traffic = generators.load_traffic(cell["traffic"])
    assert {k: traffic[k] for k in ("kind", "clients", "pool_per_client", "prompt_tokens",
                                    "output_tokens", "output_quantum", "ramp_seconds",
                                    "temperature")} == {
        "kind": "closed_loop", "clients": 32, "pool_per_client": 8,
        "prompt_tokens": {"dist": "uniform", "lo": 6144, "hi": 12288},
        "output_tokens": {"dist": "uniform", "lo": 1536, "hi": 2560},
        "output_quantum": 8, "ramp_seconds": 30, "temperature": 0.7}
    assert "think" not in " ".join(traffic)
    # every stream at its longest fits the full pool with room: no preemption
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["clients"] == engine["max_num_seqs"] == engine["decode_buckets"][-1] == 32
    worst = traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] + 1 + 32
    blocks = -(-worst // engine["block_size"])
    assert worst == 14881 and blocks == 466 and 32 * blocks == 14912 <= engine["num_kv_blocks"]
    assert worst <= engine["max_model_len"] == 466 * 32 and engine["prefill_buckets"][-1] == 2048
    from dynamo_tpu.engine import EngineConfig

    eng = EngineConfig(**engine_overrides(load_config(NAME)))
    assert eng.num_window_blocks == 0 and eng.window_blocks_auto(128) == 272
    assert eng.window_table_blocks(128) == 70 and eng.megastep == 8
    # a block is 160 KB in the full pool and 800 KB in the window pool
    mf = model_fields(load_config(NAME))
    assert 32 * mimo_v2.kv_bytes_per_token(mf) == 160 * 1024
    cache = (engine["num_kv_blocks"] + 1) * 160 * 1024 + (272 + 1) * 800 * 1024
    assert 2.73e9 < cache < 2.75e9 and 0.55 * 16.9e9 < cache + 6_859_910_784 < 0.6 * 16.9e9
    # the same work for every seed: the lengths are fixed quantiles, permuted
    plans = [generators.generate(traffic, seed, 45) for seed in (3999999979, 17)]
    lengths = [[r.max_tokens for c in p.clients for r in c[1:]] for p in plans]
    assert all(n % 8 == 1 and 1537 <= n <= 2561 for ns in lengths for n in ns)
    prompts = [sorted(len(r.prompt) for c in p.clients for r in c) for p in plans]
    assert prompts[0] == prompts[1] and 6144 <= prompts[0][0] and prompts[0][-1] <= 12288
    assert plans[0].temperature == 0.7


@pytest.fixture(scope="module")
def tiny_mimo():
    """One engine core of the rehearsal configuration, its probe sent twice."""
    import random

    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-mimo-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=7)
    rng = random.Random(7)
    body = {"prompt_ids": [rng.randrange(1, 384) for _ in range(40)], "max_tokens": 17,
            "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_reference_on_the_engines_tree_agrees_through_both_pools(tiny_mimo):
    cfg, core, _, got = tiny_mimo
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    assert set(core.params) >= {"moe", "dense_mlp", "attn", "attn_window", "final_norm",
                                "lm_head"}
    assert set(core.params["attn"]) == {"wqkv", "wo"}
    assert set(core.params["attn_window"]) == {"wqkv", "wo", "sink"}
    assert set(core.params["moe"]) == {"w_router", "expert_bias", "w_gu", "w_down"}
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0
    assert core.scheduler_stats()["window_blocks_released"] >= 2 * ((40 + 16 - 8) // 4 - 2)


def test_the_reference_needs_every_piece_it_is_given(tiny_mimo):
    cfg, core, body, _ = tiny_mimo
    mf = model_fields(cfg)
    ids, rows = body["prompt_ids"], [10, 39]
    mine = np.asarray(mimo_v2.reference_logits(core.params, mf, ids, rows, vocab_chunks=3))
    same = np.asarray(mimo_v2.reference_logits(core.params, mf, ids, rows, vocab_chunks=5))
    np.testing.assert_allclose(mine, same, atol=1e-5)
    for group, leaf in (("attn", "wo"), ("attn_window", "wo"), ("attn_window", "sink"),
                        ("moe", "expert_bias"), ("dense_mlp", "w_down")):
        changed = {**core.params, group: {**core.params[group],
                                          leaf: core.params[group][leaf] * 0 + 0.01}}
        other = np.asarray(mimo_v2.reference_logits(changed, mf, ids, rows, vocab_chunks=3))
        assert float(np.abs(mine - other).max()) > 1e-3, (group, leaf)
    for fault in ("sink", "v_scale", "window", "wide_key", "fp8"):
        other = np.asarray(mimo_v2.reference_logits(core.params, mf, ids, rows, faults=(fault,)))
        assert float(np.abs(mine - other).max()) > 1e-2, fault


def test_whole_command_on_the_cpu_on_the_wide_key_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-mimo-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "window_blocks_released_per_ktok"} <= set(
        result["metrics"]), result["metrics"]
    assert result["metrics"]["window_blocks_released_per_ktok"]["value"] > 0
    # no device ops under the kernel's scope on the CPU: its two metrics find nothing to
    # read, raise nothing, and the line leaves them out (as on a parent without the scope)
    assert not {"attn_decode_roofline.mimo", "attn_kernel_time_share.mimo"} & set(
        result["metrics"])
    record = json.loads((ROOT / "chipbench_out" / "tiny-mimo-closed-1" / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    assert record["reference"]["second_send_cached_tokens"] == 0
    # /health gives the page, the bytes a block and the cache layers BY KIND
    startup = record["startup"][0]
    assert startup["prefix_caching"] is False and startup["window_blocks"] == 96
    assert startup["cache_layers"] == {"attention": 2, "conv": 0, "window": 3}
    assert startup["cache_page_shape"] == {"attention": [20, 16], "window": [40, 16]}
    assert startup["cache_bytes_per_block"] == {"attention": 2560, "window": 7680}
    assert startup["kv_bytes_per_token"] == 640
