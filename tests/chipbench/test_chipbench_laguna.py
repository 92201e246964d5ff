"""The sixth architecture, added as files only: ``model_type`` "laguna"
(window and full attention layers of different head counts behind a per-head
gate, a cache pool per layer kind, a chip's share of 256 top-10 experts
beside a shared one). Its key map pinned for the cell's configuration, the
published keys unchanged but the cuts, its counts by hand and against what
the program reads, its reference on the engine's own tree at the tiny size,
the cell as ISSUE 39 sizes it, and the whole command on its rehearsal
configuration."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import laguna
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_laguna.json"
NAME, CELL = "laguna-s-2.1-ep8-9l-bf16", "laguna-s21-longctx-agents"
PERIOD = ["full_attention"] + 3 * ["sliding_attention"]
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 1048576,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512, "rope_parameters": ROPE, "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + 47 * ["sparse"], "gating_types": 48 * ["per_head"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12, "moe_router_logit_softcapping": 0}
CUT = {"num_hidden_layers": 9, "num_experts": 32, "vocab_size": 12544,
       "layer_types": PERIOD * 2 + ["full_attention"],
       "mlp_layer_types": ["dense"] + 8 * ["sparse"], "gating_types": 9 * ["per_head"],
       "num_attention_heads_per_layer": [48, 72, 72, 72, 48, 72, 72, 72, 48]}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "mixtral", "ouro", "axk1", "lfm2_moe", "laguna"} <= set(
        architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is laguna
    assert all(hasattr(laguna, name) for name in architectures.SURFACE)
    mf = model_fields(cfg)
    assert mf == dict(
        vocab_size=12544, hidden_size=3072, intermediate_size=12288, num_layers=9, num_heads=48,
        num_kv_heads=8, head_dim=128, rms_norm_eps=1e-06, tie_embeddings=False,
        attn_qkv_bias=False, dtype="bfloat16", layer_types=CUT["layer_types"],
        sliding_window=512, heads_per_layer=CUT["num_attention_heads_per_layer"],
        rope_by_kind=ROPE, moe_intermediate_size=1024, num_experts_per_tok=10,
        norm_topk_prob=True, routed_scaling_factor=2.5, router_scoring="sigmoid",
        attn_gate=True, first_dense_layers=1, num_shared_experts=1, num_experts=256,
        experts_held=(0, 8), name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import laguna_s21_ep8_9l

    model = ModelConfig(**mf)
    assert model == dataclasses.replace(laguna_s21_ep8_9l(), name=NAME)
    assert model.num_experts_held == 32 and model.experts_held_range == (0, 32)
    assert model.layers_of("attention") == (0, 4, 8) and len(model.layers_of("window")) == 6
    # a value the equations do not cover is refused, not ignored
    for change in ({"gating": "elementwise"}, {"moe_router_logit_softcapping": 30},
                   {"moe_apply_router_weight_on_input": True}, {"hidden_act": "gelu"},
                   {"num_hidden_layers": 8}, {"num_experts": 64},
                   {"mlp_layer_types": ["dense", "sparse", "dense"] + 6 * ["sparse"]},
                   {"shared_expert_intermediate_size": 2048}):
        with pytest.raises(ValueError, match="laguna"):
            model_fields({**cfg, **change})
    with pytest.raises(ValueError, match="rope_type"):
        ModelConfig(**model_fields({**cfg, "rope_parameters": {
            **ROPE, "full_attention": {**ROPE["full_attention"], "rope_type": "llama3"}}}))


def test_the_file_holds_the_published_keys_unchanged_but_the_cuts():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                              "gating_types", "num_attention_heads_per_layer", "num_experts",
                              "vocab_size"] == list(CUT)[:1] + list(CUT)[3:] + list(CUT)[1:3]
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    assert cfg["experts_held"] == {"rank": 0, "of": 8, "published": 256}
    # the cut keeps the leading dense layer and two whole periods, in the published order
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert cfg[key] == PUBLISHED[key][:9]
    assert cfg["serve"]["quant"] is None and cfg["torch_dtype"] == "bfloat16"
    assert cfg["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    for words in ("6 pipeline stages x 8 chips", "rank 0 of 8", "32 experts",
                  "data-parallel attention", "nothing stands in for it",
                  "--tp and --pp are not engaged"):
        assert words in cfg["deployment"], words
    assert {"gating", "router", "hidden_act", "qk_norm", "rope_pairing", "sliding_window",
            "torch_dtype", "parameter_names", "weights", "serve"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    # the guide's floors: a whole period and four layers after the dense one, 8 experts,
    # an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - 1 >= 4 + len(PERIOD) and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # no width in reduced: every width, the router's 256 and its 10 a token as published
    assert not any(k.endswith(("_dim", "_rank", "_size")) or k == "num_experts_per_tok"
                   for k in cfg["reduced"] if k != "vocab_size")


def test_counts_by_hand_and_against_what_the_program_reads():
    import jax

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine import model as model_mod

    mf = model_fields(load_config(NAME))
    full = 3072 * (48 + 16) * 128 + 48 * 128 * 3072 + 3072 * 48
    window = 3072 * (72 + 16) * 128 + 72 * 128 * 3072 + 3072 * 72
    assert laguna.attention_params(mf, 0) == full == 44_187_648
    assert laguna.attention_params(mf, 1) == window == 63_135_744
    assert laguna.expert_params(mf) == 3 * 3072 * 1024 == 9_437_184
    assert laguna.experts_read_per_step(mf) == 32
    sparse = 3072 * 256 + 33 * 9_437_184
    want = 2 * (3 * full + 6 * window + 9 * 2 * 3072 + 3 * 3072 * 12288 + 8 * sparse
                + 3072 + 3072 * 12544)
    assert laguna.decode_weight_bytes(mf, None) == want == 6_321_850_368
    # what a decode step of the program reads: EVERY leaf but the layout marker and the
    # embedding table (a row a lane); all 32 held experts, on every row
    params = jax.eval_shape(lambda: model_mod.init_params(
        jax.random.PRNGKey(0), ModelConfig(**mf)))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert want == leaves - 4 - 2 * 12544 * 3072
    assert leaves - 4 == ModelConfig(**mf).param_bytes() == 6_398_920_704
    assert load_config(NAME)["serve"]["engine"]["max_num_seqs"] <= model_mod._EXPERTS_ALL_ROWS_MAX
    seen = architectures.Observed(decode_lanes_mean=3.0)        # the traffic has no say
    assert laguna.decode_weight_bytes(mf, None, seen) == want
    with pytest.raises(ValueError, match="unquantised"):
        laguna.decode_weight_bytes(mf, "int8")
    # 12,288 B a token: three full layers of 2 x 8 x 128 values; a window layer holds 17
    # blocks a sequence whatever the context
    assert laguna.kv_bytes_per_token(mf) == 3 * 4096 == 12288
    assert laguna.window_bytes_per_sequence(mf, 32) == 6 * 17 * 32 * 4096 == 13_369_344
    # the MEAN bytes of a step's nine calls: a full layer the blocks in use, a window layer
    # the blocks of the newest 512 + 32 tokens
    one = 32 * 4096
    assert laguna.attn_decode_bytes_per_layer([7200] * 48, mf, 32) == (
        (3 * 48 * 225 + 6 * 48 * 17) * one // 9)
    assert laguna.attn_decode_bytes_per_layer([300], mf, 32) == 10 * one   # inside the window
    # had the window layers walked the context, the count (and the share) would treble
    assert 48 * 225 * one / laguna.attn_decode_bytes_per_layer([7200] * 48, mf, 32) > 2.5
    routed = 10 * 32 / 256
    assert laguna.forward_flops_per_token(mf, 7200) == int(
        2 * (3 * full + 6 * window + 3 * 3072 * 12288
             + 8 * (3072 * 256 + (routed + 1) * 9_437_184) + 3072 * 12544)
        + 3 * 4 * 48 * 128 * 7200 + 6 * 4 * 72 * 128 * 512)


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == [] and len(man["workloads"]) >= 6
    assert [w["name"] for w in man["workloads"]].count(CELL) == 1
    assert [c["name"] for c in man["configs"]].count(NAME) == 1
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "longctx-agents", 1)
    assert manifest.topology_of(cell) == "one-worker"
    assert cell["why"] == (
        "closed loop, 48 clients, prompts 4096-8192, outputs 1536-2560 (8m+1): window+full "
        "layers at 72/48 heads, two cache pools, 32 of 256 experts (1 of 8 chips; attention "
        "8x its share); no prefix reuse") and len(cell["why"]) == 195
    entry = next(c for c in man["configs"] if c["name"] == NAME)
    assert len(entry["why"]) == 199 and "3 of 9 full (12 of 48 published)" in entry["why"]
    # the rules of form a driver holds every entry to
    for e in man["configs"] + man["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(e.get(key, "x")) <= 200 and e.get(key, "x").isprintable()
    assert len(json.dumps(man)) < 64 * 1024 and len(man["per_layer"]) <= 128
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    # at least these, under whatever name and wherever they stand: PR 39's four
    # and the set-up's, then the ones that waited for room until PR 41 made one
    # entry a metric (a traced run reported 8 numbers before it)
    for reader in (
            "decode_step_device_ms", "decode_weight_floor_share", "attn_decode_roofline",
            "window_attn_time_share", "warmup_s", "compile_s", "trace_lower_s",
            "correct_check_s",
            "attn_kernel_time_share", "experts_time_share", "shared_expert_time_share",
            "router_time_share", "lm_head_time_share", "unscoped_time_share",
            "prefill_device_ms_per_ktok", "prefill_wave_fill", "tokens_per_dispatch",
            "host_ms_per_dispatch", "decode_lane_occupancy", "preemptions_per_kdispatch",
            "device_idle_share", "hbm_peak_share", "closed_loop_ttft_ms_p50",
            "decode_ms_per_token", "prefill_stall_ms_per_token", "host_stall_ms_per_token",
            "device_starved_share", "device_account_error",
            "attn_gate_time_share", "window_blocks_released_per_ktok"):
        assert layer_entry(man, reader, CELL) is not None, reader
    assert len(manifest.metrics_of(man, "per_layer", CELL)) >= 20
    for name in ("tpot_ms_p50", "output_tokens_per_s"):
        listed = next(m for m in man["end_to_end"] if m["name"] == name)["workloads"]
        assert listed.count(CELL) == 1
    # what only a window model has lists this cell, as data files over
    # readers that were there
    for reader, module, args in (
            ("window_attn_time_share", "scope_share",
             {"scope": "window", "module": "_megastep_body"}),
            ("attn_gate_time_share", "scope_share",
             {"scope": "attn_gate", "module": "_megastep_body"}),
            ("window_blocks_released_per_ktok", "prometheus_ratio", {
                "endpoint": "worker", "scale": 1000,
                "numerator": {"name": "dynamo_engine_window_blocks_released_total"},
                "denominator": {"name": "dynamo_engine_committed_tokens_total"}})):
        entry = layer_entry(man, reader, CELL)
        assert CELL in entry["workloads"]
        spec = json.loads(manifest.metric_file("per_layer", entry["name"]).read_text())
        assert spec["reader"] == module and spec["args"] == args
    # the traffic, letter for letter
    traffic = generators.load_traffic(cell["traffic"])
    assert {k: traffic[k] for k in ("kind", "clients", "pool_per_client", "prompt_tokens",
                                    "output_tokens", "output_quantum", "ramp_seconds",
                                    "temperature")} == {
        "kind": "closed_loop", "clients": 48, "pool_per_client": 8,
        "prompt_tokens": {"dist": "uniform", "lo": 4096, "hi": 8192},
        "output_tokens": {"dist": "uniform", "lo": 1536, "hi": 2560},
        "output_quantum": 8, "ramp_seconds": 24, "temperature": 0.7}
    assert "think" not in " ".join(traffic)
    # every stream at its longest fits the full pool with room: no preemption
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["clients"] == engine["max_num_seqs"] == engine["decode_buckets"][-1] == 48
    worst = traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] + 1 + 32
    blocks = -(-worst // engine["block_size"])
    assert worst == 10785 and blocks == 338 and 48 * blocks == 16224 <= engine["num_kv_blocks"]
    assert worst <= engine["max_model_len"] == 338 * 32 and engine["prefill_buckets"][-1] == 2048
    # the window pool holds every lane's decode span and one widest wave
    from dynamo_tpu.engine import EngineConfig

    eng = EngineConfig(**engine_overrides(load_config(NAME)))
    assert eng.window_blocks_auto(512) == 944 <= engine["num_window_blocks"] == 1024
    assert eng.window_table_blocks(512) == 82 and eng.megastep == 8
    # a block is 384 KB in the full pool and 768 KB in the window pool
    mf = model_fields(load_config(NAME))
    assert 32 * laguna.kv_bytes_per_token(mf) == 384 * 1024
    cache = (engine["num_kv_blocks"] + 1) * 384 * 1024 + (engine["num_window_blocks"] + 1) * (
        6 * 32 * 4096)
    assert 7.24e9 < cache < 7.26e9 and 0.78 * 16.9e9 < cache + 6_398_920_704 < 0.9 * 16.9e9
    # the same work for every seed: the lengths are fixed quantiles, permuted
    plans = [generators.generate(traffic, seed, 45) for seed in (3999999979, 17)]
    lengths = [[r.max_tokens for c in p.clients for r in c[1:]] for p in plans]
    assert all(n % 8 == 1 and 1537 <= n <= 2561 for ns in lengths for n in ns)
    assert len(lengths[0]) == 48 * 7 and abs(sum(lengths[0]) - sum(lengths[1])) < 0.01 * sum(
        lengths[0])     # all but each client's first, which is cut to stagger the clients
    prompts = [sorted(len(r.prompt) for c in p.clients for r in c) for p in plans]
    assert prompts[0] == prompts[1] and 4096 <= prompts[0][0] and prompts[0][-1] <= 8192
    assert plans[0].temperature == 0.7


@pytest.fixture(scope="module")
def tiny_laguna():
    """One engine core of the rehearsal configuration, its probe sent twice."""
    import random

    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-laguna-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=7)
    rng = random.Random(7)
    body = {"prompt_ids": [rng.randrange(1, 384) for _ in range(40)], "max_tokens": 17,
            "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_reference_on_the_engines_tree_agrees_through_both_pools(tiny_laguna):
    cfg, core, _, got = tiny_laguna
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    assert set(core.params) >= {"moe", "dense_mlp", "attn", "attn_window", "final_norm",
                                "lm_head"}
    assert set(core.params["attn"]) == set(core.params["attn_window"]) == {"wqkv", "wo", "wg"}
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    # five windows of prompt, seven by the stream's end: both sends computed every row
    assert first["cached_tokens"] == repeat["cached_tokens"] == 0
    # ... and gave back, while it went on, every block behind its last window
    assert core.scheduler_stats()["window_blocks_released"] >= 2 * ((40 + 16 - 8) // 4 - 2)


def test_the_reference_needs_every_piece_it_is_given(tiny_laguna):
    cfg, core, body, _ = tiny_laguna
    mf = model_fields(cfg)
    ids, rows = body["prompt_ids"], [10, 39]
    mine = np.asarray(laguna.reference_logits(core.params, mf, ids, rows, vocab_chunks=3))
    same = np.asarray(laguna.reference_logits(core.params, mf, ids, rows, vocab_chunks=5))
    np.testing.assert_allclose(mine, same, atol=1e-5)
    for group, leaf in (("attn", "wg"), ("attn_window", "wg"), ("attn_window", "wo"),
                        ("moe", "shared_down"), ("dense_mlp", "w_down")):
        changed = {**core.params, group: {**core.params[group],
                                          leaf: core.params[group][leaf] * 0 + 0.01}}
        other = np.asarray(laguna.reference_logits(changed, mf, ids, rows, vocab_chunks=3))
        assert float(np.abs(mine - other).max()) > 1e-3, (group, leaf)
    for fault in ("window", "gate"):
        other = np.asarray(laguna.reference_logits(core.params, mf, ids, rows, faults=(fault,)))
        assert float(np.abs(mine - other).max()) > 1e-2, fault


def test_whole_command_on_the_cpu_on_the_two_pool_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-laguna-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "window_blocks_released_per_ktok"} <= set(
        result["metrics"]), result["metrics"]
    # prompts and streams past a window of 8 in blocks of 4: blocks are given
    # back behind the waves and behind the decode cursor as the streams go on
    assert result["metrics"]["window_blocks_released_per_ktok"]["value"] > 0
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    record = json.loads((ROOT / "chipbench_out" / "tiny-laguna-closed-1" / "run.json")
                        .read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    # DECIDED: no prefix hit on a window model, and /health says so
    assert record["reference"]["second_send_cached_tokens"] == 0
    startup = record["startup"][0]
    assert startup["prefix_caching"] is False and startup["window_blocks"] == 96
    assert startup["cache_layers"] == {"attention": 2, "conv": 0, "window": 3}
