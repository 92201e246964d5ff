"""The fourth architecture, added as files only: ``model_type`` "axk1"
(latent attention over a latent page, a chip's share of sigmoid-routed
experts beside a shared one). Its key map pinned for the cell's
configuration, the published keys unchanged, its counts by hand and
against what the program reads, its reference on the engine's own tree at
the tiny size, the reader of its roofline, the cell as ISSUE 32 sizes it,
and the whole command on its rehearsal configuration."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import axk1
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.readers import scope_roofline
from chipbench.reference import check
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_axk1.json"
NAME, CELL = "a.x-k1-ep16-bf16", "axk1-ep16-decode"

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none", "v_head_dim": 128,
    "vocab_size": 163840}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 12, "vocab_size": 20480}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "mixtral", "ouro", "axk1"} <= set(architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is axk1
    assert all(hasattr(axk1, name) for name in architectures.SURFACE)
    mf = model_fields(cfg)
    assert mf == dict(
        vocab_size=20480, hidden_size=7168, intermediate_size=18432, num_layers=7,
        num_heads=64, num_kv_heads=64, head_dim=192, rope_theta=10000, rms_norm_eps=1e-06,
        tie_embeddings=False, attn_qkv_bias=False, dtype="bfloat16", attention="mla",
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_scaling=PUBLISHED["rope_scaling"], first_dense_layers=1,
        moe_intermediate_size=2048, num_experts=192, num_experts_per_tok=8,
        router_scoring="sigmoid", n_group=8, topk_group=4, norm_topk_prob=True,
        routed_scaling_factor=2.5, num_shared_experts=1, experts_held=(0, 16), name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import axk1_ep16

    model = ModelConfig(**mf)
    assert model == dataclasses.replace(axk1_ep16(), name=NAME)
    assert model.experts_held_range == (0, 12) and model.param_bytes() == 9_682_663_424
    # a value the equations do not cover is refused, not ignored
    for key, value in (("topk_method", "noaux_tc"), ("moe_layer_freq", 2),
                       ("scoring_func", "softmax"), ("n_routed_experts", 16)):
        with pytest.raises(ValueError, match="axk1"):
            model_fields({**cfg, key: value})


def test_the_file_holds_the_published_keys_unchanged_but_the_three_cuts():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert cfg["reduced"] == sorted(CUT, key=list(cfg["reduced"]).index) and set(
        cfg["reduced"]) == set(CUT)
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    assert cfg["experts_held"] == {"rank": 0, "of": 16, "published": 192}
    assert cfg["serve"]["quant"] is None and cfg["torch_dtype"] == "bfloat16"
    assert cfg["source"] == "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    for words in ("16 chips, 12 apiece", "rank 0", "data-parallel attention", "an eighth",
                  "pipeline stages", "without the exchange"):
        assert words in cfg["deployment"], words
    assert {"topk_method", "group_score", "rope_pairing", "yarn", "torch_dtype", "weights",
            "serve"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    # the guide's floors: four sparse layers after the dense one, 8 experts, 1/8 vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_counts_by_hand_and_against_what_the_program_reads():
    import jax

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine import model as model_mod

    mf = model_fields(load_config(NAME))
    attn = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    assert attn == 101_122_048 and axk1.attention_params(mf) == attn + 1536 + 512
    assert axk1.expert_params(mf) == 3 * 7168 * 2048 == 44_040_192
    assert axk1.experts_read_per_step(mf) == 12
    sparse = 7168 * 192 + 13 * 44_040_192
    want = 2 * (7 * (attn + 2048 + 2 * 7168) + 3 * 7168 * 18432 + 6 * sparse
                + 7168 + 7168 * 20480)
    assert axk1.decode_weight_bytes(mf, None) == want == 9_389_062_144
    # what a decode step of the program reads: every leaf but the embedding table
    # (a row a lane) and the layout marker; all 12 held experts, on every row
    params = jax.eval_shape(lambda: model_mod.init_params(
        jax.random.PRNGKey(0), ModelConfig(**mf)))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert want == leaves - 2 * 20480 * 7168 - 4
    assert load_config(NAME)["serve"]["engine"]["max_num_seqs"] <= model_mod._EXPERTS_ALL_ROWS_MAX
    seen = architectures.Observed(decode_lanes_mean=3.0)        # the traffic has no say
    assert axk1.decode_weight_bytes(mf, None, seen) == want
    with pytest.raises(ValueError, match="unquantised"):
        axk1.decode_weight_bytes(mf, "int8")
    assert axk1.kv_bytes_per_token(mf) == 7 * 576 * 2 == 8064
    # 128 lanes at ~1040 tokens: 33 blocks each of 32 x 1152 B, once for all 64 heads
    assert axk1.attn_decode_bytes_per_layer([1040] * 128, mf, 32) == 128 * 33 * 32 * 1152
    routed = 8 * 12 / 192
    assert axk1.forward_flops_per_token(mf, 1000) == int(
        2 * (7 * (attn + 2048) + 3 * 7168 * 18432 + 7168 * 20480
             + 6 * (7168 * 192 + (routed + 1) * 44_040_192))
        + 7 * 2 * 64 * (1024 + 64) * 1000)


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == [] and len(man["workloads"]) >= 4
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "ep-decode", 1)
    assert manifest.topology_of(cell) == "one-worker"
    # the driver holds a configuration's why and source to 200 characters too;
    # manifest.problems only measures the cells'
    for entry in man["configs"] + man["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200 and entry.get(key, "x").isprintable()
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    # at least these, under whatever name and wherever they stand (PR 41: one
    # entry a metric, with a list of cells)
    for reader in (
            "decode_step_device_ms", "decode_weight_floor_share", "attn_kernel_time_share.axk1",
            "latent_attn_roofline", "router_time_share", "experts_time_share",
            "shared_expert_time_share", "experts_touched_per_step", "expert_pairs_held_share",
            "prefill_device_ms_per_ktok", "prefill_wave_fill", "tokens_per_dispatch",
            "host_ms_per_dispatch", "decode_lane_occupancy", "preemptions_per_kdispatch",
            "lm_head_time_share", "unscoped_time_share", "device_idle_share", "hbm_peak_share",
            "closed_loop_ttft_ms_p50", "warmup_s", "compile_s", "trace_lower_s",
            "correct_check_s"):
        assert layer_entry(man, reader, CELL) is not None, reader
    # its latent attention reads with a file of its own, so the dense cells'
    # kernel share does not list it
    assert CELL not in layer_entry(man, "attn_kernel_time_share",
                                            "qwen7b-decode-batch")["workloads"]
    # the traffic, letter for letter
    traffic = generators.load_traffic(cell["traffic"])
    assert {k: traffic[k] for k in ("kind", "clients", "pool_per_client", "prompt_tokens",
                                    "output_tokens", "output_quantum", "ramp_seconds")} == {
        "kind": "closed_loop", "clients": 128, "pool_per_client": 8,
        "prompt_tokens": {"dist": "uniform", "lo": 256, "hi": 768},
        "output_tokens": {"dist": "uniform", "lo": 768, "hi": 1280},
        "output_quantum": 8, "ramp_seconds": 12}
    assert "temperature" not in traffic and "think" not in " ".join(traffic)
    # every stream at its longest fits the cache with room: no preemption
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["clients"] == engine["max_num_seqs"] == engine["decode_buckets"][-1] == 128
    worst = traffic["prompt_tokens"]["hi"] + 16 + traffic["output_tokens"]["hi"] + 1 + 16
    blocks = -(-worst // engine["block_size"])
    assert worst == 2081 and blocks == 66 and 128 * blocks == 8448 <= engine["num_kv_blocks"]
    assert worst <= engine["max_model_len"] == 4096 and engine["block_size"] == 32
    assert engine["prefill_buckets"][-1] >= 2 * (traffic["prompt_tokens"]["hi"] + 16)
    # and the chip is full as a deployment's would be: weights 9.68 GB, cache 3.17 GB
    # (1.45 x the worst case), ~2.2 GB of temporaries at the peak beside them
    mf = model_fields(load_config(NAME))
    cache = (engine["num_kv_blocks"] + 1) * 32 * axk1.kv_bytes_per_token(mf)
    assert 3.0e9 < cache < 3.4e9 and 0.75 * 16.9e9 < cache + 9_682_663_424 < 0.8 * 16.9e9
    # the same work for every seed: the lengths are fixed quantiles, permuted
    plans = [generators.generate(traffic, seed, 45) for seed in (3999999979, 17)]
    lengths = [[r.max_tokens for c in p.clients for r in c[1:]] for p in plans]
    assert all(n % 8 == 1 and 769 <= n <= 1281 for ns in lengths for n in ns)
    assert len(lengths[0]) == 128 * 7 and abs(sum(lengths[0]) - sum(lengths[1])) < 0.01 * sum(
        lengths[0])     # all but each client's first, which is cut to stagger the clients
    prompts = [sorted(len(r.prompt) for c in p.clients for r in c) for p in plans]
    assert prompts[0] == prompts[1] and 256 <= prompts[0][0] and prompts[0][-1] <= 768


@pytest.fixture(scope="module")
def tiny_axk1():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-axk1-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=5)
    body = {"prompt_ids": [int(t) for t in np.random.RandomState(0).randint(1, 380, size=40)],
            "max_tokens": 17, "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_reference_on_the_engines_tree_agrees_through_the_latent_cache(tiny_axk1):
    cfg, core, _, got = tiny_axk1
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    assert set(core.params) >= {"moe", "dense_mlp", "lm_head", "final_norm"}
    assert {"wq_a", "q_norm", "wkv_a", "kv_norm", "wk_b", "wv_b"} <= set(core.params["layers"])
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] >= 32


def test_the_reference_is_given_the_same_share_and_the_shares_differ(tiny_axk1):
    cfg, core, body, _ = tiny_axk1
    mf = model_fields(cfg)
    ids, rows = body["prompt_ids"], [10, 39]
    mine = axk1.reference_logits(core.params, mf, ids, rows, vocab_chunks=3)
    same = axk1.reference_logits(core.params, mf, ids, rows, vocab_chunks=5, held=(0, 4))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(same), atol=1e-5)
    none = axk1.reference_logits(core.params, mf, ids, rows, vocab_chunks=3, held=(0, 0))
    assert float(np.abs(np.asarray(mine - none)).max()) > 0.3    # the held experts matter


def _trace(ops):
    """``phases.load``'s shape: ops [name, start, dur, module, tf_op]."""
    return {"ops": ops, "modules": [["jit__megastep_body(1)", 0.0, 1000.0, "7"],
                                    ["jit__prefill_and_sample(2)", 2000.0, 500.0, "8"]]}


def test_scope_roofline_counts_the_scopes_own_seconds_and_reads_nothing_from_a_parent():
    path = "jit(_megastep_body)/while/body/attn/latent_paged_attention/"
    ops = [
        ["%while.1", 0.0, 1000.0, "", "jit(_megastep_body)/while/body"],     # spans its body
        ["%while.2", 100.0, 400.0, "", path + "while"],                      # the chunk loop
        ["%fusion.1", 100.0, 250.0, "", path + "while/body/gather"],
        ["%fusion.2", 350.0, 150.0, "", path + "while/body/dot_general"],
        ["%fusion.3", 500.0, 300.0, "", "jit(_megastep_body)/while/body/experts/dot_general"],
        ["%fusion.4", 2000.0, 500.0, "",
         "jit(_prefill_and_sample)/attn/latent_paged_attention/mul"],
    ]
    inside = scope_roofline.scope_seconds(_trace(ops), "latent_paged_attention",
                                          "_megastep_body")
    assert inside == pytest.approx(400e-9)      # the loop and its body once, not twice
    assert scope_roofline.scope_seconds(_trace(ops[:1] + ops[4:]), "latent_paged_attention",
                                        "_megastep_body") == 0.0

    class Ctx:
        trace = None
        cell = {"name": "no-such-cell"}
        records: list = []

    args = {"scope": "latent_paged_attention", "module": "_megastep_body"}
    assert scope_roofline.read(Ctx(), **args) is None                  # an untraced run
    Ctx.trace = {"devices": 1, "modules": {}}
    assert scope_roofline.read(Ctx(), **args) is None                  # no such program
    spec = json.loads((ROOT / "chipbench/layer_metrics/latent_attn_roofline.json").read_text())
    assert spec["reader"] == "scope_roofline" and spec["args"] == args
    own = manifest.metric_file("per_layer", "attn_kernel_time_share.axk1")
    assert own.name == "attn_kernel_time_share.axk1.json"
    assert json.loads(own.read_text())["args"]["scope"] == "latent_paged_attention"


def test_whole_command_on_the_cpu_on_the_latent_sparse_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-axk1-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "experts_touched_per_step",
            "expert_pairs_held_share"} <= set(result["metrics"]), result["metrics"]
    # four experts held of sixteen, four chosen a token in two of four groups
    assert 0 < result["metrics"]["experts_touched_per_step"]["value"] <= 4
    assert 5 < result["metrics"]["expert_pairs_held_share"]["value"] < 60
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    record = json.loads((ROOT / "chipbench_out" / "tiny-axk1-closed-1" / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    assert record["reference"]["second_send_cached_tokens"] >= 32
