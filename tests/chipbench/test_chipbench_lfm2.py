"""The fifth architecture, added as files only: ``model_type`` "lfm2_moe"
(gated short convolutions whose state rides the block table beside one GQA
layer in four at head width 64, 64 whole bias-chosen experts). Its key map
pinned for the cell's configuration, the published keys unchanged, its
counts by hand and against what the program reads, its reference on the
engine's own tree at the tiny size, the cell as ISSUE 35 sizes it, and the
whole command on its rehearsal configuration."""

import copy
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import architectures, generators, manifest
from chipbench.architectures import lfm2_moe
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_lfm2.json"
NAME, CELL = "lfm2-24b-a2b-10l-bf16", "lfm2-24b-hybrid-decode"
PERIOD = ["full_attention", "conv", "conv", "conv"]

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
CUT = {"num_hidden_layers": 10, "layer_types": ["conv", "conv"] + PERIOD * 2}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "mixtral", "ouro", "axk1", "lfm2_moe"} <= set(architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is lfm2_moe
    assert all(hasattr(lfm2_moe, name) for name in architectures.SURFACE)
    mf = model_fields(cfg)
    assert mf == dict(
        vocab_size=65536, hidden_size=2048, intermediate_size=11776, num_layers=10,
        num_heads=32, num_kv_heads=8, head_dim=64, rope_theta=1000000.0, rms_norm_eps=1e-05,
        tie_embeddings=True, dtype="bfloat16", layer_types=CUT["layer_types"], conv_L_cache=3,
        conv_bias=False, qk_norm=True, first_dense_layers=2, moe_intermediate_size=1536,
        num_experts=64, num_experts_per_tok=4, router_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=1, router_bias=True, router_norm_eps=1e-6, name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import lfm2_24b_a2b_10l

    model = ModelConfig(**mf)
    assert model == dataclasses.replace(lfm2_24b_a2b_10l(), name=NAME)
    assert model.param_bytes() == 10_534_180_352 and model.kv_head_pairs and model.hybrid
    # a value the equations do not cover is refused, not ignored
    with pytest.raises(ValueError, match="lfm2_moe"):
        model_fields({**cfg, "rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}})
    with pytest.raises(ValueError, match="lfm2_moe"):
        model_fields({**cfg, "num_hidden_layers": 9})
    with pytest.raises(NotImplementedError, match="conv_bias"):
        ModelConfig(**model_fields({**cfg, "conv_bias": True}))


def test_the_file_holds_the_published_keys_unchanged_but_the_two_cuts():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    # the cut keeps the leading dense layers and two whole periods, in the published order
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:10]
    assert cfg["serve"]["quant"] is None and cfg["torch_dtype"] == "bfloat16"
    assert cfg["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    for words in ("4 stages of 10 layers", "stage 0", "final norm and the head",
                  "--pp is not engaged"):
        assert words in cfg["deployment"], words
    assert {"head_dim", "tie_word_embeddings", "torch_dtype", "router_norm_eps", "expert_bias",
            "qk_norm", "rope_pairing", "conv", "weights", "serve"} <= set(cfg["assumed"])
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    # the guide's floors: a whole period and four layers after the dense ones, 8 experts,
    # an eighth of the vocabulary: here 8 layers, all 64 experts, the whole vocabulary
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4 + len(PERIOD)
    assert cfg["num_experts"] == 64 and cfg["vocab_size"] == PUBLISHED["vocab_size"]
    # no width in reduced
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in cfg["reduced"])


def test_counts_by_hand_and_against_what_the_program_reads():
    import jax

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine import model as model_mod

    mf = model_fields(load_config(NAME))
    assert lfm2_moe.conv_params(mf) == 2048 * 6144 + 3 * 2048 + 2048 * 2048 == 16_783_360
    assert lfm2_moe.attention_params(mf) == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 == 10_485_888
    assert lfm2_moe.expert_params(mf) == 3 * 2048 * 1536 == 9_437_184
    assert lfm2_moe.experts_read_per_step(mf) == 64
    sparse = 2048 * 64 + 64 * 9_437_184
    want = 2 * (8 * 16_783_360 + 2 * 10_485_888 + 10 * 2 * 2048 + 2 * 3 * 2048 * 11776
                + 8 * sparse + 2048 + 2048 * 65536) + 8 * 64 * 4
    assert lfm2_moe.decode_weight_bytes(mf, None) == want == 10_534_181_376
    # what a decode step of the program reads: EVERY leaf but the layout marker; the tied
    # embedding table is read whole as the output matrix; all 64 experts, on every row
    params = jax.eval_shape(lambda: model_mod.init_params(
        jax.random.PRNGKey(0), ModelConfig(**mf)))
    leaves = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert want == leaves - 4
    assert load_config(NAME)["serve"]["engine"]["max_num_seqs"] <= model_mod._EXPERTS_ALL_ROWS_MAX
    seen = architectures.Observed(decode_lanes_mean=3.0)        # the traffic has no say
    assert lfm2_moe.decode_weight_bytes(mf, None, seen) == want
    with pytest.raises(ValueError, match="unquantised"):
        lfm2_moe.decode_weight_bytes(mf, "int8")
    # 4,096 B a token: two attention layers of 2 x 8 x 64 values; the conv layers hold none
    assert lfm2_moe.kv_bytes_per_token(mf) == 2 * 2048 == 4096
    assert lfm2_moe.state_bytes_per_sequence(mf) == 8 * 8192
    # ONE attention layer's call: 128 lanes at ~1300 tokens, 41 blocks each of 32 x 2048 B
    # (the published head width: a head padded to 128 would be twice that)
    assert lfm2_moe.attn_decode_bytes_per_layer([1300] * 128, mf, 32) == 128 * 41 * 32 * 2048
    assert lfm2_moe.forward_flops_per_token(mf, 1000) == int(
        2 * (8 * 16_783_360 + 2 * 10_485_888 + 2 * 3 * 2048 * 11776
             + 8 * (2048 * 64 + 4 * 9_437_184) + 2048 * 65536)
        + 2 * 4 * 32 * 64 * 1000)


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    assert manifest.problems(man) == [] and len(man["workloads"]) >= 5
    assert [w["name"] for w in man["workloads"]].count(CELL) == 1
    assert [c["name"] for c in man["configs"]].count(NAME) == 1
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "hybrid-decode", 1)
    assert manifest.topology_of(cell) == "one-worker"
    # the rules of form a driver holds every entry to (manifest.problems measures the cells' why)
    for entry in man["configs"] + man["workloads"]:
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200 and entry.get(key, "x").isprintable()
    assert len(json.dumps(man)) < 64 * 1024 and len(man["per_layer"]) <= 128
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    # at least these, under whatever name and wherever they stand (PR 41: one
    # entry a metric, with a list of cells)
    for reader in (
            "decode_step_device_ms", "decode_weight_floor_share", "conv_time_share",
            "conv_state_time_share", "attn_kernel_time_share", "attn_decode_roofline",
            "router_time_share", "experts_time_share", "experts_touched_per_step",
            "prefill_device_ms_per_ktok", "prefill_wave_fill", "tokens_per_dispatch",
            "host_ms_per_dispatch", "decode_lane_occupancy", "preemptions_per_kdispatch",
            "lm_head_time_share", "unscoped_time_share", "device_idle_share", "hbm_peak_share",
            "closed_loop_ttft_ms_p50", "warmup_s", "compile_s", "trace_lower_s",
            "correct_check_s"):
        assert layer_entry(man, reader, CELL) is not None, reader
    # the two conv readers' files are data over a reader that was there, and
    # only a cell with a conv layer lists them
    for name, scope in (("conv_time_share", "conv"), ("conv_state_time_share", "state")):
        entry = layer_entry(man, name, CELL)
        assert CELL in entry["workloads"]
        spec = json.loads(manifest.metric_file("per_layer", entry["name"]).read_text())
        assert spec["reader"] == "scope_share"
        assert spec["args"] == {"scope": scope, "module": "_megastep_body"}
    # the traffic, letter for letter
    traffic = generators.load_traffic(cell["traffic"])
    assert {k: traffic[k] for k in ("kind", "clients", "pool_per_client", "prompt_tokens",
                                    "output_tokens", "output_quantum", "ramp_seconds")} == {
        "kind": "closed_loop", "clients": 128, "pool_per_client": 8,
        "prompt_tokens": {"dist": "uniform", "lo": 256, "hi": 768},
        "output_tokens": {"dist": "uniform", "lo": 1024, "hi": 2048},
        "output_quantum": 8, "ramp_seconds": 12}
    assert "temperature" not in traffic and "think" not in " ".join(traffic)
    # every stream at its longest fits the cache with room: no preemption
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["clients"] == engine["max_num_seqs"] == engine["decode_buckets"][-1] == 128
    worst = traffic["prompt_tokens"]["hi"] + 16 + traffic["output_tokens"]["hi"] + 1 + 16
    blocks = -(-worst // engine["block_size"])
    assert worst == 2849 and blocks == 90 and 128 * blocks == 11520 <= engine["num_kv_blocks"]
    assert worst <= engine["max_model_len"] == 4096 and engine["block_size"] == 32
    assert engine["prefill_buckets"][-1] >= 2 * (traffic["prompt_tokens"]["hi"] + 16)
    # a block is 192 KB: two attention layers' K/V and eight conv layers' state
    mf = model_fields(load_config(NAME))
    per_block = 32 * lfm2_moe.kv_bytes_per_token(mf) + lfm2_moe.state_bytes_per_sequence(mf)
    assert per_block == 192 * 1024
    cache = (engine["num_kv_blocks"] + 1) * per_block
    assert 0.75 * 16.9e9 < cache + 10_534_180_352 < 0.9 * 16.9e9
    # the same work for every seed: the lengths are fixed quantiles, permuted
    plans = [generators.generate(traffic, seed, 45) for seed in (3999999979, 17)]
    lengths = [[r.max_tokens for c in p.clients for r in c[1:]] for p in plans]
    assert all(n % 8 == 1 and 1025 <= n <= 2049 for ns in lengths for n in ns)
    assert len(lengths[0]) == 128 * 7 and abs(sum(lengths[0]) - sum(lengths[1])) < 0.01 * sum(
        lengths[0])     # all but each client's first, which is cut to stagger the clients
    prompts = [sorted(len(r.prompt) for c in p.clients for r in c) for p in plans]
    assert prompts[0] == prompts[1] and 256 <= prompts[0][0] and prompts[0][-1] <= 768


def test_a_later_cell_joins_an_entry_by_list_and_a_suffix_a_cell_is_refused():
    """The naming rule of PR 41 (chipbench/README.md): one per-layer entry a
    metric. A cell a later PR appends (here two made-up ones, with a
    configuration of their own) joins the ``workloads`` list of every entry it
    reports and the file stays sound; the same reader file under a suffix of
    the cell's own, moving the same end-to-end metric, is what
    ``manifest.problems`` refuses. (Until PR 41 a fixture in a conftest.py here
    cut the file to its first four cells for one test that pinned their
    number; the pin is loosened and the fixture gone.)"""
    man = manifest.load()
    keys = [(manifest.metric_file("per_layer", m["name"]).stem, m["moves"])
            for m in man["per_layer"]]
    assert len(set(keys)) == len(keys)
    later = copy.deepcopy(man)
    for n in ("x", "y"):
        later["configs"].append(dict(man["configs"][0], name=f"cfg-{n}",
                                     file=man["configs"][0]["file"]))
        later["workloads"].append(dict(man["workloads"][0], name=f"cell-{n}",
                                       config=f"cfg-{n}", traffic="decode-batch"))
        for m in later["end_to_end"] + later["per_layer"]:
            if man["workloads"][0]["name"] in m.get("workloads", ()):
                m["workloads"].append(f"cell-{n}")
    # (the made-up cells share a traffic file with a made-up configuration each,
    # so the pair of configuration and traffic still appears once)
    assert manifest.problems(later) == []
    assert len(later["per_layer"]) == len(man["per_layer"])
    base = layer_entry(man, "decode_step_device_ms", CELL)
    suffixed = copy.deepcopy(man)
    suffixed["per_layer"].append(dict(base, name=base["name"] + ".x", workloads=[CELL]))
    assert any("one entry with a list of cells" in p for p in manifest.problems(suffixed))
    # a suffix is for another end-to-end metric under moves, or a reader file of its own
    chat = layer_entry(man, "decode_step_device_ms", "qwen1p5b-chat-steady")
    assert (chat["name"], chat["moves"]) == (base["name"] + ".chat", "tpot_ms_mean")
    assert manifest.metric_file("per_layer", "attn_kernel_time_share.axk1").stem == (
        "attn_kernel_time_share.axk1")


@pytest.fixture(scope="module")
def tiny_lfm2():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-lfm2-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=5)
    body = {"prompt_ids": [int(t) for t in np.random.RandomState(0).randint(1, 380, size=40)],
            "max_tokens": 17, "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_reference_on_the_engines_tree_agrees_through_both_kinds_of_page(tiny_lfm2):
    cfg, core, _, got = tiny_lfm2
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    assert set(core.params) >= {"moe", "dense_mlp", "conv", "attn", "final_norm"}
    assert "lm_head" not in core.params and "expert_bias" in core.params["moe"]
    assert set(core.params["conv"]) == {"in_proj", "conv_w", "out_proj"}
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] == 32
    assert core.conv_state_reads["prefix_hit"] == 1


def test_the_reference_needs_every_piece_it_is_given(tiny_lfm2):
    cfg, core, body, _ = tiny_lfm2
    mf = model_fields(cfg)
    ids, rows = body["prompt_ids"], [10, 39]
    mine = np.asarray(lfm2_moe.reference_logits(core.params, mf, ids, rows, vocab_chunks=3))
    same = np.asarray(lfm2_moe.reference_logits(core.params, mf, ids, rows, vocab_chunks=5))
    np.testing.assert_allclose(mine, same, atol=1e-5)
    for group, leaf in (("conv", "conv_w"), ("attn", "k_layernorm"), ("moe", "expert_bias")):
        changed = {**core.params, group: {**core.params[group],
                                          leaf: core.params[group][leaf] * 0 + 1.0}}
        other = np.asarray(lfm2_moe.reference_logits(changed, mf, ids, rows, vocab_chunks=3))
        assert float(np.abs(mine - other).max()) > 1e-3, (group, leaf)


def test_whole_command_on_the_cpu_on_the_hybrid_sparse_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-lfm2-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "experts_touched_per_step"} <= set(
        result["metrics"]), result["metrics"]
    # all eight experts held, two chosen a token
    assert 0 < result["metrics"]["experts_touched_per_step"]["value"] <= 8
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    record = json.loads((ROOT / "chipbench_out" / "tiny-lfm2-closed-1" / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    assert record["reference"]["second_send_cached_tokens"] >= 32
