"""The third architecture, added as files only: the looped stack
(``model_type`` "ouro"). Its key map pinned for the cell's configuration,
its counts by hand, its reference on the engine's own tree at the tiny size,
the reader of its one new scope, the cell as ISSUE 27 sizes it, and the
whole command on its rehearsal configuration."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import architectures, generators, manifest, peaks
from chipbench.architectures import ouro
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.readers import scope_share
from chipbench.reference import check
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_ouro.json"
NAME, CELL = "ouro-2.6b-bf16", "ouro2p6b-reason-decode"

# the catalog's copy of the published config.json (model-configs guide)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def test_found_by_model_type_with_the_key_map_pinned():
    assert {"qwen2", "mixtral", "ouro"} <= set(architectures.known())
    cfg = load_config(NAME)
    assert architectures.of(cfg) is ouro
    assert all(hasattr(ouro, name) for name in architectures.SURFACE)
    assert model_fields(cfg) == dict(
        vocab_size=49152, hidden_size=2048, intermediate_size=5632, num_layers=48,
        num_heads=16, num_kv_heads=16, head_dim=128, rope_theta=1000000,
        rms_norm_eps=1e-06, tie_embeddings=False, attn_qkv_bias=False, dtype="bfloat16",
        ut_steps=4, early_exit_threshold=1, sandwich_norm=True, name=NAME)

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.config import ouro_2_6b

    import dataclasses

    model = ModelConfig(**model_fields(cfg))
    assert model == dataclasses.replace(ouro_2_6b(), name=NAME)
    assert model.num_cache_layers == 192 and model.param_bytes() == 2 * 2_667_974_657


def test_the_file_holds_the_published_keys_unchanged():
    cfg = load_config(NAME)
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == [] and cfg["serve"]["quant"] is None
    assert cfg["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    # every line of the equations the published file does not give has its origin
    assert {"sandwich_norm", "norm_between_passes", "kv_slot_per_pass_and_layer", "exit_gate",
            "no_bias", "weights", "serve"} <= set(cfg["assumed"])
    assert all("modeling_ouro.py" in cfg["assumed"][k] for k in (
        "sandwich_norm", "norm_between_passes", "kv_slot_per_pass_and_layer", "exit_gate",
        "no_bias"))
    entry = next(c for c in manifest.load()["configs"] if c["name"] == NAME)
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"chipbench/configs/{NAME}.json"


def test_counts_by_hand():
    mf = model_fields(load_config(NAME))
    proj = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert proj == 51_380_224 == peaks.projection_params(mf)
    head, norm = 49152 * 2048 * 2, 2048 * 2
    assert ouro.kv_bytes_per_token(mf) == 192 * 2 * 16 * 128 * 2 == 1_572_864
    assert ouro.kv_bytes_per_token(mf, kv_bytes=1) == 786_432
    assert ouro.decode_weight_bytes(mf, None) == 192 * (proj * 2 + 4 * norm) + head + 4 * norm
    assert ouro.decode_weight_bytes(mf, None) == 19_934_494_720
    # a dense model of the same shapes streams its layers once: two norms a layer
    assert peaks.decode_weight_bytes(mf, None) == 48 * (proj * 2 + 2 * norm) + head + norm
    # int8 weight-only: a byte a weight, a float32 scale per output channel
    channels = (3 * 2048) + 2048 + 2 * 5632 + 2048
    assert ouro.decode_weight_bytes(mf, "int8") == (
        192 * (proj + 4 * channels + 4 * norm) + 49152 * 2048 + 4 * 49152 + 4 * norm)
    # the traffic does not move a dense step's bytes
    seen = architectures.Observed(decode_lanes_mean=7.5)
    assert ouro.decode_weight_bytes(mf, None, seen) == ouro.decode_weight_bytes(mf, None)
    # one kernel call per pass and layer reads one plane's blocks: 8 lanes at ~330 tokens
    per_call = ouro.attn_decode_bytes_per_layer([330] * 8, mf, 32)
    assert per_call == 8 * 11 * 32 * 2 * 16 * 128 * 2 == 23_068_672
    assert ouro.forward_flops_per_token(mf, 100) == (
        2 * (192 * proj + 2048 * 49152) + 192 * 4 * 2048 * 100)
    # a block of 32 tokens on the host or the wire
    assert 32 * ouro.kv_bytes_per_token(mf) == 50_331_648


def test_the_cell_is_the_one_the_issue_sizes():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-decode", 1)
    assert manifest.topology_of(cell) == "one-worker"
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e == {"setup_s", "tpot_ms_p50", "output_tokens_per_s"}
    # at least these, under whatever name and wherever they stand (PR 41: one
    # entry a metric, with a list of cells)
    for reader in (
            "decode_step_device_ms", "decode_weight_floor_share", "attn_kernel_time_share",
            "attn_decode_roofline", "lm_head_time_share", "unscoped_time_share",
            "device_idle_share", "hbm_peak_share", "tokens_per_dispatch", "host_ms_per_dispatch",
            "decode_lane_occupancy", "preemptions_per_kdispatch", "closed_loop_ttft_ms_p50",
            "layer_passes_per_token", "loop_norm_time_share", "warmup_s", "compile_s",
            "trace_lower_s", "correct_check_s"):
        assert layer_entry(man, reader, CELL) is not None, reader
    # the loop's own two list no cell that runs its stack once
    for reader in ("layer_passes_per_token", "loop_norm_time_share"):
        assert "qwen7b-decode-batch" not in layer_entry(man, reader, CELL)["workloads"]
    # every stream at its longest fits the cache with room: no preemption
    traffic = generators.load_traffic(cell["traffic"])
    engine = load_config(NAME)["serve"]["engine"]
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == engine["max_num_seqs"] == 8
    worst = traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"] + 1 + 8 + 16
    blocks = -(-worst // engine["block_size"])
    assert blocks == 20 and 8 * blocks <= engine["num_kv_blocks"] == 168
    assert worst <= engine["max_model_len"] == 2048
    # and the cache is 8.5 GB of the chip, beside 5.3 GB of weights
    mf = model_fields(load_config(NAME))
    cache = 169 * 32 * ouro.kv_bytes_per_token(mf)
    assert 0.25 * 16e9 < cache + 5_335_949_314 < 0.9 * 16.9e9
    plan = generators.generate(traffic, 3999999979, 45)
    lengths = [r.max_tokens for c in plan.clients for r in c[1:]]
    assert all(n % 8 == 1 and 193 <= n <= 353 for n in lengths) and len(lengths) == 56


@pytest.fixture(scope="module")
def tiny_ouro():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-ouro-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=5)
    body = {"prompt_ids": [int(t) for t in np.random.RandomState(0).randint(1, 380, size=40)],
            "max_tokens": 17, "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_reference_on_the_engines_tree_agrees_through_the_cache(tiny_ouro):
    cfg, core, _, got = tiny_ouro
    assert manifest.problems(manifest.load(ROOT / TINY)) == []
    assert set(core.params) >= {"exit_gate", "lm_head", "final_norm"}
    assert {"attn_post_norm", "mlp_post_norm"} <= set(core.params["layers"])
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] >= 32


def test_logits_after_each_pass_show_where_a_difference_grows(tiny_ouro):
    cfg, core, body, _ = tiny_ouro
    mf = model_fields(cfg)
    ids, rows = body["prompt_ids"], [10, 39]
    logits, gates = ouro.reference_logits(core.params, mf, ids, rows, vocab_chunks=3,
                                          every_pass=True)
    assert logits.shape == (3, 2, 384) and gates.shape == (3, 2)
    last = ouro.reference_logits(core.params, mf, ids, rows, vocab_chunks=5)
    np.testing.assert_allclose(np.asarray(logits[-1]), np.asarray(last), atol=1e-5)
    # the passes do something: a pass fewer is another model
    assert float(np.abs(np.asarray(logits[1] - logits[2])).max()) > 0.3
    two = ouro.reference_logits(core.params, {**mf, "ut_steps": 2}, ids, rows, vocab_chunks=3)
    np.testing.assert_allclose(np.asarray(two), np.asarray(logits[1]), atol=1e-5)


def _trace(ops):
    """``phases.load``'s shape: ops [name, start, dur, module, tf_op]."""
    return {"ops": ops, "modules": [["jit__megastep_body(1)", 0.0, 1000.0, "7"],
                                    ["jit__prefill_and_sample(2)", 2000.0, 500.0, "8"]]}


def test_scope_share_reads_a_scope_the_sections_do_not_list():
    path = "jit(_megastep_body)/while/body/while/body/"
    ops = [
        ["%while.1", 0.0, 1000.0, "", path[:-1]],                         # spans its body
        ["%fusion.1", 0.0, 600.0, "", path + "mlp/dot_general"],
        ["%fusion.2", 600.0, 100.0, "", path + "loop_norm/cond/branch_1_fun/mul"],
        ["%fusion.3", 700.0, 300.0, "", path + "lm_head/dot_general"],
        ["%fusion.4", 2000.0, 500.0, "", "jit(_prefill_and_sample)/while/body/loop_norm/mul"],
    ]
    assert scope_share.share(_trace(ops), "loop_norm", "_megastep_body") == pytest.approx(10.0)
    assert scope_share.share(_trace(ops), "loop_norm") == pytest.approx(100 * 600 / 1500)
    assert scope_share.share(_trace(ops), "lm_head", "_megastep_body") == pytest.approx(30.0)
    # a program without the scope, as the parent of PR 27 is: nothing to read
    assert scope_share.share(_trace(ops[:2] + ops[3:4]), "loop_norm", "_megastep_body") is None
    assert scope_share.share(_trace([]), "loop_norm") is None
    # part of a path, not of a name: "norm" is no scope here
    assert scope_share.share(_trace(ops), "norm") is None

    class Ctx:
        trace = None
        cell = {"name": "no-such-cell"}

    assert scope_share.read(Ctx(), "loop_norm") is None           # an untraced run
    Ctx.trace = {"devices": 1}
    assert scope_share.read(Ctx(), "loop_norm") is None           # no trace file


def test_whole_command_on_the_cpu_on_the_looped_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-ouro-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50", "layer_passes_per_token.loop"} <= set(
                result["metrics"]), result["metrics"]
    # three passes a token: a little more for the iterations a stream's end wastes, a
    # little either way for the dispatches whose commits fall on the window's other side
    assert 2.7 < result["metrics"]["layer_passes_per_token.loop"]["value"] < 3.5
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    record = json.loads((ROOT / "chipbench_out" / "tiny-ouro-closed-1" / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    assert record["reference"]["second_send_cached_tokens"] >= 32
