"""The second architecture, added as files only: the sparse top-k MLP
(``model_type`` "mixtral") at the tiny size on the CPU. Its key map and
counts by hand, the engine against its plain reference through the cache,
faults in the router caught, and the whole command on its configuration."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import architectures, manifest, peaks
from chipbench.architectures import mixtral
from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check
from chipbench.reference import mixtral as reference

ROOT = Path(__file__).resolve().parents[2]
MOE = "tests/chipbench/data/tiny_manifest_moe.json"


def test_found_by_model_type_with_its_own_key_map():
    assert {"qwen2", "mixtral"} <= set(architectures.known())
    assert manifest.problems(manifest.load(ROOT / MOE)) == []
    cfg = load_config("tiny-moe-rehearsal")
    assert architectures.of(cfg) is mixtral
    mf = model_fields(cfg)
    assert (mf["num_experts"], mf["num_experts_per_tok"], mf["intermediate_size"]) == (4, 2, 96)
    assert "attn_qkv_bias" not in mf and mf["head_dim"] == 16

    from dynamo_tpu.engine import ModelConfig
    from dynamo_tpu.engine.model import _moe_capacity

    model = ModelConfig(**mf)
    assert model.is_moe and not model.attn_qkv_bias
    # what the file's ``assumed`` says: every expert can hold every token
    assert all(_moe_capacity(n, model) == n for n in (1, 4, 8, 32, 128))


def test_counts_by_hand():
    mf = model_fields(load_config("tiny-moe-rehearsal"))
    h, i, v, L, q, kv, E, k = 64, 96, 384, 2, 64, 32, 4, 2
    attention = h * (q + 2 * kv) + q * h
    expert, router = 3 * h * i, h * E
    rest = 2 * h * 4 + 0   # two norms a layer in float32; no bias

    def stream(experts):   # float32, tied: the embedding table is the output matrix
        return L * ((attention + router + experts * expert) * 4 + rest) + h * v * 4 + h * 4

    seen = architectures.Observed
    assert mixtral.experts_read_per_step(mf) == k
    assert mixtral.decode_weight_bytes(mf, None) == stream(2)
    assert mixtral.experts_read_per_step(mf, seen(decode_lanes_mean=1.0)) == k
    # 3 lanes: 4 (1 - 0.5^3) = 3.5 distinct experts expected; many lanes: all 4
    assert mixtral.decode_weight_bytes(mf, None, seen(decode_lanes_mean=3.0)) == stream(3.5)
    assert mixtral.decode_weight_bytes(mf, None, seen(decode_lanes_mean=64.0)) == pytest.approx(
        stream(4), rel=1e-9)
    with pytest.raises(ValueError):
        mixtral.decode_weight_bytes(mf, "int8")
    # a token computes with its k experts and the router, whatever the batch
    assert mixtral.forward_flops_per_token(mf, 10) == (
        2 * (L * (attention + router + k * expert) + h * v) + L * 4 * q * 10)
    assert mixtral.kv_bytes_per_token(mf) == peaks.kv_bytes_per_token(mf) == 2 * kv * 2 * L


def test_routing_weights_are_the_softmax_over_the_chosen():
    logits = jax.numpy.asarray([[2.0, 0.0, 1.0, -1.0], [0.0, 0.0, 3.0, 3.0]])
    w = np.asarray(reference.routing_weights(logits, 2))
    e = np.exp([2.0, 1.0])
    np.testing.assert_allclose(w[0], [e[0] / e.sum(), 0, e[1] / e.sum(), 0], rtol=1e-6)
    np.testing.assert_allclose(w[1], [0, 0, 0.5, 0.5], rtol=1e-6)


@pytest.fixture(scope="module")
def tiny_moe():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-moe-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=5)
    body = {"prompt_ids": [int(t) for t in np.random.RandomState(0).randint(1, 380, size=40)],
            "max_tokens": 17, "top": 5}
    return cfg, core, body, check.score_request(core, cfg, body)


def test_engine_agrees_with_reference_through_the_cache(tiny_moe):
    _, _, _, got = tiny_moe
    verdict = check.compare(got["served"], got["scored"])
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] >= 32


def _all_experts_softmax(router_logits, top_k):
    """The fault: softmax over all experts, the chosen not renormalised."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    _, idx = jax.lax.top_k(router_logits, top_k)
    rows = jax.numpy.arange(router_logits.shape[0])[:, None]
    return jax.numpy.zeros_like(probs).at[rows, idx].set(probs[rows, idx])


@pytest.mark.parametrize("fault", ["top_k_off_by_one", "softmax_over_all_experts",
                                   "experts_swapped"])
def test_a_fault_in_the_sparse_block_is_caught(tiny_moe, fault, monkeypatch):
    cfg, core, body, got = tiny_moe
    params = core.params
    if fault == "top_k_off_by_one":
        cfg = {**cfg, "num_experts_per_tok": cfg["num_experts_per_tok"] + 1}
    elif fault == "softmax_over_all_experts":
        monkeypatch.setattr(reference, "routing_weights", _all_experts_softmax)
    else:
        layers = dict(params["layers"])
        layers["w_down"] = layers["w_down"][:, ::-1]
        params = {**params, "layers": layers}
    seqs = [check.score_probe(cfg, params, body["prompt_ids"], probe, vocab_chunks=3)
            for probe in got["served"]]
    verdict = check.compare(got["served"], {"sequences": seqs})
    assert not verdict["ok"] and verdict["max_abs_diff"] > 2 * check.LOGPROB_ATOL


def test_whole_command_on_the_cpu_on_the_sparse_configuration():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}   # as a user's shell
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-moe-closed-1", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", MOE, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert {"tokens_per_dispatch", "device_idle_share", "warmup_s", "correct_check_s",
            "closed_loop_ttft_ms_p50"} <= set(result["metrics"]), result["metrics"]
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    record = json.loads((ROOT / "chipbench_out" / "tiny-moe-closed-1" / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]
    assert record["reference"]["second_send_cached_tokens"] >= 32
