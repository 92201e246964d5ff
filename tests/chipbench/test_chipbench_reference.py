"""The plain reference against the engine at the tiny size on the CPU:
prefill then decode through the paged cache must agree with the
reference's full forward pass, in log-probabilities; a fault in either
must be caught."""

import copy

import jax
import numpy as np
import pytest

from chipbench.configs import engine_overrides, load_config, model_fields
from chipbench.reference import check, qwen2


@pytest.fixture(scope="module")
def tiny():
    from dynamo_tpu.engine import EngineConfig, EngineCore, ModelConfig

    cfg = load_config("tiny-rehearsal")
    core = EngineCore(ModelConfig(**model_fields(cfg)),
                      EngineConfig(**engine_overrides(cfg)), seed=5)
    body = {"prompt_ids": list(np.random.RandomState(0).randint(1, 380, size=40)),
            "max_tokens": 17, "top": 5}
    body["prompt_ids"] = [int(t) for t in body["prompt_ids"]]
    return cfg, core, body, check.score_request(core, cfg, body)


def test_engine_agrees_with_reference_through_the_cache(tiny):
    _, _, body, got = tiny
    verdict = check.compare(got["served"], got["scored"])
    # float32 model on the CPU: agreement is to rounding, far inside the
    # tolerance set for bf16 on the chip.
    assert verdict["ok"] and verdict["max_abs_diff"] < 1e-4
    assert verdict["compared"] == 2 * 17 * 5
    first, repeat = got["served"]
    assert len(first["tokens"]) == 17 and first["tokens"] == repeat["tokens"]
    assert first["cached_tokens"] == 0 and repeat["cached_tokens"] >= 32


def test_the_worker_answers_with_its_megastep_length(tiny):
    _, core, _, got = tiny
    assert got["megastep_k"] == core.engine.megastep >= 1


@pytest.mark.parametrize("fault", ["drop_bias", "scale_wo", "shift_logprobs"])
def test_a_fault_is_caught(tiny, fault):
    cfg, core, body, got = tiny
    served = copy.deepcopy(got["served"])
    if fault == "shift_logprobs":
        # what a lower-precision engine looks like from outside
        for s in served:
            s["top_lps"] = [[lp - 0.5 for lp in row] for row in s["top_lps"]]
        scored = got["scored"]
    else:
        params = dict(core.params)
        layers = dict(params["layers"])
        if fault == "drop_bias":
            layers["bqkv"] = layers["bqkv"] * 0
        else:
            layers["wo"] = layers["wo"] * 1.5
        params["layers"] = layers
        seqs = [check.score_probe(cfg, params, body["prompt_ids"], probe, vocab_chunks=3)
                for probe in served]
        scored = {"sequences": seqs}
    assert not check.compare(served, scored)["ok"]


def test_rope_is_the_rotate_half_convention():
    x = jax.numpy.ones((3, 1, 4))
    out = np.asarray(qwen2.rope(x, jax.numpy.arange(3), 10000.0))
    np.testing.assert_allclose(out[0], 1.0)              # position 0: identity
    # position 1, pair (0, 2) rotates by angle 1: (cos - sin, cos + sin)
    np.testing.assert_allclose(out[1, 0, [0, 2]], [np.cos(1) - np.sin(1), np.cos(1) + np.sin(1)],
                               rtol=1e-6)


def test_chunked_vocab_equals_whole(tiny):
    cfg, core, body, _ = tiny
    mf = model_fields(cfg)
    ids = body["prompt_ids"]
    a = check.reference_logprobs(cfg, core.params, ids, [len(ids) - 1], vocab_chunks=1)
    b = check.reference_logprobs(cfg, core.params, ids, [len(ids) - 1], vocab_chunks=7)
    np.testing.assert_allclose(a, b, atol=1e-6)
    assert a.shape == (1, mf["vocab_size"])
