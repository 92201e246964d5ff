"""Engine-model numerics over the unified ragged forward: paged-cache
consistency across prefill/decode splits, sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import config as cfgmod
from dynamo_tpu.engine.model import decode_tokens, init_cache, init_params
from dynamo_tpu.engine.sampler import sample
from tests.model_harness import prefill_chunk

CFG = cfgmod.tiny_model()
ENG = cfgmod.tiny_engine()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _tables(block_ids: list[int], B: int) -> np.ndarray:
    t = np.full((B, ENG.max_blocks_per_seq), ENG.garbage_block, np.int32)
    t[0, : len(block_ids)] = block_ids
    return t


def test_prefill_then_decode_matches_monolithic_prefill(params):
    """Prefill(n) + k decode steps == one monolithic prefill(n+k)."""
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, CFG.vocab_size, size=37).tolist()
    extra = rng.randint(0, CFG.vocab_size, size=5).tolist()
    blocks = list(range(6))

    # Ground truth: one monolithic prefill over the whole sequence.
    want, _ = prefill_chunk(
        params, init_cache(CFG, ENG), prompt + extra, 0, blocks, CFG, ENG, 64
    )

    # Paged path: prefill the prompt, then decode the extra tokens.
    logits, cache = prefill_chunk(
        params, init_cache(CFG, ENG), prompt, 0, blocks, CFG, ENG, 64
    )
    B = ENG.max_num_seqs
    tables = _tables(blocks, B)
    for i, tok in enumerate(extra):
        toks_b = np.zeros(B, np.int32)
        toks_b[0] = tok
        pos = np.zeros(B, np.int32)
        pos[0] = len(prompt) + i
        active = np.zeros(B, bool)
        active[0] = True
        logits_b, cache = decode_tokens(
            params, cache, jnp.asarray(toks_b), jnp.asarray(tables),
            jnp.asarray(pos), jnp.asarray(active), CFG, ENG,
        )
        logits = logits_b[0]

    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_chunked_prefill_matches_monolithic(params):
    rng = np.random.RandomState(3)
    seq = rng.randint(0, CFG.vocab_size, size=48).tolist()
    blocks = list(range(8))

    want, _ = prefill_chunk(
        params, init_cache(CFG, ENG), seq, 0, blocks, CFG, ENG, 64
    )

    cache = init_cache(CFG, ENG)
    _, cache = prefill_chunk(params, cache, seq[:32], 0, blocks, CFG, ENG, 32)
    got, cache = prefill_chunk(params, cache, seq[32:], 32, blocks, CFG, ENG, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_mixed_ragged_batch_matches_separate_calls(params):
    """Two sequences of different chunk lengths in ONE forward_tokens call
    match two single-sequence calls (the engine's mixed-wave shape)."""
    from dynamo_tpu.engine.model import forward_tokens

    rng = np.random.RandomState(11)
    p1 = rng.randint(0, CFG.vocab_size, size=19).tolist()
    p2 = rng.randint(0, CFG.vocab_size, size=9).tolist()
    bs = ENG.block_size

    want1, _ = prefill_chunk(
        params, init_cache(CFG, ENG), p1, 0, [0, 1, 2], CFG, ENG, 32
    )
    want2, _ = prefill_chunk(
        params, init_cache(CFG, ENG), p2, 0, [3, 4], CFG, ENG, 32
    )

    T = 32
    n = len(p1) + len(p2)
    tokens = np.zeros(T, np.int32)
    tokens[:n] = p1 + p2
    positions = np.zeros(T, np.int32)
    positions[: len(p1)] = np.arange(len(p1))
    positions[len(p1) : n] = np.arange(len(p2))
    ids1, ids2 = np.array([0, 1, 2], np.int32), np.array([3, 4], np.int32)
    write_pages = np.full(T, ENG.garbage_block, np.int32)
    write_pages[: len(p1)] = ids1[np.arange(len(p1)) // bs]
    write_pages[len(p1) : n] = ids2[np.arange(len(p2)) // bs]
    write_offs = np.zeros(T, np.int32)
    write_offs[:n] = positions[:n] % bs
    tables = np.full((2, ENG.max_blocks_per_seq), ENG.garbage_block, np.int32)
    tables[0, :3] = ids1
    tables[1, :2] = ids2
    kv_lens = np.array([len(p1), len(p2)], np.int32)
    cu = np.array([0, len(p1), n], np.int32)
    last_rows = np.array([len(p1) - 1, n - 1], np.int32)

    logits, _ = forward_tokens(
        params, init_cache(CFG, ENG),
        jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(write_pages), jnp.asarray(write_offs),
        jnp.asarray(kv_lens), jnp.asarray(tables), jnp.asarray(cu),
        jnp.asarray(np.array([2], np.int32)), jnp.asarray(last_rows),
        CFG, ENG,
    )
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want1), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(want2), rtol=2e-3, atol=2e-3)


def test_sampler_greedy_and_distributions():
    V = 50
    logits = np.full((3, V), -10.0, np.float32)
    logits[0, 7] = 5.0          # greedy lane
    logits[1, [3, 4]] = [4.0, 3.9]  # top_k=2 lane
    logits[2, 11] = 8.0         # top_p tiny => only argmax survives
    out = sample(
        jnp.asarray(logits),
        jax.random.PRNGKey(0),
        temperature=jnp.asarray([0.0, 1.0, 1.0]),
        top_k=jnp.asarray([0, 2, 0], jnp.int32),
        top_p=jnp.asarray([1.0, 1.0, 0.1]),
    )
    out = np.asarray(out)
    assert out[0] == 7
    assert out[1] in (3, 4)
    assert out[2] == 11


def test_sampler_temperature_spread():
    logits = jnp.zeros((1, 16), jnp.float32)  # uniform
    seen = {
        int(sample(
            logits, jax.random.PRNGKey(i),
            jnp.asarray([1.0]), jnp.asarray([0], jnp.int32), jnp.asarray([1.0]),
        )[0])
        for i in range(24)
    }
    assert len(seen) > 4  # actually sampling, not collapsing to argmax


def test_int8_weight_only_quantization_accuracy():
    """Quantized params produce near-identical logits (per-channel int8 is
    ~0.4% weight error) and identical greedy generations on the tiny
    model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
    from dynamo_tpu.engine.model import init_params, quantize_params
    from tests.test_engine_core import _req, run_to_completion

    cfg = tiny_model()
    params = init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params)
    # Quantized leaves really are int8 (the capacity point).
    assert qparams["layers"]["wqkv"]["w"].dtype == jnp.int8
    assert qparams["layers"]["w_down"]["w"].dtype == jnp.int8

    core_f = EngineCore(cfg, tiny_engine(), params=params, seed=0)
    core_q = EngineCore(cfg, tiny_engine(), params=qparams, seed=0)
    prompt = list(range(3, 40))
    sf = core_f.add_request(_req(prompt, "f", max_tokens=8))
    sq = core_q.add_request(_req(prompt, "q", max_tokens=8))
    df, _ = run_to_completion(core_f, [sf])
    dq, _ = run_to_completion(core_q, [sq])
    # Greedy tokens should survive quantization on a tiny random model;
    # allow a small divergence tail (argmax near-ties).
    agree = sum(a == b for a, b in zip(df["f"], dq["q"]))
    assert agree >= 6, (df["f"], dq["q"])
