"""Sparse MoE: routing math, dense equivalence, EP sharding, engine e2e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineCore, tiny_engine
from dynamo_tpu.engine.config import ModelConfig, tiny_moe
from dynamo_tpu.engine.model import (
    _mlp,
    _moe_mlp,
    fuse_gu,
    init_cache,
    init_params,
)
from dynamo_tpu.parallel.sharding import cache_sharding, make_mesh, shard_params
from tests.model_harness import prefill_chunk
from tests.test_engine_core import _req, run_to_completion

MOE = tiny_moe()


def test_moe_reduces_to_dense_with_identical_experts():
    """top_k == num_experts with identical experts == the dense MLP."""
    cfg = ModelConfig(
        name="t", vocab_size=64, hidden_size=16, intermediate_size=32,
        num_layers=1, num_heads=2, num_kv_heads=2, head_dim=8, dtype="float32",
        num_experts=4, num_experts_per_tok=4, tie_embeddings=True,
    )
    rng = jax.random.PRNGKey(0)
    w_gate = jax.random.normal(rng, (16, 32)) * 0.1
    w_up = jax.random.normal(jax.random.fold_in(rng, 1), (16, 32)) * 0.1
    w_down = jax.random.normal(jax.random.fold_in(rng, 2), (32, 16)) * 0.1
    dense_w = {"wgu": fuse_gu(w_gate, w_up), "w_down": w_down}
    moe_lp = {
        "w_router": jnp.zeros((16, 4)),  # uniform routing
        "w_gate": jnp.tile(w_gate[None], (4, 1, 1)),
        "w_up": jnp.tile(w_up[None], (4, 1, 1)),
        "w_down": jnp.tile(w_down[None], (4, 1, 1)),
    }
    x = jax.random.normal(jax.random.fold_in(rng, 3), (6, 16))
    dense_cfg = ModelConfig(
        name="d", vocab_size=64, hidden_size=16, intermediate_size=32,
        num_layers=1, num_heads=2, num_kv_heads=2, head_dim=8, dtype="float32",
        tie_embeddings=True,
    )
    want = _mlp(x, dense_w, dense_cfg, tp=1)
    got = _moe_mlp(x, moe_lp, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_moe_top_k_sparsity():
    """Only top-k experts receive nonzero weight."""
    cfg = tiny_moe()
    rng = jax.random.PRNGKey(1)
    params = init_params(rng, cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0 slice
    x = jax.random.normal(rng, (5, cfg.hidden_size))
    router = jnp.dot(x, lp["w_router"])
    _, idx = jax.lax.top_k(router, cfg.num_experts_per_tok)
    out = _moe_mlp(x, lp, cfg)
    assert out.shape == x.shape
    assert int(idx.shape[1]) == 2


def test_moe_engine_generates_end_to_end():
    core = EngineCore(MOE, tiny_engine(), seed=0)
    seq = core.add_request(_req(list(range(2, 30)), "moe1", max_tokens=6))
    done, fin = run_to_completion(core, [seq])
    assert len(done["moe1"]) == 6
    assert fin["moe1"] == "length"
    # Greedy determinism across engines.
    core2 = EngineCore(MOE, tiny_engine(), seed=0)
    seq2 = core2.add_request(_req(list(range(2, 30)), "moe2", max_tokens=6))
    done2, _ = run_to_completion(core2, [seq2])
    assert done2["moe2"] == done["moe1"]


@pytest.mark.slow  # heaviest moe compile; tier-1 keeps the alltoall/e2e cells
def test_moe_expert_parallel_matches_single_device():
    eng = tiny_engine()
    prompt = list(np.arange(1, 21))
    blocks = [0, 1, 2, 3]

    params1 = init_params(jax.random.PRNGKey(2), MOE, tp=1)
    want, _ = prefill_chunk(
        params1, init_cache(MOE, eng), prompt, 0, blocks, MOE, eng, 32
    )

    mesh = make_mesh(dp=2, tp=2)  # ep rides the tp axis: 4 experts / 2
    params2 = init_params(jax.random.PRNGKey(2), MOE, tp=2)
    sp = shard_params(params2, MOE, mesh)
    cd = jax.device_put(init_cache(MOE, eng), cache_sharding(mesh))
    got, _ = prefill_chunk(sp, cd, prompt, 0, blocks, MOE, eng, 32, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def _dense_moe_reference(x, lp, cfg):
    """All-experts dense dispatch (the pre-round-4 implementation), kept
    as ground truth for the sparse gather/scatter path."""
    xf = x.reshape(-1, x.shape[-1])
    N = xf.shape[0]
    router = jnp.dot(xf, lp["w_router"], preferred_element_type=jnp.float32)
    vals, idx = jax.lax.top_k(router, cfg.num_experts_per_tok)
    probs = jax.nn.softmax(vals, axis=-1)
    weights = jnp.zeros_like(router).at[jnp.arange(N)[:, None], idx].set(probs)
    gate = jnp.einsum("nh,ehi->nei", xf, lp["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.einsum("nh,ehi->nei", xf, lp["w_up"], preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gate) * up).astype(x.dtype)
    down = jnp.einsum("nei,eih->neh", act, lp["w_down"], preferred_element_type=jnp.float32)
    return jnp.einsum("ne,neh->nh", weights, down).astype(x.dtype).reshape(x.shape)


def test_sparse_dispatch_matches_dense_reference():
    """With enough capacity, sparse gather/scatter dispatch is exact."""
    import dataclasses

    cfg = dataclasses.replace(tiny_moe(), moe_capacity_factor=float(tiny_moe().num_experts))
    rng = jax.random.PRNGKey(7)
    params = init_params(rng, cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.fold_in(rng, 1), (13, cfg.hidden_size))
    want = _dense_moe_reference(x, lp, cfg)
    got = _moe_mlp(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_sparse_dispatch_flops_scale_with_top_k_not_num_experts():
    """Per-token expert-MLP FLOPs must follow top_k (x capacity factor),
    not num_experts — the point of sparse dispatch."""
    import dataclasses

    cfg = dataclasses.replace(
        tiny_moe(), num_experts=8, num_experts_per_tok=1, moe_capacity_factor=1.0
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.ones((32, cfg.hidden_size))

    def flops(fn):
        cost = jax.jit(fn).lower(x).compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost["flops"])

    sparse = flops(lambda v: _moe_mlp(v, lp, cfg))
    dense = flops(lambda v: _dense_moe_reference(v, lp, cfg))
    # Dense computes all 8 experts per token; sparse only top-1 + padding.
    assert sparse < dense / 3, f"sparse {sparse} not ≪ dense {dense}"


def test_capacity_overflow_drops_tokens_not_correctness():
    """With capacity 1 and every token routed to one expert, outputs stay
    finite and shaped (dropped tokens contribute zero, GShard semantics)."""
    import dataclasses

    cfg = dataclasses.replace(tiny_moe(), moe_capacity_factor=0.01)
    params = init_params(jax.random.PRNGKey(3), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (9, cfg.hidden_size))
    out = _moe_mlp(x, lp, cfg)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))


def test_alltoall_dispatch_matches_replicated_and_dense():
    """Token all-to-all EP dispatch (wide-EP mode, cfg.moe_dispatch=
    'alltoall') equals the replicated-dispatch path AND the dense
    reference on the same mesh with generous capacity (both dispatch
    modes, identical outputs)."""
    import dataclasses

    cfg = dataclasses.replace(
        tiny_moe(), moe_capacity_factor=float(tiny_moe().num_experts)
    )
    rng = jax.random.PRNGKey(7)
    params = init_params(rng, cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    # 14 tokens: NOT divisible by tp=2 — exercises the a2a pad path.
    x = jax.random.normal(jax.random.fold_in(rng, 1), (14, cfg.hidden_size))
    want = _dense_moe_reference(x, lp, cfg)

    mesh = make_mesh(dp=1, tp=2)
    from jax.sharding import NamedSharding, PartitionSpec as P

    lp_sharded = {
        "w_router": jax.device_put(lp["w_router"], NamedSharding(mesh, P())),
        "w_gate": jax.device_put(lp["w_gate"], NamedSharding(mesh, P("tp"))),
        "w_up": jax.device_put(lp["w_up"], NamedSharding(mesh, P("tp"))),
        "w_down": jax.device_put(lp["w_down"], NamedSharding(mesh, P("tp"))),
    }
    rep = _moe_mlp(x, lp_sharded, cfg, mesh=mesh)
    a2a_cfg = dataclasses.replace(cfg, moe_dispatch="alltoall")
    a2a = _moe_mlp(x, lp_sharded, a2a_cfg, mesh=mesh)

    np.testing.assert_allclose(np.asarray(rep), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a2a), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a2a), np.asarray(rep), rtol=1e-6, atol=1e-6)


def test_alltoall_engine_parity_with_single_device():
    """The REAL EngineCore in alltoall EP mode matches the single-device
    engine greedily (EP e2e for the wide-EP dispatch)."""
    import dataclasses

    cfg = dataclasses.replace(
        tiny_moe(), moe_capacity_factor=float(tiny_moe().num_experts)
    )

    def run(mesh, moe_dispatch):
        c = dataclasses.replace(cfg, moe_dispatch=moe_dispatch)
        core = EngineCore(c, tiny_engine(), seed=0, mesh=mesh)
        seqs = [
            core.add_request(_req(list(range(5 + i, 30 + i)), f"r{i}", max_tokens=5))
            for i in range(2)
        ]
        done, fins = run_to_completion(core, seqs)
        assert len(fins) == 2
        return done

    want = run(None, "replicated")
    got = run(make_mesh(dp=2, tp=2), "alltoall")
    assert got == want
