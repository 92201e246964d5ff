"""Device-mesh sharding for the JAX engine: megatron-style TP + DP.

The reference delegates tensor parallelism to its GPU engines and only
plumbs `tp_size` flags (`components/backends/vllm/src/dynamo/vllm/args.py`,
SURVEY.md §2.6); on TPU the partitioning is first-party and rides ICI via
XLA collectives — no NCCL.

Mapping (classic megatron over axes ``("dp", "tp")``):
- fused ``wqkv``: column-parallel — the shard-blocked fuse layout
  (``[q_s | k_s | v_s]`` per shard, model.fuse_qkv) makes a plain
  ``P(None, None, "tp")`` hand each shard its own (q, k, v) block
- attention output / mlp down: row-parallel (XLA inserts the psum)
- fused ``wgu``: column-parallel, same shard-blocked trick
- lm_head: vocab-split (sampling reduces across shards inside jit)
- combined paged KV cache ``[L, n_pages, page_size, 2*n_kv, d]``:
  combined-head axis split across tp (K/V interleaved, so K and V of a
  head land on the same shard)
- decode batch: split across dp; prefill (one sequence) replicated on dp

Requires ``tp`` to divide num_heads, num_kv_heads, and intermediate_size
(llama3 GQA: tp ≤ 8). Larger tp would split head_dim — future work.

IMPORTANT: the fused params must have been built with THIS tp
(``init_params(rng, cfg, tp)`` / ``load_hf_llama(path, tp=...)``) — the
shard-blocked column order depends on it.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig


def make_mesh(dp: int = 1, tp: int = 1, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if dp * tp > len(devices):
        raise ValueError(f"mesh {dp}x{tp} needs {dp*tp} devices, have {len(devices)}")
    grid = np.asarray(devices[: dp * tp]).reshape(dp, tp)
    return Mesh(grid, ("dp", "tp"))


def param_partition_specs(cfg: ModelConfig, tp: int) -> dict[str, Any]:
    """PartitionSpec pytree matching `model.init_params` structure
    (mesh-free: also used for memory planning of pods larger than the
    local machine, parallel/placement.py).

    A model with latent attention or the sigmoid-routed sparse MLP has no
    rule here: its share of a layer is stated WITHOUT a mesh, as
    ``ModelConfig.experts_held = (rank, of)`` (the chips that share each
    layer's routed experts; attention, router, shared experts and norms
    whole on every one), and one chip runs it without the exchange."""
    if cfg.latent or cfg.shared_sparse:
        from dynamo_tpu.engine.config import UnsupportedModelOption

        raise UnsupportedModelOption(
            "tp", cfg.name,
            "no sharding rule for the latent projections or the held experts "
            "(a share is stated with experts_held, not with a mesh)",
        )
    for what, n in (
        ("num_kv_heads", cfg.num_kv_heads),
        ("num_heads", cfg.num_heads),
        ("intermediate_size", cfg.intermediate_size),
    ):
        if n % tp:
            raise ValueError(f"tp={tp} must divide {what}={n}")

    layers = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        "wqkv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
    }
    if cfg.attn_qkv_bias:
        layers["bqkv"] = P(None, "tp")  # fused column order, like wqkv
    if cfg.sandwich_norm:
        layers["attn_post_norm"] = P(None, None)
        layers["mlp_post_norm"] = P(None, None)
    if cfg.is_moe:
        # Expert parallelism: the expert axis shards over the model axis;
        # the expert-sum contraction becomes a psum over 'tp'.
        if cfg.num_experts % tp:
            raise ValueError(
                f"tp={tp} must divide num_experts={cfg.num_experts}"
            )
        layers["w_router"] = P(None, None, None)
        layers["w_gate"] = P(None, "tp", None, None)
        layers["w_up"] = P(None, "tp", None, None)
        layers["w_down"] = P(None, "tp", None, None)
    else:
        layers["wgu"] = P(None, None, "tp")
        layers["w_down"] = P(None, "tp", None)
    specs = {
        "embed": P(None, None),
        "layers": layers,
        "final_norm": P(None),
        "fuse_tp": P(),
    }
    if cfg.ut_steps > 1:
        specs["exit_gate"] = {"w": P(None), "b": P()}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def param_shardings(cfg: ModelConfig, mesh: Mesh) -> dict[str, Any]:
    """NamedSharding pytree matching `model.init_params` structure."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_partition_specs(cfg, mesh.shape["tp"]),
        is_leaf=lambda x: isinstance(x, P),
    )


def cache_sharding(mesh: Mesh, quantized: bool = False, num_layers: int = 0):
    """Per-layer cache pages [n_pages, page_size, 2*n_kv, d] —
    combined-head axis on tp. One sharding covers every element of the
    per-layer tuple (model.init_cache) as a pytree prefix.

    ``quantized`` (int8 KV, engine/kv_quant.py): each layer entry is a
    {"kv": 4-D, "scale": 3-D} dict, so the prefix trick no longer fits
    one rank — return the full per-layer tuple (``num_layers`` entries),
    scale pages sharded on the same combined-head axis."""
    if not quantized:
        return NamedSharding(mesh, P(None, None, "tp", None))
    entry = {
        "kv": NamedSharding(mesh, P(None, None, "tp", None)),
        "scale": NamedSharding(mesh, P(None, None, "tp")),
    }
    return tuple(dict(entry) for _ in range(num_layers))


def decode_batch_shardings(mesh: Mesh) -> dict[str, NamedSharding]:
    """Decode-step batch operands: batch axis split across dp."""
    dp = NamedSharding(mesh, P("dp"))
    return {
        "tokens": dp,
        "block_tables": NamedSharding(mesh, P("dp", None)),
        "positions": dp,
        "active": dp,
    }


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def expand_specs_for_params(specs: Any, params: Any) -> Any:
    """Match a PartitionSpec pytree to a possibly int8-quantized params
    pytree: where params holds a quantized weight ``{"w", "scale"}``
    (model.quantize_weight layout) under a single spec leaf, expand to
    per-member specs. ``scale`` is ``w``'s shape with the contraction
    axis collapsed to 1, so any sharded axis that is size-1 in scale
    (row-parallel weights: wo, w_down) replicates instead."""
    def expand(spec, p):
        if isinstance(p, dict) and set(p) == {"w", "scale"}:
            scale_spec = P(*[
                ax if p["scale"].shape[i] != 1 else None
                for i, ax in enumerate(spec)
            ])
            return {"w": spec, "scale": scale_spec}
        if isinstance(p, dict):
            return {k: expand(spec[k], p[k]) for k in p}
        return spec

    return {k: expand(specs[k], params[k]) for k in params}


def shard_params(params: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    """Place an (unsharded, possibly int8-quantized) params pytree onto
    the mesh."""
    specs = param_partition_specs(cfg, mesh.shape["tp"])
    if "fuse_tp" not in params:  # pytrees predating the layout marker
        specs.pop("fuse_tp")
    specs = expand_specs_for_params(specs, params)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )
