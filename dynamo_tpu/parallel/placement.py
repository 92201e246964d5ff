"""Memory-placement planning: does a (model, engine, mesh) fit the pod?

The north-star deployment (BASELINE.md) is Llama-3-70B disaggregated P/D
on a v5e-64 (16 hosts x 4 chips, 16 GB HBM each). This module is the
planning math a topology is checked against BEFORE burning a pod on an
OOM: per-chip parameter bytes under the TP sharding
(`parallel/sharding.py` — projections split over tp, embeddings/norms
replicated, dp replicas each hold a full copy), per-chip KV-cache bytes
(the combined [L, pages, bs, 2kv, d] cache splits its head axis over
tp), plus a headroom fraction for activations and XLA scratch.

Shape source of truth: ``jax.eval_shape`` over ``model.init_params`` /
``model.init_cache`` with the very PartitionSpecs the engine serves under
(`param_partition_specs`) — the plan counts exactly the arrays the engine
allocates, not a hand formula that can drift from the code.

:func:`place` is the other half: where the engine's weights and cache are
put, by kind of mesh (``EngineCore.__init__`` calls it after
``engine/options.py:resolve``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.tracing import startclock

# v5e: 16 GiB HBM per chip.
V5E_HBM_BYTES = 16 * 1024**3


@dataclass
class MemoryPlan:
    param_bytes_per_chip: int
    cache_bytes_per_chip: int
    headroom_frac: float

    @property
    def total_per_chip(self) -> int:
        return math.ceil(
            (self.param_bytes_per_chip + self.cache_bytes_per_chip)
            * (1.0 + self.headroom_frac)
        )

    def fits(self, hbm_bytes: int = V5E_HBM_BYTES) -> bool:
        return self.total_per_chip <= hbm_bytes

    def describe(self, hbm_bytes: int = V5E_HBM_BYTES) -> str:
        gib = 1024**3
        return (
            f"params {self.param_bytes_per_chip / gib:.2f} GiB/chip + "
            f"kv {self.cache_bytes_per_chip / gib:.2f} GiB/chip "
            f"(+{self.headroom_frac:.0%} headroom) = "
            f"{self.total_per_chip / gib:.2f} / {hbm_bytes / gib:.0f} GiB"
        )


def memory_plan(
    model: ModelConfig,
    engine: EngineConfig,
    tp: int,
    dp: int = 1,
    quant: str | None = None,
    headroom_frac: float = 0.15,
) -> MemoryPlan:
    """Per-chip memory plan for serving ``model`` on a dp x tp mesh.

    Parameter shapes come from ``jax.eval_shape`` of the real init (no
    device memory is touched); each leaf's per-chip share divides by the
    product of mesh axes its PartitionSpec names. ``quant='int8'`` maps
    each projection leaf to 1 byte/element + one float32 scale per
    output column (matching model.quantize_params). dp never divides —
    every dp replica holds full params and its own cache.
    """
    from jax.sharding import PartitionSpec

    from dynamo_tpu.engine.model import init_cache, init_params
    from dynamo_tpu.parallel.sharding import param_partition_specs

    params_shape = jax.eval_shape(
        lambda k: init_params(k, model, tp), jax.random.PRNGKey(0)
    )
    specs = param_partition_specs(model, tp)
    is_spec = lambda x: isinstance(x, PartitionSpec)  # noqa: E731
    spec_of = {
        path: spec
        for path, spec in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=is_spec
        )[0]
    }

    param_bytes = 0
    for path, sd in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
        spec = spec_of[path]
        on_tp = any(name == "tp" for name in spec)
        div = tp if on_tp else 1
        n = math.prod(sd.shape) if sd.shape else 1
        if quant == "int8" and sd.ndim >= 2 and on_tp:
            # Quantized set = the projections — exactly the tp-annotated
            # matrices (quantize_params leaves embeddings/norms at the
            # model dtype).
            param_bytes += math.ceil(n / div)  # 1 byte / element
            param_bytes += math.ceil(sd.shape[-1] / div) * 4  # f32 scales
        else:
            param_bytes += math.ceil(n / div) * sd.dtype.itemsize

    # Per-layer tuple cache (model.init_cache): combined-head axis over tp.
    cache_shapes = jax.eval_shape(lambda: init_cache(model, engine))
    cache_bytes = sum(
        math.ceil(math.prod(s.shape) / tp) * s.dtype.itemsize
        for s in cache_shapes
    )

    return MemoryPlan(
        param_bytes_per_chip=param_bytes,
        cache_bytes_per_chip=cache_bytes,
        headroom_frac=headroom_frac,
    )


def _check_fuse_tp(params, tp: int) -> None:
    """The fused wqkv/wgu column layout is tp-dependent; serving params
    fused for a different tp would produce silently wrong logits
    (permuted q/k/v and gate/up columns). Fail loudly instead."""
    from dynamo_tpu.engine.model import params_fuse_tp

    fused = params_fuse_tp(params)
    if fused != tp:
        raise ValueError(
            f"params were fused for tp={fused} but the serving mesh has "
            f"tp={tp}; reload with load_hf_llama(path, tp={tp}) or "
            f"init_params(rng, cfg, tp={tp})"
        )


def place(model_cfg, engine_cfg, params, seed: int, mesh, pp_mesh) -> tuple:
    """``(params, cache, dp, pp, pp_micro, batch_shardings)``: the weights
    and the cache where the kind of mesh puts them, with its sizes: staged
    over ``pp_mesh`` (parallel/pipeline.py, the STACKED cache), sharded
    over ``mesh`` (parallel/sharding.py), or on the one default device.
    ``params`` None initialises them in place, seeded; the start-up clock's
    ``weights`` stage ends where the cache's arrays begin. Weights fused for
    another tp are refused (:func:`_check_fuse_tp`); what the
    configurations and the meshes alone decide was checked before
    (engine/options.py)."""
    from dynamo_tpu.engine.model import init_cache, init_params

    dp = pp = pp_micro = 1
    batch_shardings = None
    tp = int(mesh.shape["tp"]) if mesh is not None else 1  # pp stages keep tp=1 layouts
    if params is not None:
        _check_fuse_tp(params, tp)
    if pp_mesh is not None:
        from dynamo_tpu.parallel.pipeline import (
            cache_sharding_pp,
            pp_microbatches,
            pp_param_specs,
            shard_params_pp,
        )

        pp = int(pp_mesh.shape["pp"])
        pp_micro = pp_microbatches(pp)
        if params is not None:
            # int8 params ({'w','scale'} dict leaves) shard like any
            # stacked layer array: both members carry the layer axis
            # first, so shard_params_pp places the pair per stage.
            params = shard_params_pp(params, model_cfg, pp_mesh)
        else:
            from jax.sharding import NamedSharding

            specs = pp_param_specs(model_cfg, pp)
            params = jax.jit(
                init_params,
                static_argnums=(1,),
                out_shardings=jax.tree.map(
                    lambda s: NamedSharding(pp_mesh, s), specs,
                    is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec
                    ),
                ),
            )(jax.random.PRNGKey(seed), model_cfg)
        # pp keeps the STACKED [L, ...] cache — the layer axis is the
        # stage sharding (parallel/pipeline.py).
        from dynamo_tpu.engine.model import init_cache_stacked

        startclock.mark("cache_alloc")
        cache = jax.jit(
            partial(init_cache_stacked, model_cfg, engine_cfg),
            out_shardings=cache_sharding_pp(
                pp_mesh, quantized=engine_cfg.kv_quantized
            ),
        )()
    elif mesh is not None:
        from dynamo_tpu.parallel.sharding import (
            cache_sharding,
            decode_batch_shardings,
            param_shardings,
            shard_params,
        )

        dp = int(mesh.shape["dp"])
        batch_shardings = decode_batch_shardings(mesh)
        if params is None:
            # Initialize directly into the sharded layout — no
            # single-device staging (a 70B pytree never fits one chip).
            params = jax.jit(
                init_params,
                static_argnums=(1, 2),
                out_shardings=param_shardings(model_cfg, mesh),
            )(jax.random.PRNGKey(seed), model_cfg, tp)
        else:
            params = shard_params(params, model_cfg, mesh)
        startclock.mark("cache_alloc")
        cache = jax.jit(
            partial(init_cache, model_cfg, engine_cfg),
            out_shardings=cache_sharding(
                mesh,
                quantized=engine_cfg.kv_quantized,
                num_layers=model_cfg.num_layers,
            ),
        )()
    else:
        if params is not None:
            # Host pytrees (engine/loader.py returns numpy) land on
            # device ONCE here; device arrays pass through untouched.
            params = jax.device_put(params)
        params = params if params is not None else init_params(
            jax.random.PRNGKey(seed), model_cfg
        )
        startclock.mark("cache_alloc")
        cache = init_cache(model_cfg, engine_cfg)
    return params, cache, dp, pp, pp_micro, batch_shardings
