"""Time the dense gather-based decode attention against the Pallas
kernel at bench decode shapes.

Hypothesis (from tools/profile_decode.py): at decode shapes the Pallas
ragged kernel is DMA-latency-bound at ~12x its KV traffic (~215 us/layer
at B=32 vs ~18 us of page reads). A dense XLA path — gather the whole
block table span into [T, span, heads, d], one masked softmax — moves
~2x the bytes (gather write+read) but is pure streaming, so it should
win whenever span (= max_model_len / block_size pages) is small.

Usage: python -m tools.time_dense_decode_attn [--batch 32] [--ctx 192]
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig, llama3_1b
from dynamo_tpu.ops.ragged_attention import (
    ragged_paged_attention_ref,
)


def time_chain(fn, q, kv, n_iters, n=5):
    def chain(q, kv):
        def body(acc, _):
            return fn(acc, kv), ()

        acc, _ = jax.lax.scan(body, q, jnp.arange(n_iters))
        return acc

    jitted = jax.jit(chain)
    jax.block_until_ready(jitted(q, kv))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(q, kv))
        best = min(best, time.perf_counter() - t0)
    return best


def slope(fn, q, kv):
    """Per-call cost from a 64->256 chain-length slope: 192 calls of
    signal dwarf run-to-run variation in the fixed per-dispatch cost,
    which wrecked shorter two-point fits (negative slopes)."""
    t64 = time_chain(fn, q, kv, 64)
    t256 = time_chain(fn, q, kv, 256)
    return (t256 - t64) / 192 * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=192)
    ap.add_argument("--blocks", type=int, default=512)
    ap.add_argument("--max-model-len", type=int, default=512)
    args = ap.parse_args()

    from dynamo_tpu.device import enable_compile_cache, require_accelerator

    enable_compile_cache()
    print(f"# device={require_accelerator('tools/time_dense_decode_attn.py')}")

    cfg = llama3_1b()
    engine = EngineConfig(
        num_kv_blocks=args.blocks, block_size=32, max_model_len=args.max_model_len
    )
    B = args.batch
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, cfg.num_heads, cfg.head_dim), cfg.jax_dtype)
    kv = jnp.asarray(
        rng.randn(
            args.blocks + 1, engine.block_size, 2 * cfg.num_kv_heads, cfg.head_dim
        ),
        cfg.jax_dtype,
    )
    kv_lens = jnp.full((B,), args.ctx + 1, jnp.int32)
    per = engine.max_blocks_per_seq
    tables = jnp.asarray(
        np.stack([rng.permutation(args.blocks)[:per] for _ in range(B)]), jnp.int32
    )
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    num_seqs = jnp.asarray([B], jnp.int32)
    sm_scale = cfg.head_dim ** -0.5

    span = per * engine.block_size
    gather_mb = B * span * 2 * cfg.num_kv_heads * cfg.head_dim * 2 / 1e6
    print(
        f"# B={B} ctx={args.ctx} span={span} gather={gather_mb:.1f}MB/layer "
        f"(x{cfg.num_layers} layers)"
    )

    def dense(qq, kv):
        return ragged_paged_attention_ref(
            qq, kv, kv_lens, tables, cu, num_seqs, sm_scale=sm_scale
        )

    def kernel(qq, kv):
        from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
            ragged_paged_attention as k,
        )

        return k(
            qq, kv, kv_lens, tables, cu, num_seqs, sm_scale=sm_scale,
            num_kv_pages_per_block=8, num_queries_per_block=8,
        )

    for name, fn in (("pallas_p8_q8", kernel), ("dense_gather", dense)):
        t = slope(fn, q, kv)
        print(f"{name:14s} {t:8.4f} ms/call ({t*cfg.num_layers:7.3f} ms/model-step)")


if __name__ == "__main__":
    main()
