"""Sweep Pallas ragged-paged-attention grid constants at decode shapes.

The kernel's (num_kv_pages_per_block, num_queries_per_block) grid choice
dominates decode attention cost (tools/profile_decode.py measured
3.8 ms/step vs ~0.5 ms of KV traffic at bench shapes). Times a 64-long
scan of kernel calls per config so the per-invocation dispatch overhead
amortizes away.

Usage: python -m tools.sweep_attention [--batch 32] [--ctx 192]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig, llama3_1b

def _time_chain(q, kv, kv_lens, tables, cu, num_seqs, sm_scale, kw, n_iters, n=3):
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention as kernel,
    )

    def chain(q, kv):
        def body(acc, _):
            out = kernel(
                q + acc * 0.0, kv, kv_lens, tables, cu, num_seqs,
                sm_scale=sm_scale, **kw,
            )
            return out, ()
        acc, _ = jax.lax.scan(body, q, jnp.arange(n_iters))
        return acc

    fn = jax.jit(chain)
    jax.block_until_ready(fn(q, kv))  # compile + sync
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, kv))
        best = min(best, time.perf_counter() - t0)
    return best


def time_config(q, kv, kv_lens, tables, cu, num_seqs, sm_scale, kw):
    """Two chain lengths; the slope removes the fixed per-invocation
    dispatch/transfer overhead."""
    args = (q, kv, kv_lens, tables, cu, num_seqs, sm_scale, kw)
    t16 = _time_chain(*args, 16)
    t64 = _time_chain(*args, 64)
    return (t64 - t16) / 48 * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=192)
    ap.add_argument("--blocks", type=int, default=512)
    ap.add_argument("--max-model-len", type=int, default=512)
    args = ap.parse_args()

    from dynamo_tpu.device import enable_compile_cache, require_accelerator

    enable_compile_cache()
    print(f"# device={require_accelerator('tools/sweep_attention.py')}")

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
        rng.permutation(args.blocks)[: B * per].reshape(B, per)
        if args.blocks >= B * per
        else np.stack([rng.permutation(args.blocks)[:per] for _ in range(B)]),
        jnp.int32,
    )
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    num_seqs = jnp.asarray([B], jnp.int32)
    sm_scale = cfg.head_dim ** -0.5

    kv_bytes = B * (args.ctx + 1) * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    print(f"# B={B} ctx={args.ctx} pages/seq={per} one-layer kv read "
          f"{kv_bytes/1e6:.1f}MB -> roofline {kv_bytes/819e9*1e3:.4f} ms "
          f"(x{cfg.num_layers} layers)")

    configs = [("default", {})]
    for pages in (2, 4, 8, 16):
        if pages > per:
            continue
        for qb in (8, 16, 32, 64):
            if qb > max(B, 8):
                continue
            configs.append(
                (f"p{pages}_q{qb}",
                 dict(num_kv_pages_per_block=pages, num_queries_per_block=qb))
            )
    for name, kw in configs:
        try:
            t = time_config(q, kv, kv_lens, tables, cu, num_seqs, sm_scale, kw)
            print(f"{name:12s} {t:8.4f} ms/call  ({t*cfg.num_layers:7.3f} ms/model-step)")
        except Exception as e:  # noqa: BLE001
            print(f"{name:12s} FAILED: {type(e).__name__}: {str(e)[:100]}")


if __name__ == "__main__":
    main()
