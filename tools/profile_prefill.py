"""Prefill-wave profiler: where does TTFT go?

Times the engine's ragged prefill program (forward_tokens + fused
sampling) at bench shapes — bucket 2048, 16 sequences of 128 tokens —
and compares against the compute/bandwidth floor from the device's
published peaks. At 1B, a 2048-token wave is ~5.1 TFLOP (~26 ms at the
v5e bf16 peak) + one weight stream (~3 ms) — anything far above that is
overhead to find.

Usage: python -m tools.profile_prefill [--bucket 2048] [--seqs 16]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig, llama3_1b
from dynamo_tpu.engine.model import forward_tokens, init_cache, init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bucket", type=int, default=2048)
    ap.add_argument("--seqs", type=int, default=16)
    ap.add_argument("--blocks", type=int, default=768)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--no-attn", action="store_true")
    args = ap.parse_args()

    from dynamo_tpu.device import (
        device_peaks,
        enable_compile_cache,
        require_accelerator,
    )

    enable_compile_cache()
    device = require_accelerator("tools/profile_prefill.py")

    cfg = llama3_1b()
    T, S = args.bucket, args.seqs
    if T % S:
        raise SystemExit(f"--bucket {T} must be a multiple of --seqs {S}")
    per = T // S  # tokens per sequence
    eng = EngineConfig(
        num_kv_blocks=args.blocks, block_size=32, max_num_seqs=args.seqs,
        max_model_len=max(512, per), prefill_buckets=(args.bucket,),
        decode_buckets=(args.seqs,),
    )
    if per % eng.block_size:
        # The page assignment below tiles whole pages per sequence.
        raise SystemExit(
            f"tokens/seq {per} must be a multiple of block_size {eng.block_size}"
        )
    bs = eng.block_size
    rng = np.random.RandomState(0)

    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, T), jnp.int32)
    positions = jnp.asarray(np.tile(np.arange(per, dtype=np.int32), S))
    pages_per_seq = -(-per // bs)
    ids = rng.permutation(args.blocks)[: S * pages_per_seq].reshape(S, -1)
    write_pages = jnp.asarray(
        np.repeat(ids, bs, axis=1).reshape(-1)[:T].astype(np.int32)
    )
    write_offs = jnp.asarray(
        np.tile(np.arange(per, dtype=np.int32) % bs, S)
    )
    kv_lens = jnp.full((S,), per, jnp.int32)
    tables = np.full((S, eng.max_blocks_per_seq), eng.garbage_block, np.int32)
    tables[:, :pages_per_seq] = ids
    tables = jnp.asarray(tables)
    cu = jnp.asarray(np.arange(S + 1, dtype=np.int32) * per)
    num_seqs = jnp.asarray([S], jnp.int32)
    last_rows = jnp.asarray(
        (np.arange(S, dtype=np.int32) + 1) * per - 1
    )

    params = init_params(jax.random.PRNGKey(0), cfg)

    if args.no_attn:
        # Attribution variant: identity attention (same matmuls/scatter).
        import dynamo_tpu.ops.ragged_attention as ra

        ra.ragged_paged_attention = (
            lambda q, *a, **kw: q
        )
        import dynamo_tpu.engine.model as _m

        _m.ragged_paged_attention = ra.ragged_paged_attention

    def wave(p, c, tok):
        logits, c = forward_tokens(
            p, c, tok, positions, write_pages, write_offs, kv_lens,
            tables, cu, num_seqs, last_rows, cfg, eng, None,
        )
        # Sample on device like the engine's fused program: the host
        # fetch is [S] ints, not [S, V] logits.
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

    fwd = jax.jit(wave, donate_argnums=(1,))

    cache = init_cache(cfg, eng)
    toks, cache = fwd(params, cache, tokens)
    jax.block_until_ready(toks)  # compile + sync

    times = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        toks, cache = fwd(params, cache, tokens)
        jax.block_until_ready(toks)
        times.append(time.perf_counter() - t0)
    times.sort()

    # Matmul FLOPs only: the embedding table is a gather (0 FLOPs) and
    # the lm head runs over the S last rows, not all T.
    h, i = cfg.hidden_size, cfg.intermediate_size
    per_layer = h * (cfg.q_size + 2 * cfg.kv_size) + cfg.q_size * h + 3 * h * i
    flops = 2 * T * cfg.num_layers * per_layer + 2 * S * h * cfg.vocab_size
    print(f"# device={device} bucket={T} seqs={S} per={per}: "
          f"flops {flops/1e12:.2f} TF")
    if device["platform"] == "tpu":
        peaks = device_peaks(device["kind"])
        print(
            f"# floors from published peaks: "
            f"{flops / (peaks.bf16_tflops * 1e12) * 1e3:.1f} ms MXU, "
            f"{cfg.param_bytes() / (peaks.hbm_gbps * 1e9) * 1e3:.1f} ms "
            "weight stream"
        )
    print(
        f"prefill wave: best {times[0]*1e3:.1f} ms, "
        f"median {times[len(times)//2]*1e3:.1f} ms "
        f"({T/times[0]:.0f} tok/s best)"
    )


if __name__ == "__main__":
    main()
