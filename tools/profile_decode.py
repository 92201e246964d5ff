"""Decode-step ablation profiler: where does the step time go?

Builds the same fused decode+sample chain EngineCore compiles (bench.py
shapes: llama3-1b, B=32, ctx ~192) and times variants with individual
stages disabled. The deltas attribute step time to attention kernel,
cache scatter, lm-head/logits, sampler, and the matmul weight stream.
The last rows — a chain of 1 against a chain of ``--steps`` — price the
fixed per-dispatch cost on the machine it runs on (PERF.md "Bring-up on
v5e").

Usage: python -m tools.profile_decode [--batch 32] [--ctx 192]
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig, llama3_1b
from dynamo_tpu.engine.model import (
    _dot,
    _interleave_kv,
    _logits,
    init_cache,
    init_params,
    rms_norm,
    rope,
    split_gu,
    split_qkv,
)
from dynamo_tpu.ops.ragged_attention import ragged_paged_attention


def build_forward(cfg, engine, *, attn=True, scatter=True, head=True,
                  dense_attn=False, stacked_cache=False):
    """One decode step over B lanes with stages toggleable. ``dense_attn``
    swaps the Pallas kernel for the pure-XLA gather/softmax reference —
    more raw bytes, but it fuses with the surrounding layer instead of
    paying the custom-call boundary per layer. ``stacked_cache`` times the
    pre-r5 [L, ...] single-array layout: its per-layer slices forced XLA
    to materialize a copy at each Pallas call (measured +1.4 ms/step at
    B=32 — the reason model.init_cache is a per-layer tuple now)."""

    def fwd(params, cache, tokens, block_tables, positions, active):
        B = tokens.shape[0]
        bs = engine.block_size
        sm_scale = cfg.head_dim ** -0.5
        page = jnp.take_along_axis(block_tables, (positions // bs)[:, None], axis=1)[:, 0]
        write_pages = jnp.where(active, page, engine.garbage_block)
        write_offs = positions % bs
        kv_lens = jnp.where(active, positions + 1, 1).astype(jnp.int32)
        cu = jnp.arange(B + 1, dtype=jnp.int32)
        num_seqs = jnp.array([B], jnp.int32)

        x = params["embed"][tokens]
        lp_all = params["layers"]
        for l in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[l], lp_all)
            y = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            qkv = _dot(y, lp["wqkv"]).astype(x.dtype)
            q, k, v = split_qkv(qkv, cfg)
            T = q.shape[0]
            q = rope(q.reshape(T, cfg.num_heads, cfg.head_dim), positions, cfg.rope_theta)
            k = rope(k.reshape(T, cfg.num_kv_heads, cfg.head_dim), positions, cfg.rope_theta)
            kvn = _interleave_kv(k.reshape(T, cfg.kv_size), v, cfg)
            if stacked_cache:
                if scatter:
                    cache = cache.at[l, write_pages, write_offs].set(kvn)
                cache_l = cache[l]
            else:
                cache_l = cache[l]
                if scatter:
                    cache_l = cache_l.at[write_pages, write_offs].set(kvn)
                    cache = cache[:l] + (cache_l,) + cache[l + 1:]
            if attn and dense_attn:
                from dynamo_tpu.ops.ragged_attention import (
                    ragged_paged_attention_ref,
                )

                a = ragged_paged_attention_ref(
                    q, cache_l, kv_lens, block_tables, cu, num_seqs,
                    sm_scale=sm_scale,
                )
            elif attn:
                a = ragged_paged_attention(
                    q, cache_l, kv_lens, block_tables, cu, num_seqs,
                    sm_scale=sm_scale,
                )
            else:
                a = q
            a = a.reshape(T, cfg.q_size)
            x = x + _dot(a, lp["wo"]).astype(x.dtype)
            y = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            gu = _dot(y, lp["wgu"])
            g, u = split_gu(gu)
            act = (jax.nn.silu(g) * u).astype(x.dtype)
            x = x + _dot(act, lp["w_down"]).astype(x.dtype)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        if head:
            logits = _logits(x, params, cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt = tokens
        return nxt, cache

    return fwd


def build_chain(cfg, engine, n_steps, unroll=False, **flags):
    fwd = build_forward(cfg, engine, **flags)

    def chain(params, cache, tokens, block_tables, positions, active):
        step = jnp.asarray(active, jnp.int32)

        def body(carry, i):
            toks, cache = carry
            nxt, cache = fwd(params, cache, toks, block_tables, positions + i * step, active)
            return (nxt, cache), nxt

        if unroll:
            toks, outs = tokens, []
            for i in range(n_steps):
                (toks, cache), nxt = body((toks, cache), jnp.int32(i))
                outs.append(nxt)
            return jnp.stack(outs), cache
        (_, cache), sampled = jax.lax.scan(body, (tokens, cache), jnp.arange(n_steps))
        return sampled, cache

    return jax.jit(chain, donate_argnums=(1,))


def timeit(fn, args, cache, n=5):
    # compile + warm, then best of n with the device drained inside the
    # timed region.
    out, cache = fn(*args[:1], cache, *args[2:])
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out, cache = fn(*args[:1], cache, *args[2:])
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best, cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=192)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--blocks", type=int, default=512)
    ap.add_argument("--only", default=None,
                    help="run a single variant; 'full' also prices the "
                         "per-dispatch cost (chain of 1 vs --steps)")
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--max-model-len", type=int, default=512)
    ap.add_argument("--int8", action="store_true", help="int8 weight-only quant")
    args = ap.parse_args()

    from dynamo_tpu.device import (
        device_peaks,
        enable_compile_cache,
        require_accelerator,
    )

    enable_compile_cache()
    device = require_accelerator("tools/profile_decode.py")

    cfg = llama3_1b()
    engine = EngineConfig(
        num_kv_blocks=args.blocks, block_size=args.block_size,
        max_num_seqs=args.batch, max_model_len=args.max_model_len,
        decode_buckets=(args.batch,), decode_chain=args.steps,
    )
    B, n_steps = args.batch, args.steps
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.int8:
        from dynamo_tpu.engine.model import quantize_params

        params = quantize_params(params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size, B), jnp.int32)
    positions = jnp.full((B,), args.ctx, jnp.int32)
    bs = engine.block_size
    blocks_per_seq = engine.max_blocks_per_seq
    tables = np.full((B, blocks_per_seq), engine.garbage_block, np.int32)
    need = (args.ctx + n_steps) // bs + 1
    ids = rng.permutation(args.blocks)[: B * need].reshape(B, need)
    tables[:, :need] = ids
    tables = jnp.asarray(tables)
    active = jnp.ones((B,), bool)

    pbytes = cfg.param_bytes()
    kv_tok = cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 2 * 2
    print(f"# B={B} ctx={args.ctx} steps={n_steps} params={pbytes/1e9:.2f}GB "
          f"kv/tok={kv_tok} device={device}")

    variants = [
        ("full", dict()),
        ("full_stacked_cache", dict(stacked_cache=True)),
        ("full_unrolled", dict(unroll=True)),
        ("full_dense_attn", dict(dense_attn=True)),
        ("no_attn", dict(attn=False)),
        ("no_scatter", dict(scatter=False)),
        ("no_head", dict(head=False)),
        ("no_attn_no_scatter", dict(attn=False, scatter=False)),
        # NOTE: variants with head=False AND scatter=False have a loop-
        # invariant scan body at long chains — XLA hoists it and the
        # number measures nothing. Trust matmuls_only at --steps 32 only.
        ("matmuls_only", dict(attn=False, scatter=False, head=False)),
    ]
    if args.only:
        variants = [v for v in variants if v[0] == args.only]
    results = {}
    for name, flags in variants:
        cache = init_cache(cfg, engine)  # per-layer tuple (engine layout)
        if flags.get("stacked_cache"):
            from dynamo_tpu.engine.model import init_cache_stacked

            cache = init_cache_stacked(cfg, engine)
        fn = build_chain(cfg, engine, n_steps, **flags)
        t, cache = timeit(fn, (params, cache, tokens, tables, positions, active), cache)
        del cache
        per_step = t / n_steps * 1e3
        results[name] = per_step
        print(f"{name:22s} {t*1e3:8.2f} ms/chain   {per_step:7.3f} ms/step")

    if args.only not in (None, "full"):
        return

    # single-step (chain of 1) dispatch overhead
    cache = init_cache(cfg, engine)
    fn1 = build_chain(cfg, engine, 1)
    t1, cache = timeit(fn1, (params, cache, tokens, tables, positions, active), cache)
    del cache
    print(f"{'single_step_chain1':22s} {t1*1e3:8.2f} ms/chain   {t1*1e3:7.3f} ms/step")
    tn = results["full"] * n_steps / 1e3
    marginal = (tn - t1) / (n_steps - 1) if n_steps > 1 else t1
    print(f"# chain(1) {t1*1e3:.2f} ms vs chain({n_steps}) {tn*1e3:.2f} ms: "
          f"marginal {marginal*1e3:.3f} ms/step, fixed per dispatch "
          f"{(t1 - marginal)*1e3:.3f} ms")
    if args.only:
        return

    full = results["full"]
    print("\n# attributed ms/step:")
    print(f"  attention kernel : {full - results['no_attn']:.3f}")
    print(f"  cache scatter    : {full - results['no_scatter']:.3f}")
    print(f"  lm head + argmax : {full - results['no_head']:.3f}")
    print(f"  matmul stream    : {results['matmuls_only']:.3f}")
    if device["platform"] == "tpu":
        hbm = device_peaks(device["kind"]).hbm_gbps
        floor = (pbytes + B * (args.ctx + n_steps / 2) * kv_tok) / (hbm * 1e9) * 1e3
        print(f"  roofline floor   : {floor:.3f} ({hbm:.0f} GB/s published)")


if __name__ == "__main__":
    main()
