"""A prefill wave's attention call alone on the chip: the library kernel over
one 2,048-query chunk of Laguna's full (48 / 8 heads) and window (72 / 8
heads, window 512) layers at the cell's tables, WHOLE against IN PIECES
(``ops/ragged_attention.split_query_chunks``) over KV blocks of 8 / 16 / 32
pages. ms a call on the host's clock around ten calls, and the largest
difference from the first variant's output.

    chiprun -- python -m tools.attn_wave_bench [--shapes full,window]
        [--chunks 0,128,256,512] [--pages 8,16,32] [--before 4096]

The kernel walks every KV block up to ``kv_lens`` for every block of
queries (causality and the window are masks), and a pass of its body costs
about the same at 256 keys as at 1,024: what this sweep shows, and what
``ops/ragged_attention.py`` chose its wide-heads grid and
``ModelConfig.wave_query_chunk`` by (PERF.md section 5, PR 39). Refuses the
CPU: a host-clock time of the reference path says nothing about the kernel.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import ragged_attention as ra

PAGE, HEAD_DIM, KV_HEADS, LANES, ROWS = 32, 128, 8, 48, 2048
# shape -> (query heads, table width, pages in the pool, window)
SHAPES = {"full": (48, 338, 16385, None), "window": (72, 82, 1025, 512)}


def build(shape: str, before: int, seed: int):
    """One sequence's chunk of ``ROWS`` queries behind ``before`` tokens of
    context, as the engine states it: a window call's table starts at the
    page of the oldest key its first query sees."""
    heads, width, n_pages, window = SHAPES[shape]
    first = max(0, before - (window - 1)) // PAGE if window else 0
    kv_len = before - first * PAGE + ROWS
    need = -(-kv_len // PAGE)
    assert need <= width, f"{shape}: {need} pages for a table of {width}"
    tables = np.zeros((LANES, width), np.int32)
    tables[0, :need] = np.random.default_rng(seed).permutation(n_pages - 1)[:need]
    kv_lens, cu = np.zeros(LANES, np.int32), np.full(LANES + 1, ROWS, np.int32)
    kv_lens[0], cu[0] = kv_len, 0
    keys = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(keys[0], (ROWS, heads, HEAD_DIM), jnp.bfloat16),
            jax.random.normal(keys[1], (n_pages, PAGE, 2 * KV_HEADS, HEAD_DIM), jnp.bfloat16),
            jnp.asarray(kv_lens), jnp.asarray(tables), jnp.asarray(cu),
            jnp.asarray([1], jnp.int32))


def call(window, chunk, pages, q, kv, kv_lens, tables, cu, ns):
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import ragged_paged_attention

    if chunk:
        kv_lens, tables, cu, ns = ra.split_query_chunks(
            ROWS, kv_lens, tables, cu, ns, chunk=chunk, page_size=PAGE, window=window)
    return ragged_paged_attention(
        q, kv, kv_lens, tables, cu, ns, sm_scale=HEAD_DIM ** -0.5, sliding_window=window,
        num_kv_pages_per_block=min(pages, tables.shape[1]),
        num_queries_per_block=ra._WIDE_HEADS_QUERIES_PER_BLOCK)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="full,window")
    ap.add_argument("--chunks", default="0,128,256,512", help="0: the call whole")
    ap.add_argument("--pages", default="8,16,32")
    ap.add_argument("--before", type=int, default=4096, help="tokens of context before the chunk")
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("attn_wave_bench times the TPU kernel: run it through chiprun")
    for shape in args.shapes.split(","):
        inputs, first = build(shape, args.before, args.seed), None
        for chunk in map(int, args.chunks.split(",")):
            for pages in map(int, args.pages.split(",")):
                line = {"shape": shape, "before": args.before, "chunk": chunk, "pages": pages}
                fn = jax.jit(functools.partial(call, SHAPES[shape][3], chunk, pages))
                try:
                    out = jax.block_until_ready(fn(*inputs))
                except Exception as e:   # noqa: BLE001 - Mosaic's refusal (VMEM) is a result
                    print(json.dumps({**line, "error": str(e)[:200]}), flush=True)
                    continue
                times = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(10):
                        last = fn(*inputs)
                    jax.block_until_ready(last)
                    times.append((time.perf_counter() - t0) / 10)
                first = out if first is None else first
                diff = jnp.max(jnp.abs(out.astype(jnp.float32) - first.astype(jnp.float32)))
                print(json.dumps({**line, "ms": round(min(times) * 1e3, 3),
                                  "max_diff_vs_first": float(diff)}), flush=True)


if __name__ == "__main__":
    main()
