"""The wide-key WAVE attention alone (``ops/gqa_attention.py``), at the wave
shapes of the cell ``mimo-v25-ep16-longctx``: one sequence's chunk of 2,048
queries behind 0 / 4,096 / 10,240 cached tokens, the chunked ``jax.numpy``
walk (``gqa_ragged_jnp``) against the Pallas kernel (``gqa_ragged_pallas``),
timed on the host's clock around a jitted loop of dependent calls.

Two shapes: ``full`` (64 query heads on 4 KV heads, pages of ``[320, 128]``,
a table of 466 pages, 8 rows as a wave's) and ``window`` (64 on 8, pages of
``[640, 128]``, the 70-column window table shifted to the chunk's oldest
visible key, ``kv_lens`` counted from that page, window 128, a sink a head).
For each: ms a call, the call's share of the chip's bf16 peak (the FLOPs of
the keys each query sees at 192 + 128 a key a head, no padding counted), and
the largest difference from the walk on the same arguments. ``--sweep``
varies the kernel's grid one constant at a time around the module's: query
rows an item x pages a KV block x blocks in the ring x rows a product.

Refuses to run without a TPU: a time from the CPU says nothing here.

Usage (through the chip tool, from the repo root):
    python -m tools.gqa_wave_bench [--before 0,4096,10240] [--shapes full,window]
        [--sweep] [--items 64,128,256] [--blocks 8,12,16,32] [--rings 2,3]
        [--products 256,512,1024,2048] [--grids '[{"pages_per_block": 24, "product_rows": 512}]']
        [--calls 4]
Writes ``chiprun_out/gqa_wave_bench/table.json`` beside the table.
"""

from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path

import numpy as np

DK, DV, PS, WINDOW, ROWS, HEADS, SEQS = 192, 128, 32, 128, 2048, 64, 8
# n_kv, table width, pages in the layer's array
SHAPES = {"full": (4, 466, 15361), "window": (8, 70, 273)}


def make_case(shape: str, before: int, seed: int):
    """One sequence's chunk of ``ROWS`` queries behind ``before`` tokens, as
    the engine states it, and the FLOPs the call needs."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.gqa_attention import gqa_page_shape

    n_kv, width, n_pages = SHAPES[shape]
    rng = np.random.RandomState(seed)
    first = max(0, before - (WINDOW - 1)) // PS if shape == "window" else 0
    kv_len = before - first * PS + ROWS
    need = -(-kv_len // PS)
    assert need <= width, f"{shape}: {need} pages for a table of {width}"
    tables = np.zeros((SEQS, width), np.int32)
    tables[0, :need] = rng.permutation(n_pages - 1)[:need]
    kv_lens, cu = np.zeros(SEQS, np.int32), np.full(SEQS + 1, ROWS, np.int32)
    kv_lens[0], cu[0] = kv_len, 0
    pos = before + np.arange(ROWS)
    seen = np.minimum(pos + 1, WINDOW) if shape == "window" else pos + 1
    flops = 2 * int(seen.sum()) * HEADS * (DK + DV)
    pages = jnp.asarray(rng.randn(n_pages, *gqa_page_shape(PS, n_kv, DK, DV)), jnp.bfloat16)
    q = jnp.asarray(rng.randn(ROWS, HEADS, DK), jnp.bfloat16)
    sinks = jnp.asarray(4.35 + 0.5 * rng.randn(HEADS), jnp.float32) if shape == "window" else None
    args = (q, pages, jnp.asarray(kv_lens), jnp.asarray(tables), jnp.asarray(cu),
            jnp.asarray([1], jnp.int32), sinks)
    return args, n_kv, flops


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--before", default="0,4096,10240")
    ap.add_argument("--shapes", default="full,window")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--items", default="64,128,256")
    ap.add_argument("--blocks", default="8,12,16,32")
    ap.add_argument("--rings", default="2,3")
    ap.add_argument("--products", default="256,512,1024,2048")
    ap.add_argument("--grids", default="[]", help="a JSON list of grids to time besides")
    ap.add_argument("--calls", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise SystemExit("gqa_wave_bench times a TPU kernel; this backend is "
                         f"{jax.default_backend()}")
    from dynamo_tpu.device import device_info, device_peaks
    from dynamo_tpu.ops import gqa_attention as ga

    peak_flops = device_peaks(device_info()["kind"]).bf16_tflops * 1e12
    ints = lambda text: [int(n) for n in text.split(",")]   # noqa: E731
    grids = [{}]
    if args.sweep:   # one constant at a time around the module's
        grids += [{"queries_per_item": n} for n in ints(args.items)]
        grids += [{"pages_per_block": n} for n in ints(args.blocks)]
        grids += [{"blocks_in_ring": n} for n in ints(args.rings)]
        grids += [{"product_rows": n} for n in ints(args.products)]
    grids += json.loads(args.grids)
    rows = []

    def timed(call, case):
        """ms a call of ``args.calls`` dependent calls, best of three, and
        the last call's output."""
        @jax.jit
        def loop(q, pages, lens, *rest):
            def body(_, carry):   # the next call's lengths wait for this call's output
                lens, out = carry
                out = call(q, pages, lens, *rest)
                return lens + jnp.isnan(out[0, 0, 0]).astype(lens.dtype), out
            return jax.lax.fori_loop(
                0, args.calls, body, (lens, jnp.zeros((ROWS, HEADS, DV), q.dtype)))[1]

        out = jax.block_until_ready(loop(*case))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(loop(*case))
            best = min(best, time.perf_counter() - t0)
        return best / args.calls * 1e3, out

    for shape in args.shapes.split(","):
        for before in ints(args.before):
            case, n_kv, flops = make_case(shape, before, seed=before + 1)
            kw = dict(n_kv=n_kv, sm_scale=DK ** -0.5,
                      window=WINDOW if shape == "window" else None)
            walk = lambda q, pages, lens, tables, cu, ns, sinks: ga.gqa_ragged_jnp(  # noqa: E731
                q, pages, lens, tables, cu, ns, sinks=sinks, **kw)
            walk_ms, want = timed(walk, case)
            rows.append({"shape": shape, "before": before, "impl": "jnp",
                         "ms_per_call": round(walk_ms, 3),
                         "mxu_pct": round(100 * flops / peak_flops / (walk_ms * 1e-3), 1)})
            print(rows[-1], flush=True)
            # what a call costs before its first row: the layout passes around
            # the kernel and an empty grid (no sequence is live)
            idle_ms, _ = timed(functools.partial(ga.gqa_ragged_pallas, **kw),
                               (*case[:5], jnp.asarray([0], jnp.int32), case[6]))
            rows.append({"shape": shape, "before": before, "impl": "pallas, no live sequence",
                         "ms_per_call": round(idle_ms, 3)})
            print(rows[-1], flush=True)
            for grid in grids:
                try:
                    ms, out = timed(functools.partial(ga.gqa_ragged_pallas, **kw, **grid), case)
                except Exception as e:  # noqa: BLE001 — a grid Mosaic refuses is a row
                    rows.append({"shape": shape, "before": before, "impl": "pallas", **grid,
                                 "error": str(e)[:200]})
                    print(rows[-1], flush=True)
                    continue
                rows.append({
                    "shape": shape, "before": before, "impl": "pallas", **grid,
                    "ms_per_call": round(ms, 3),
                    "mxu_pct": round(100 * flops / peak_flops / (ms * 1e-3), 1),
                    "walk_over_kernel": round(walk_ms / ms, 2),
                    "max_abs_diff": float(jnp.max(jnp.abs(
                        out.astype(jnp.float32) - want.astype(jnp.float32)))),
                })
                print(rows[-1], flush=True)
    out_dir = Path("chiprun_out/gqa_wave_bench")
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "table.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
