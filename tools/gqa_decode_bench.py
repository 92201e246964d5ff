"""The wide-key decode attention kernel alone (``ops/gqa_attention.py``), at
the decode shapes of the cell ``mimo-v25-ep16-longctx``, timed on the host's
clock around a jitted loop of dependent calls.

Two shapes: ``full`` (64 query heads on 4 KV heads, pages of ``[320, 128]``,
a table of 466 pages, contexts uniform 6,144-14,848) and ``window`` (64 on
8, pages of ``[640, 128]``, the 70-column window table shifted to each
lane's oldest visible key, ``kv_lens`` counted from that page, window 128,
a sink a head). For each, over ``--blocks`` pages a KV block x ``--rings``
blocks in the ring: microseconds a call, the share of the HBM roofline as
the benchmark's ``attn_decode_roofline.mimo`` counts the bytes (every cached
token's 2,560 B on a full layer, the newest 128 tokens' 5,120 B each on a
window layer; block edges not counted), and the largest difference from the
chunked ``jax.numpy`` walk on the same arguments.

Refuses to run without a TPU: a time from the CPU says nothing here.

Usage (through the chip tool, from the repo root):
    python -m tools.gqa_decode_bench [--lanes 32,16] [--blocks 8,16,32]
                                     [--rings 2,3,4] [--calls 16]
Writes ``chiprun_out/gqa_decode_bench/table.json`` beside the table.
"""

from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path

import numpy as np

DK, DV, PS, WINDOW = 192, 128, 32, 128
# n_kv, table width, pages in the layer's array
SHAPES = {"full": (4, 466, 15361), "window": (8, 70, 273)}


def make_case(shape: str, lanes: int, seed: int):
    import jax.numpy as jnp

    from dynamo_tpu.ops.gqa_attention import gqa_page_shape

    n_kv, width, n_pages = SHAPES[shape]
    rng = np.random.RandomState(seed)
    ctx = (6144 + (rng.permutation(lanes) + 0.5) / lanes * (14848 - 6144)).astype(np.int32)
    tables = np.zeros((lanes, width), np.int32)
    if shape == "full":
        lens, need_bytes = ctx, int(ctx.sum()) * n_kv * (DK + DV) * 2
        perm, used = rng.permutation(n_pages - 1), 0
        for s, n in enumerate(lens):
            need = -(-int(n) // PS)
            tables[s, :need] = perm[used:used + need]
            used += need
    else:   # the table from the page of the oldest visible key, lens from its first token
        first = np.maximum(ctx - WINDOW, 0) // PS
        lens = ctx - PS * first
        need_bytes = lanes * WINDOW * n_kv * (DK + DV) * 2
        for s in range(lanes):
            tables[s, :6] = rng.permutation(n_pages - 1)[:6]
    pages = jnp.asarray(rng.randn(n_pages, *gqa_page_shape(PS, n_kv, DK, DV)), jnp.bfloat16)
    q = jnp.asarray(rng.randn(lanes, 64, DK), jnp.bfloat16)
    sinks = jnp.asarray(4.35 + 0.5 * rng.randn(64), jnp.float32) if shape == "window" else None
    return (q, pages, jnp.asarray(lens, jnp.int32), jnp.asarray(tables), sinks), n_kv, need_bytes


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--lanes", default="32,16")
    ap.add_argument("--blocks", default="8,16,32")
    ap.add_argument("--rings", default="2,3,4")
    ap.add_argument("--calls", type=int, default=16)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise SystemExit("gqa_decode_bench times a TPU kernel; this backend is "
                         f"{jax.default_backend()}")
    from dynamo_tpu.device import device_info, device_peaks
    from dynamo_tpu.ops import gqa_attention as ga

    hbm_bytes_per_s = device_peaks(device_info()["kind"]).hbm_gbps * 1e9
    rows = []
    for shape in SHAPES:
        for lanes in (int(n) for n in args.lanes.split(",")):
            case, n_kv, need = make_case(shape, lanes, seed=lanes)
            q, pages, lens, tables, sinks = case
            kw = dict(n_kv=n_kv, sm_scale=DK ** -0.5,
                      window=WINDOW if shape == "window" else None)
            want = jax.jit(functools.partial(ga.gqa_ragged_jnp, **kw, sinks=sinks))(
                q, pages, lens, tables, None, jnp.asarray([lanes], jnp.int32))
            for blocks in [None, *(int(n) for n in args.blocks.split(","))]:
                for ring in ([3] if blocks is None else [int(n) for n in args.rings.split(",")]):
                    call = functools.partial(ga.gqa_decode_pallas, **kw, pages_per_block=blocks,
                                             blocks_in_ring=ring)

                    @jax.jit
                    def loop(q, pages, lens, tables, sinks, call=call):
                        def body(_, carry):
                            q, out = carry
                            out = call(q, pages, lens, tables, sinks)
                            return q + (0 * out[..., :1]).astype(q.dtype), out
                        return jax.lax.fori_loop(
                            0, args.calls, body, (q, jnp.zeros((lanes, 64, DV), q.dtype)))[1]

                    try:
                        out = jax.block_until_ready(loop(q, pages, lens, tables, sinks))
                        best = float("inf")
                        for _ in range(3):
                            t0 = time.perf_counter()
                            jax.block_until_ready(loop(q, pages, lens, tables, sinks))
                            best = min(best, time.perf_counter() - t0)
                    except Exception as e:  # noqa: BLE001 — a grid Mosaic refuses is a row
                        rows.append({"shape": shape, "lanes": lanes, "blocks": blocks,
                                     "ring": ring, "error": str(e)[:200]})
                        print(rows[-1], flush=True)
                        continue
                    us = best / args.calls * 1e6
                    rows.append({
                        "shape": shape, "lanes": lanes, "blocks": blocks or "auto", "ring": ring,
                        "us_per_call": round(us, 1),
                        "roofline_pct": round(100 * need / hbm_bytes_per_s / (us * 1e-6), 1),
                        "max_abs_diff": float(jnp.max(jnp.abs(
                            out.astype(jnp.float32) - want.astype(jnp.float32)))),
                    })
                    print(rows[-1], flush=True)
    out_dir = Path("chiprun_out/gqa_decode_bench")
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "table.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
