"""Decode attention alone, at the decode shapes the benchmark's cells
run, timed from a device trace.

For each shape (lanes, heads, page-table width, page array and context
distribution of a cell) it runs the library ragged kernel over a sweep
of its grid (``num_queries_per_block`` x ``num_kv_pages_per_block``),
the serving entry's decode path first (``serving``: whatever
``ops/ragged_attention.py`` does with ``cu_q_lens=None``), and prints
for each: kernel microseconds a call (mean device duration of the
kernel's events in a trace), the share of the HBM roofline (bytes of the
pages in use / peak bandwidth over that time: at the cells' 32-token
pages, what the benchmark's ``attn_decode_roofline`` computes), and the largest
difference from the serving path's output.

A LATENT shape (``axk1-128``, ``axk1-32``: A.X-K1's absorbed decode
call, 64 heads against one 576-wide key a token, pages of ``[144, 128]``)
runs ``ops/latent_attention.py`` instead: the serving entry first, then
the ``jnp`` path and the first-party kernel side by side over
``--latent-pages`` pages a KV block (and 2 / 3 / 4 blocks in its ring). Its
microseconds are the whole call's (every device op of the program, as
the benchmark's ``latent_attn_roofline.axk1`` counts the scope), the
kernel's own beside them.

A WINDOW shape (``laguna-window-48``: the decode call of Laguna's
sliding_attention layers, 72 query heads on 8 KV heads, window 512) hands
the kernel what the engine hands it: each lane's table starts at the page
that holds the oldest key its query sees, ``kv_lens`` counts from that
page's first token, and ``sliding_window`` masks the rest; the bytes are
those pages'. Its ``reference`` row is the ``jnp`` path on the same
arguments: no kernel to time, but the largest difference says whether the
kernel's groups of 9 heads are the reference's. ``laguna-full-48`` is the
same cell's full layers (48 heads on 8, the whole context).

A GROUPED shape (more query heads than KV heads, no window: ``7b``,
``1p5b-*``, ``lfm2-128``, ``nemotron-128``, ``laguna-full-48``) prints the
serving entry (the first-party kernel of ``ops/grouped_attention.py`` where
its ``fits`` holds), the library kernel at the decode grid, and the
first-party kernel over ``--gqa-pages`` pages a KV block x blocks in its
ring, each with its share of the HBM rate of the pages in use; a group of ONE
(``ouro``, ``olmo-48``) the same with ``ops/mha_attention.py`` and
``--mha-pages``.

Refuses to run without a TPU: a time from the CPU says nothing here.

Usage (through the chip tool, from the repo root):
    python -m tools.attn_decode_bench [--shapes 7b,1p5b-32,ouro,axk1-128]
                                      [--quick] [--page-size 32]
                                      [--latent-pages 8,16,32,64]
                                      [--mha-pages 4,8,16] [--gqa-pages 4,8,16,32]
``--page-size`` re-cuts a dense shape's cache and tables into pages of
another size (the worker's ``--block-size``), the same tokens in all.
Writes ``chiprun_out/attn_decode_bench/table.json`` beside the table.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import json
import math
import re
import tempfile
import time
from pathlib import Path

import numpy as np

# lanes, q heads, kv heads, page-table width, pages in one layer's array,
# contexts (kind, lo, hi): qwen7b-decode-batch; qwen1p5b-chat-steady at
# its three decode widths; ouro2p6b-reason-decode (4 planes of 169 pages).
SHAPES = {
    "7b": (32, 28, 4, 256, 3073, ("uniform", 384, 1536)),
    "1p5b-8": (8, 12, 2, 256, 11265, ("loguniform", 160, 2560)),
    "1p5b-16": (16, 12, 2, 256, 11265, ("loguniform", 160, 2560)),
    "1p5b-32": (32, 12, 2, 256, 11265, ("loguniform", 160, 2560)),
    "ouro": (8, 16, 16, 64, 676, ("uniform", 128, 608)),
    # olmo-hybrid-7b-reason-decode's full layers: 30 heads, a group of ONE
    # (as ouro's 16), in pages that keep 32 (PAGE_KV_HEADS)
    "olmo-48": (48, 30, 30, 128, 2049, ("uniform", 128, 2560)),
    # laguna-s21-longctx-agents: the full layers' pool, and the window
    # layers' (tables of EngineConfig.window_table_blocks columns)
    "laguna-full-48": (48, 48, 8, 338, 16385, ("uniform", 4096, 10752)),
    "laguna-window-48": (48, 72, 8, 82, 1025, ("uniform", 4096, 10752)),
    # lfm2-24b-hybrid-decode: 32 query heads of 64 against 8 KV heads, cached
    # in pairs (4 rows of 128 a token: paired_heads_attention); prompts
    # 256-768 and outputs to 2,048 (traffic/hybrid-decode.json)
    "lfm2-128": (128, 32, 4, 128, 16385, ("uniform", 256, 2816)),
    # nemotron3-nano-ep2-decode's two attention blocks: 32 heads on 2;
    # prompts 256-768 and outputs to 1,280 (traffic/ep-decode.json)
    "nemotron-128": (128, 32, 2, 128, 12289, ("uniform", 256, 2048)),
}
WINDOWS = {"laguna-window-48": 512}   # shape -> its layers' sliding window
PAGE_KV_HEADS = {"olmo-48": 32}       # shape -> KV heads its pages keep, the spare ones zero
MHA_PAGES = (4, 8, 16)   # pages a KV block of the group-1 kernel; main() may set it
GQA_PAGES = (4, 8, 16, 32)   # ... of the grouped kernel
RINGS: tuple[int, ...] = ()  # blocks in a first-party kernel's ring; main() may set it
# lanes, heads, r, dr, page-table width, pages in one layer's array,
# contexts: axk1-ep16-decode at its two decode widths.
LATENT_SHAPES = {
    "axk1-128": (128, 64, 512, 64, 128, 12289, ("uniform", 300, 2080)),
    "axk1-32": (32, 64, 512, 64, 128, 12289, ("uniform", 300, 2080)),
}
LATENT_PAGES = (8, 16, 32, 64)   # pages a KV block of the kernel; main() may set it
HEAD_DIM = 128
PAGE_SIZE = 32   # of SHAPES' widths and page counts; main() may re-cut it
CALLS = 8   # timed calls of each variant inside the trace


def geometry(shape: str):
    """``SHAPES[shape]`` with its table width and page count re-cut from
    32-token pages to ``PAGE_SIZE``."""
    lanes, n_q, n_kv, width, n_pages, contexts = SHAPES[shape]
    return (lanes, n_q, n_kv, width * 32 // PAGE_SIZE,
            (n_pages - 1) * 32 // PAGE_SIZE + 1, contexts)


def _contexts_and_tables(rng, lanes, width, n_pages, contexts, page_size):
    """(context of each lane, its block table over distinct shuffled pages,
    pages in use)."""
    kind, lo, hi = contexts
    u = (rng.permutation(lanes) + 0.5) / lanes      # mid-point quantiles
    if kind == "uniform":
        lens = lo + u * (hi - lo)
    else:
        lens = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    lens = lens.astype(np.int32)
    tables = np.zeros((lanes, width), np.int32)
    perm, used = rng.permutation(n_pages - 1), 0
    for s, n in enumerate(lens):
        need = -(-int(n) // page_size)
        tables[s, :need] = perm[used:used + need]
        used += need
    return lens, tables, used


def make_case(shape: str, seed: int):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    if shape in LATENT_SHAPES:
        from dynamo_tpu.ops.latent_attention import latent_page_shape

        lanes, heads, r, dr, width, n_pages, contexts = LATENT_SHAPES[shape]
        lens, tables, blocks = _contexts_and_tables(rng, lanes, width, n_pages, contexts, 32)
        rows, w = latent_page_shape(32, r, dr)
        args = (jnp.asarray(rng.randn(lanes, heads, r), jnp.bfloat16),
                jnp.asarray(rng.randn(lanes, heads, dr), jnp.bfloat16),
                jnp.asarray(rng.randn(n_pages, rows, w), jnp.bfloat16),
                jnp.asarray(lens), jnp.asarray(tables))
        return args, blocks * rows * w * 2, lens
    lanes, n_q, n_kv, width, n_pages, contexts = geometry(shape)
    if shape in WINDOWS:
        # what a lane holds of its context: from the page of the oldest key
        # its newest query sees (position context - window) to the end
        kind, lo, hi = contexts
        span = WINDOWS[shape] + PAGE_SIZE - 1
        contexts = (kind, min(lo, span), min(hi, span))
    lens, tables, blocks = _contexts_and_tables(rng, lanes, width, n_pages, contexts, PAGE_SIZE)
    q = jnp.asarray(rng.randn(lanes, n_q, HEAD_DIM), jnp.bfloat16)
    kv = rng.randn(n_pages, PAGE_SIZE, 2 * PAGE_KV_HEADS.get(shape, n_kv), HEAD_DIM)
    kv[:, :, 2 * n_kv:] = 0.0
    kv = jnp.asarray(kv, jnp.bfloat16)
    need_bytes = blocks * PAGE_SIZE * 2 * n_kv * HEAD_DIM * 2
    return (q, kv, jnp.asarray(lens), jnp.asarray(tables)), need_bytes, lens


def variants(shape: str, quick: bool):
    """[(tag, fn(q, kv, lens, tables))], the serving decode path first."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention as library,
    )

    from dynamo_tpu.ops import grouped_attention, mha_attention
    from dynamo_tpu.ops.ragged_attention import (
        decode_shape_grid,
        ragged_paged_attention,
        ragged_paged_attention_ref,
    )

    lanes, n_q, n_kv, width, _, _ = geometry(shape)
    serving_grid = decode_shape_grid(PAGE_SIZE, width)
    sm = HEAD_DIM ** -0.5
    window = WINDOWS.get(shape)
    spare = PAGE_KV_HEADS.get(shape, n_kv) - n_kv

    def serving(q, kv, lens, tables):
        return ragged_paged_attention(
            q, kv, lens, tables, None, jnp.asarray([lanes], jnp.int32),
            sm_scale=sm, window=window, num_kv_heads=n_kv)

    def reference(q, kv, lens, tables):
        return ragged_paged_attention_ref(
            q, kv, lens, tables, None, jnp.asarray([lanes], jnp.int32),
            sm_scale=sm, window=window)

    def lib(qb, pages):
        def fn(q, kv, lens, tables):
            q = jnp.pad(q, ((0, 0), (0, spare), (0, 0)))   # zero queries for the spare heads
            return library(
                q, kv, lens, tables, jnp.arange(lanes + 1, dtype=jnp.int32),
                jnp.asarray([lanes], jnp.int32), sm_scale=sm, sliding_window=window,
                num_queries_per_block=qb, num_kv_pages_per_block=pages)[:, :n_q]
        return fn

    def first_party(entry, pages, ring):
        def fn(q, kv, lens, tables):
            return entry(q, kv, lens, tables, jnp.asarray([lanes], jnp.int32), sm_scale=sm,
                         pages_per_block=pages, blocks_in_ring=ring)
        return fn

    if not window:
        # The serving entry is a first-party kernel at its constants where
        # its ``fits`` holds (a group of ONE: ops/mha_attention.py; a group:
        # ops/grouped_attention.py); beside it the library kernel at the
        # decode grid (what served before: the largest difference is between
        # the two kernels) and the first-party kernel over pages a KV block x
        # blocks in its ring.
        out = [("serving", serving), ("library q{}_p{}".format(*serving_grid), lib(*serving_grid))]
        page = jax.ShapeDtypeStruct((1, PAGE_SIZE, 2 * (n_kv + spare), HEAD_DIM), jnp.bfloat16)
        if n_q == n_kv:
            module, entry, tag, sweep = (mha_attention, mha_attention.mha_decode_pallas,
                                         "mha", MHA_PAGES)
            granule = mha_attention.granule_pages(PAGE_SIZE)
        else:
            module, entry, tag, sweep = (grouped_attention, grouped_attention.grouped_decode_pallas,
                                         "gqa", GQA_PAGES)
            granule = 4 * grouped_attention.quarter_pages(PAGE_SIZE, n_kv)
        served = (module.block_pages(page, width), module._KERNEL_BLOCKS_IN_RING)
        for pages in sweep:
            for ring in RINGS or ((module._KERNEL_BLOCKS_IN_RING,) if quick else (2, 3, 4)):
                # (the serving entry's pair left out: one program compiles to
                # one executable under the first name)
                if pages <= width and pages % granule == 0 and (pages, ring) != served:
                    out.append((f"{tag}_p{pages}_r{ring}", first_party(entry, pages, ring)))
        if quick or n_q == n_kv:
            return out
    else:
        # The serving path IS one of the grids: the same program compiles to
        # one executable under the first name, so the sweep leaves that one out.
        out = [("serving q{}_p{}".format(*serving_grid), serving)]
        out.append(("reference", reference))   # a table narrow enough for the jnp path's gather

    # the library kernel's grids
    qbs = (1, 8) if quick else (1, 2, 4, 8, 16, 32)
    # KV blocks of 128 ... 1024 tokens: 4 ... 32 pages of 32.
    tokens = (256, 512) if quick else (128, 256, 384, 512, 768, 1024)
    pgs = sorted({t // PAGE_SIZE for t in tokens} - {0})
    for qb in qbs:
        for pages in pgs:
            if (qb <= max(lanes, 8) and pages <= width
                    and (qb, pages) != serving_grid):
                out.append((f"lib_q{qb}_p{pages}", lib(qb, pages)))
    return out


def latent_variants(shape: str, quick: bool):
    """[(tag, fn(q_lat, q_rope, pages, lens, tables))]: the serving entry
    (the kernel at the module's constants, on a TPU), the ``jnp`` path, the
    kernel over pages a block x blocks in the ring (the serving entry's pair
    left out: one program compiles to one executable under the first name)."""
    from dynamo_tpu.ops import latent_attention as la

    sm = (128 + LATENT_SHAPES[shape][3]) ** -0.5      # qk_nope_head_dim + qk_rope_head_dim
    out = [("serving", functools.partial(la.latent_decode_attention, sm_scale=sm)),
           ("jnp", functools.partial(la.latent_decode_jnp, sm_scale=sm))]
    for pages in LATENT_PAGES:
        for ring in (3,) if quick else (2, 3, 4):
            if (pages, ring) != (la._KERNEL_PAGES_PER_BLOCK, la._KERNEL_BLOCKS_IN_RING):
                out.append((f"kernel_p{pages}_r{ring}", functools.partial(
                    la.latent_decode_pallas, sm_scale=sm, pages_per_block=pages,
                    blocks_in_ring=ring)))
    return out


KERNELS = ("ragged_paged_attention", "latent_decode_attention_kernel")


def kernel_times(trace_dir: str) -> tuple[dict[str, list[float]], dict[str, list[float]]]:
    """({program: [device ns of each attention-kernel event]}, {program:
    [device ns of each execution of the whole program]}). A device op
    carries no program name on this installation; the "XLA Modules" line
    does ("jit_<name>(<program id>)"), and an op belongs to the module
    whose interval holds its start."""
    from jax.profiler import ProfileData

    path = max(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    out: dict[str, list[float]] = {}
    whole: dict[str, list[float]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, re.sub(r"\(.*\)$", "", e.name))
            for e in lines["XLA Modules"].events)
        starts = [m[0] for m in modules]
        for a, b, name in modules:
            whole.setdefault(name, []).append(b - a)
        for e in lines["XLA Ops"].events:
            # The event's name is the op's HLO text, "%<name> = ...".
            if not any(k in e.name[:120].split(" = ")[0] for k in KERNELS):
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < modules[i][1]:
                out.setdefault(modules[i][2], []).append(e.duration_ns)
    return out, whole


def bench_shape(shape: str, seed: int, quick: bool, hbm_bytes_per_s: float):
    import jax

    args, need_bytes, lens = make_case(shape, seed)
    floor_us = 1e6 * need_bytes / hbm_bytes_per_s
    latent = shape in LATENT_SHAPES
    if latent:
        lanes, heads, r, dr, width, n_pages, _ = LATENT_SHAPES[shape]
        said = (f"lanes {lanes}, heads {heads} against one key of {r} + {dr}, "
                f"page size 32, table width {width}, pages {n_pages}")
    else:
        geo = geometry(shape)
        said = (f"lanes {geo[0]}, heads {geo[1]}/{geo[2]}, page size "
                f"{PAGE_SIZE}, table width {geo[3]}, pages {geo[4]}")
    print(f"## {shape}: {said}, contexts "
          f"{int(lens.min())}-{int(lens.max())} "
          f"(mean {lens.mean():.0f}); blocks in use {need_bytes / 1e6:.2f} MB "
          f"= {floor_us:.1f} us at the HBM's peak", flush=True)
    ready, rows, base = [], [], None
    for tag, fn in (latent_variants if latent else variants)(shape, quick):
        def program(*a, _fn=fn):
            return _fn(*a)
        program.__name__ = f"{shape}_{tag}".replace("-", "_").replace(" ", "_")
        jitted = jax.jit(program)
        t0 = time.perf_counter()
        try:
            out = np.asarray(jax.block_until_ready(jitted(*args)), np.float32)
        except Exception as e:  # noqa: BLE001 — a refused grid is a row of the table
            rows.append({"shape": shape, "variant": tag,
                         "error": f"{type(e).__name__}: {str(e)[:300]}"})
            print(f"{tag:16s} REFUSED {type(e).__name__}: {str(e)[:160]}", flush=True)
            continue
        base = out if base is None else base
        ready.append((tag, jitted, float(np.max(np.abs(out - base))),
                      time.perf_counter() - t0))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _, jitted, _, _ in ready:
            for _ in range(CALLS):
                out = jitted(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        times, whole = kernel_times(trace_dir)
    print(f"# trace: kernel events under {len(times)} programs, e.g. "
          f"{sorted(times)[:2]}", flush=True)
    for tag, jitted, diff, compile_s in ready:
        ns = times.get(f"jit_{jitted.__name__}", [])
        if latent:
            # The whole call is the measure (the jnp path IS many ops); the
            # kernel's own events beside it where there is a kernel.
            call = whole.get(f"jit_{jitted.__name__}", [])
            if not call:
                rows.append({"shape": shape, "variant": tag, "error": "no program event"})
                print(f"{tag:16s} no execution of jit_{jitted.__name__} in the trace",
                      flush=True)
                continue
            us = sum(call) / len(call) / 1e3
            kernel_us = sum(ns) / len(ns) / 1e3 if ns else None
            rows.append({
                "shape": shape, "variant": tag, "call_us": us,
                "call_us_min": min(call) / 1e3, "calls": len(call),
                "kernel_us": kernel_us, "roofline_share": 100.0 * floor_us / us,
                "max_abs_diff_vs_serving": diff, "compile_run_s": compile_s,
            })
            print(f"{tag:16s} {us:9.1f} us a call (min {min(call) / 1e3:8.1f}, "
                  f"{len(call)} calls; the kernel alone "
                  + (f"{kernel_us:8.1f}" if ns else "     none")
                  + f")  {100.0 * floor_us / us:5.1f}% of the HBM roofline  "
                  f"max|diff| {diff:.4f}", flush=True)
            continue
        if not ns:
            rows.append({"shape": shape, "variant": tag, "error": "no kernel event",
                         "max_abs_diff_vs_serving": diff})
            print(f"{tag:16s} no kernel event under jit_{jitted.__name__} "
                  f"(max|diff| {diff:.4f}); the trace has {sorted(times)[:4]}", flush=True)
            continue
        us = sum(ns) / len(ns) / 1e3
        rows.append({
            "shape": shape, "variant": tag, "kernel_us": us,
            "kernel_us_min": min(ns) / 1e3, "calls": len(ns),
            "roofline_share": 100.0 * floor_us / us,
            "max_abs_diff_vs_serving": diff, "compile_run_s": compile_s,
        })
        print(f"{tag:16s} {us:9.1f} us a call (min {min(ns) / 1e3:8.1f}, "
              f"{len(ns)} calls)  {100.0 * floor_us / us:5.1f}% of the HBM "
              f"roofline  max|diff| {diff:.4f}", flush=True)
    return rows


def main() -> int:
    global PAGE_SIZE, LATENT_PAGES, MHA_PAGES, GQA_PAGES, RINGS
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join([*SHAPES, *LATENT_SHAPES]))
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--page-size", type=int, default=PAGE_SIZE,
                    choices=(8, 16, 32, 64, 128, 256))
    ap.add_argument("--quick", action="store_true",
                    help="a few grids only (a smoke run of the tool)")
    ap.add_argument("--latent-pages", default=",".join(map(str, LATENT_PAGES)),
                    help="pages a KV block of the latent kernel, swept")
    ap.add_argument("--mha-pages", default=",".join(map(str, MHA_PAGES)),
                    help="pages a KV block of the group-1 kernel, swept")
    ap.add_argument("--gqa-pages", default=",".join(map(str, GQA_PAGES)),
                    help="pages a KV block of the grouped kernel, swept")
    ap.add_argument("--rings", default="",
                    help="blocks in a first-party kernel's ring, swept (default: the "
                         "module's with --quick, else 2,3,4)")
    args = ap.parse_args()
    PAGE_SIZE = args.page_size
    MHA_PAGES = tuple(int(n) for n in args.mha_pages.split(","))
    GQA_PAGES = tuple(int(n) for n in args.gqa_pages.split(","))
    RINGS = tuple(int(n) for n in args.rings.split(",") if n)
    LATENT_PAGES = tuple(int(n) for n in args.latent_pages.split(","))

    from dynamo_tpu.device import device_info, device_peaks, enable_compile_cache

    enable_compile_cache()
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(
            f"tools/attn_decode_bench.py: no TPU (platform {info['platform']!r}, "
            f"device_kind {info['kind']!r}); a kernel time from another "
            "device is not a measurement of this one")
    peaks = device_peaks(info["kind"])
    print(f"# device {info['kind']} x {info['count']}; HBM peak "
          f"{peaks.hbm_gbps} GB/s ({peaks.source}); seed {args.seed}", flush=True)
    rows = []
    for shape in args.shapes.split(","):
        rows += bench_shape(shape, args.seed, args.quick, peaks.hbm_gbps * 1e9)
    out = Path("chiprun_out/attn_decode_bench")
    out.mkdir(parents=True, exist_ok=True)
    name = "table.json" if PAGE_SIZE == 32 else f"table_page{PAGE_SIZE}.json"
    (out / name).write_text(json.dumps(
        {"device": info, "seed": args.seed, "page_size": PAGE_SIZE,
         "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
