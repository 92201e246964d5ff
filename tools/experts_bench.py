"""One sparse layer's expert products alone, at the shapes the
benchmark's sparse cells run, timed on the host's clock around calls that
end in ``block_until_ready``.

For each shape (``lfm2``: 64 of 64 experts of 2048 x 1536 held, 4 a
token; ``axk1``: 12 of 192 experts of 7168 x 2048 held, 8 a token;
``sdar``: 128 of 128 of 2048 x 768, 8 a token; ``laguna``: 32 of 256 of
3072 x 1024, 10 a token; ``mimo``: 16 of 256 of 4096 x 2048, 8 a token)
and each row count it runs, on the same routed rows:

- ``serving``: what ``model._shared_sparse_mlp`` would run at that width
  (every expert on every row at or under ``_EXPERTS_ALL_ROWS_MAX`` rows,
  by the path ``ops/expert_stream.py:impl`` chooses;
  ``model._experts_grouped`` above it, its implementation chosen by
  ``ops/expert_stream.py:grouped_impl`` and ``ops/grouped_matmul.py:impl``);
- ``all_rows``: ``model._experts_all_rows``, the definition the others
  are compared with;
- ``stream/pallas`` over ``--blocks`` at a step's widths (``rows of w_gu x
  rows of w_down x blocks in the ring`` of ``ops/expert_stream.py``'s
  kernel; ``auto`` = the module's own; interpreted where there is no TPU);
- ``grouped/ragged_dot``, ``grouped/pallas`` over ``--tilings``
  (``rows x columns`` of a block of the library kernel, ``K`` whole; ``auto``
  = ``grouped_matmul.tiling``) and ``grouped/stream`` over ``--items``
  (``rows an item at most x blocks in the ring at most`` of
  ``ops/expert_stream.py``'s grouped kernel; ``auto`` = the module's own;
  interpreted where there is no TPU), and with ``--pieces`` the parts of
  the grouped layer one by one: the permutation, the gather of the sorted
  rows, the products (the two library ones, or the one kernel), the
  combine.

It prints ms a call against the time the held experts' bytes take at the
chip's HBM rate (and, for a grouped variant, the TOUCHED experts' bytes: an
expert no row chose is not read), the rows the products ran on over the pairs held, and
the largest difference from ``all_rows``.

Refuses to run without a TPU (a time from the CPU says nothing here)
unless ``--toy`` cuts the shapes to a size the CPU runs in seconds: that
is the rehearsal of its control flow, and its times mean nothing.

``--in-loop K`` times every variant as ``K`` dependent calls inside one
``fori_loop``, as a megastep holds a sparse layer, and prints ms a call.

Usage (through the chip tool, from the repo root):
    python -m tools.experts_bench [--shapes lfm2,axk1,sdar,laguna,mimo]
                                  [--rows 32,128,256,512,1024,2048]
                                  [--routing random,one,skewed] [--tilings auto,128x512]
                                  [--blocks auto,1024x1536x3] [--items auto,512x3]
                                  [--in-loop 8] [--pieces] [--toy]
Writes ``chiprun_out/experts_bench/table.json`` beside the table.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

# hidden, expert width, experts the router chooses among, experts held
# (the first ones), experts a token: lfm2-24b-hybrid-decode, axk1-ep16-decode,
# sdar30b-block-decode, laguna-s21-longctx-agents, mimo-v25-ep16-longctx.
SHAPES = {
    "lfm2": (2048, 1536, 64, 64, 4),
    "axk1": (7168, 2048, 192, 12, 8),
    "sdar": (2048, 768, 128, 128, 8),
    "laguna": (3072, 1024, 256, 32, 10),
    "mimo": (4096, 2048, 256, 16, 8),
}
TOY = {
    "lfm2": (256, 128, 16, 16, 4),
    "axk1": (256, 128, 48, 3, 8),
    "sdar": (256, 128, 32, 32, 8),
    "laguna": (256, 128, 32, 4, 5),
    "mimo": (256, 256, 64, 4, 8),
}
HBM_BYTES_PER_S = 819e9     # TPU v5e (chipbench/peaks.py)
CALLS = 20


def make_case(shape: tuple, rows: int, routing: str, seed: int):
    """(xf, w_held, chosen_held, w_gu, w_down) of one layer: bf16 rows
    and weights; ``routing`` ``random`` draws each row's experts evenly
    among all, ``one`` sends every row to held expert 0 first (the
    dropless worst case), ``skewed`` favours some experts as a router of
    random weights does (normal scores + 1.5 x a normal draw an expert: at
    8 of 128 a row and 1,024 rows ~85 experts are touched, ~125 tiles of
    128 rows, the fullest expert ~850 rows: SDAR's step by its counters)."""
    import jax
    import jax.numpy as jnp

    h, im, experts, held, k = shape
    rs = np.random.RandomState(seed)
    score = rs.rand(rows, experts)
    if routing == "one":
        score[:, 0] = 2.0
    elif routing == "skewed":
        score = rs.randn(rows, experts) + 1.5 * rs.randn(experts)[None, :]
    idx = np.argsort(-score, axis=1)[:, :k]
    chosen = np.zeros((rows, experts), bool)
    chosen[np.arange(rows)[:, None], idx] = True
    chosen_held = jnp.asarray(chosen[:, :held])
    w_held = jnp.where(chosen_held, jnp.asarray(rs.rand(rows, held), jnp.float32), 0.0)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    normal = lambda key, dims, scale: (
        jax.random.normal(key, dims, jnp.float32) * scale).astype(jnp.bfloat16)
    return (normal(keys[0], (rows, h), 1.0), w_held, chosen_held,
            normal(keys[1], (held, h, 2 * im), h ** -0.5),
            normal(keys[2], (held, im, h), im ** -0.5))


def _grouped(impl: str, k: int, all_held: bool, tm: int | None, tn: int | None,
             items: str = "auto", interpret: bool = False):
    """``model._experts_grouped`` traced anew, with the library kernel's
    blocks stated (``tm`` rows, ``K`` whole, ``tn`` columns; None: the
    module's own choice) or the streamed kernel's ``items`` (``rows an item
    at most x blocks in the ring at most``; ``auto``: the module's own),
    interpreted where there is no TPU."""
    import contextlib
    import functools
    from unittest import mock

    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import expert_stream as es
    from dynamo_tpu.ops import grouped_matmul as gm

    kw = {"interpret": interpret}
    if items != "auto":
        kw.update(zip(("item_rows", "ring"), (int(n) for n in items.split("x"))))

    def run(xf, w_held, chosen_held, w_gu, w_down):
        with contextlib.ExitStack() as stack:
            if tm is not None:      # read at trace time, which is inside this call
                stack.enter_context(mock.patch.object(gm, "_TILE_ROWS", tm))
                stack.enter_context(mock.patch.object(
                    gm, "tiling", lambda kk, n, itemsize: (tm, kk, min(tn, n))))
            if impl == "stream":
                stack.enter_context(mock.patch.object(
                    es, "expert_stream_grouped", functools.partial(es.expert_stream_grouped, **kw)))
            return model._experts_grouped.__wrapped__(
                xf, w_held, chosen_held, w_gu, w_down, k=k, impl=impl, all_held=all_held)

    return run


def wave_impl(shape: tuple, rows: int, backend: str, w_gu, w_down) -> str:
    """The implementation ``model._shared_sparse_mlp`` gives a wave of
    ``rows`` rows at ``shape``."""
    from dynamo_tpu.engine import model

    return model.wave_impl(backend, w_gu.dtype, rows * shape[4] / shape[2], w_gu, w_down)


def _stream(blocks: str, interpret: bool):
    """``expert_stream`` at the blocks stated (``tk x ti x ring``; ``auto``:
    the module's own), interpreted where there is no TPU."""
    from dynamo_tpu.ops import expert_stream as es

    kw = {}
    if blocks != "auto":
        kw = dict(zip(("tk", "ti", "ring"), (int(n) for n in blocks.split("x"))))

    def run(xf, w_held, chosen_held, w_gu, w_down):
        return es.expert_stream(xf, w_held, w_gu, w_down, interpret=interpret, **kw)

    return run


def variants(shape: tuple, rows: int, tilings: list[str], blocks: list[str], items: list[str],
             on_tpu: bool, wave: str):
    """[(tag, fn(xf, w_held, chosen_held, w_gu, w_down), rows a tile of
    its grouped products (a function of the groups' counts: the streamed
    kernel's own account) or None)], serving first
    (``wave``: :func:`wave_impl`'s answer for the case). XLA's own tile on
    a TPU is not the module's to know: its rows read as the pairs held."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import expert_stream as es
    from dynamo_tpu.ops import grouped_matmul as gm

    k, all_held = min(shape[4], shape[3]), shape[3] == shape[2]
    step = model.expert_call_shape(rows) == "step"

    def serving(xf, w_held, chosen_held, w_gu, w_down):
        import jax

        if step:
            if es.impl(jax.default_backend(), xf.dtype, rows, w_gu, w_down) == "stream/pallas":
                return es.expert_stream(xf, w_held, w_gu, w_down)
            return model._experts_all_rows(xf, w_held, w_gu, w_down)
        return model._experts_grouped(xf, w_held, chosen_held, w_gu, w_down, k=k, impl=wave,
                                      all_held=all_held)

    def all_rows(xf, w_held, chosen_held, w_gu, w_down):
        return model._experts_all_rows(xf, w_held, w_gu, w_down)

    auto = gm.tile_rows("pallas")
    tiles = {"ragged_dot": 1, "pallas": auto, "stream": es.grouped_rows_visited}
    out = [("serving", serving, None if step else tiles[wave]), ("all_rows", all_rows, None)]
    if step:
        out += [(f"stream/pallas {b}", _stream(b, not on_tpu), None) for b in blocks]
    out.append(("grouped/ragged_dot", _grouped("ragged_dot", k, all_held, None, None), 1))
    if on_tpu:
        for t in tilings:
            tm, tn = (None, None) if t == "auto" else (int(n) for n in t.split("x"))
            out.append((f"grouped/pallas {t}", _grouped("pallas", k, all_held, tm, tn), tm or auto))
    for i in items:
        out.append((f"grouped/stream {i}", _grouped("stream", k, all_held, None, None, i,
                                                    not on_tpu), es.grouped_rows_visited))
    return out


def pieces(shape: tuple, impl: str, interpret: bool):
    """[(tag, fn, None)] of the grouped layer's parts over ALL the sorted places
    at once (the layer goes a slab at a time where they are many), each a
    program of its own (what the whole fuses is not seen here). ``impl``
    ``"stream"``: the places as the streamed kernel has them (a group's
    first a multiple of ``GROUP_ALIGN``), and the one kernel where the
    others have two products."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import expert_stream as es
    from dynamo_tpu.ops import grouped_matmul as gm

    k = min(shape[4], shape[3])
    stream = impl == "stream"
    tile = es.SLAB_ROWS if stream else gm.tile_rows(impl)
    align = es.GROUP_ALIGN if stream else 1

    def sort(chosen_held, w_held):
        return model._sorted_pairs(chosen_held, w_held, k, tile, align)

    def perm(xf, w_held, chosen_held, w_gu, w_down):
        return sort(chosen_held, w_held)

    def gather(xf, w_held, chosen_held, w_gu, w_down):
        return xf[sort(chosen_held, w_held)[0]]

    def places(xf, chosen_held):
        return -(-(xf.shape[0] * k + (align - 1) * chosen_held.shape[1]) // tile) * tile

    def product(which):
        def run(xf, w_held, chosen_held, w_gu, w_down):
            counts = jnp.sum(chosen_held, axis=0, dtype=jnp.int32)
            w = w_gu if which == "gate_up" else w_down
            lhs = jnp.zeros((places(xf, chosen_held), w.shape[1]), xf.dtype) + xf[0, 0]
            return gm.grouped_matmul(lhs, w, counts, impl=impl)
        return run

    def kernel(xf, w_held, chosen_held, w_gu, w_down):
        counts = jnp.sum(chosen_held, axis=0, dtype=jnp.int32)
        padded = -(-counts // align) * align
        lhs = jnp.zeros((places(xf, chosen_held), xf.shape[1]), xf.dtype) + xf[0, 0]
        return es.expert_stream_grouped(lhs, jnp.cumsum(padded) - padded, counts, w_gu, w_down,
                                        interpret=interpret)

    def combine(xf, w_held, chosen_held, w_gu, w_down):
        rows, counts, place, weight = sort(chosen_held, w_held)
        y = jnp.zeros((rows.shape[0], xf.shape[1]), jnp.float32) + w_held[0, 0]
        return gm.combine(jnp.zeros(xf.shape, jnp.float32), y,
                          jnp.where(place < rows.shape[0], place, y.shape[0]), weight,
                          full=shape[3] == shape[2])

    products = [("  the kernel", kernel, None)] if stream else [
        ("  product gate/up", product("gate_up"), None), ("  product down", product("down"), None)]
    return [("  permutation", perm, None), ("  permutation + gather", gather, None), *products,
            ("  permutation + combine", combine, None)]


def in_loop(fn, turns: int):
    """``fn`` as ``turns`` calls inside one ``fori_loop``, each on rows the
    one before it moved (by a part in 2^20 of its result, so that no call
    can be hoisted out or run beside another): a megastep's hold on a
    sparse layer. Returns the last call's result."""
    import jax
    import jax.numpy as jnp

    def run(xf, *rest):
        def turn(_, carry):
            out = fn(carry[0], *rest)
            return (xf + (out * 2.0 ** -20).astype(xf.dtype)), out

        return jax.lax.fori_loop(0, turns, turn, (xf, jnp.zeros(xf.shape, jnp.float32)))[1]

    return run


def time_call(fn, args, turns: int = 1) -> tuple[float, object]:
    """(ms a call, the result): ``CALLS`` dispatches, of ``turns`` calls
    each where ``fn`` returns ``[N, h]`` (a piece that does not is timed a
    call a dispatch)."""
    import jax

    if turns > 1 and getattr(jax.eval_shape(fn, *args), "shape", None) == args[0].shape:
        fn = in_loop(fn, turns)
    else:
        turns = 1
    run = jax.jit(fn)
    out = jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = run(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS * 1e3 / turns, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--rows", default="32,128,256,512,1024,2048")
    ap.add_argument("--routing", default="random")
    ap.add_argument("--tilings", default="auto")
    ap.add_argument("--blocks", default="auto")
    ap.add_argument("--items", default="auto")
    ap.add_argument("--in-loop", type=int, default=1)
    ap.add_argument("--pieces", action="store_true")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/experts_bench")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import expert_stream as es
    from dynamo_tpu.ops import grouped_matmul as gm

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.toy:
        raise SystemExit("experts_bench: no TPU here (a time from the CPU says nothing); "
                         "--toy rehearses the control flow at toy shapes")
    print(f"device: {device.platform} / {device.device_kind}"
          + (" (toy shapes: the times mean nothing)" if args.toy else ""), flush=True)
    table = []
    for name in args.shapes.split(","):
        shape = (TOY if args.toy else SHAPES)[name]
        h, im, experts, held, k = shape
        expert_ms = 3 * h * im * 2 / HBM_BYTES_PER_S * 1e3
        floor_ms = held * expert_ms
        for routing in args.routing.split(","):
            for rows in (int(n) for n in args.rows.split(",")):
                case = make_case(shape, rows, routing, args.seed)
                counts = jnp.sum(case[2], axis=0, dtype=jnp.int32)
                pairs, touched = int(jnp.sum(counts)), int(jnp.sum(counts > 0))
                touched_ms = touched * expert_ms
                wave = wave_impl(shape, rows, device.platform, case[3], case[4])
                print(f"\n== {name}: {held} of {experts} experts of {h} x {im} held, {rows} rows, "
                      f"{routing} routing, {pairs} pairs held on {touched} experts; the bytes take "
                      f"{floor_ms:.3f} ms, the touched experts' {touched_ms:.3f} ms; a wave here "
                      f"gets grouped/{wave}", flush=True)
                todo = variants(shape, rows, args.tilings.split(","), args.blocks.split(","),
                                args.items.split(","), on_tpu, wave)
                if args.pieces:
                    todo += pieces(shape, wave, not on_tpu)
                want = None
                for tag, fn, tile in todo:
                    line = {"shape": name, "rows": rows, "routing": routing, "variant": tag.strip(),
                            "pairs_held": pairs, "experts_touched": touched, "bytes_ms": floor_ms,
                            "touched_bytes_ms": touched_ms, "wave_impl": wave,
                            "in_loop": args.in_loop}
                    try:
                        ms, out = time_call(fn, case, args.in_loop)
                    except Exception as e:   # a tiling the compiler refuses: say so, go on
                        print(f"{tag:<32} FAILED: {str(e).splitlines()[0][:160]}", flush=True)
                        table.append({**line, "error": str(e)[:400]})
                        continue
                    line["ms"] = ms
                    note = ""
                    if tag == "all_rows":
                        want = out
                        line["rows_computed"] = held * rows
                    elif tile:
                        line["rows_computed"] = int(
                            tile(counts) if callable(tile) else gm.rows_visited(counts, tile))
                    if want is not None and tag.startswith(("grouped", "stream")):
                        line["max_abs_diff"] = float(jnp.max(jnp.abs(out - want)))
                        note = (f", max |diff| {line['max_abs_diff']:.2e} of "
                                f"{float(jnp.max(jnp.abs(want))):.2e}")
                    if "rows_computed" in line:
                        note = f", rows / pair {line['rows_computed'] / max(pairs, 1):.2f}" + note
                    print(f"{tag:<32} {ms:8.3f} ms = {ms / floor_ms:5.2f} x the bytes, "
                          f"{ms / max(touched_ms, 1e-9):5.2f} x the touched{note}", flush=True)
                    table.append(line)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "table.json").write_text(json.dumps(
        {"device": device.device_kind, "toy": args.toy, "lines": table}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
