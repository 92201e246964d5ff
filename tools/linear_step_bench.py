"""A linear-attention layer's decode STATE STEP alone
(``ops/linear_attention.py``), at the shape of the cell
``olmo-hybrid-7b-reason-decode``, timed on the host's clock around a jitted
loop of dependent calls that carries the slab as a megastep's scan does.

``--shape olmo``: 30 heads, a float32 tile of ``[96, 192]`` a head, two side
by side as the slab keeps them, ``lanes + 1`` lane slots (the last the garbage
slot), lanes in a random order of slots. For each of ``--lanes``: the
first-party kernel over ``--heads`` heads a grid step, beside the ``jax.numpy`` step (gather, two products, a
rank-one update, scatter): milliseconds a call, the share of the HBM
roofline as the benchmark's ``linear_state_roofline`` counts the bytes (every
live lane's 2,211,840 B of state read once and written once), and the
largest difference from the ``jax.numpy`` step on the same arguments.

``--shape nemotron`` (PR 54): a Mamba-2 layer's step instead
(``ops/ssm.py``), the cell ``nemotron3-nano-ep2-decode``'s: 64 heads of 64
channels, a state 128 wide in 8 groups, 2,097,152 B a lane; ``--heads`` then
sweeps the heads a grid step of ``ssd_step_pallas`` (whole groups of 8).

Refuses to run without a TPU: a time from the CPU says nothing here.

Usage (through the chip tool, from the repo root):
    python -m tools.linear_step_bench [--shape olmo] [--lanes 32,48,64]
                                      [--heads 5,10,15,30] [--calls 16]
Writes ``chiprun_out/linear_step_bench/table.json`` beside the table.
"""

from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path

import numpy as np

# heads, key width, value width (nemotron: heads, channels a head, state width)
SHAPES = {"olmo": (30, 96, 192), "nemotron": (64, 64, 128)}
_SSM_GROUPS = 8


def make_case(shape: str, lanes: int, seed: int):
    """``(state, slots, q, k, v, alpha, beta, fresh)`` and the bytes the call
    must move."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.linear_attention import heads_per_tile, l2_normalize, pack_heads

    H, dk, dv = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    if shape == "nemotron":   # (state, slots, x, dt, a, B, C, D, fresh)
        state = jax.random.normal(ks[0], (lanes + 1, H, dk, dv), jnp.float32)
        slots = jnp.asarray(np.random.RandomState(seed).permutation(lanes), jnp.int32)
        dt = jnp.exp(jax.random.uniform(ks[2], (lanes, H), jnp.float32, -6.9, -2.3))
        return (state, slots, jax.random.normal(ks[1], (lanes, H, dk)), dt,
                jnp.exp(-8.0 * dt), jax.random.normal(ks[3], (lanes, _SSM_GROUPS, dv)),
                jax.random.normal(ks[4], (lanes, _SSM_GROUPS, dv)), jnp.ones((H,), jnp.float32),
                jnp.zeros((lanes,), bool)), 2 * lanes * H * dk * dv * 4
    state = pack_heads(jax.random.normal(ks[0], (lanes + 1, H, dk, dv), jnp.float32),
                       heads_per_tile(H, dv))            # the slab's layout
    slots = jnp.asarray(np.random.RandomState(seed).permutation(lanes), jnp.int32)
    q = l2_normalize(jax.random.normal(ks[1], (lanes, H, dk)), 1e-6) * dk ** -0.5
    k = l2_normalize(jax.random.normal(ks[2], (lanes, H, dk)), 1e-6)
    v = jax.random.normal(ks[3], (lanes, H, dv), jnp.float32)
    alpha = jax.random.uniform(ks[4], (lanes, H), jnp.float32, 0.9, 0.999)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (lanes, H), jnp.float32))
    fresh = jnp.zeros((lanes,), bool)
    return (state, slots, q, k, v, alpha, beta, fresh), 2 * lanes * H * dk * dv * 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", default="olmo", choices=sorted(SHAPES))
    ap.add_argument("--lanes", default="32,48,64")
    ap.add_argument("--heads", default="5,10,15,30")
    ap.add_argument("--calls", type=int, default=16)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise SystemExit("linear_step_bench times a TPU kernel (send it through chiprun); "
                         f"this backend is {jax.default_backend()}")
    from dynamo_tpu.device import device_info, device_peaks
    from dynamo_tpu.ops import linear_attention as la
    from dynamo_tpu.ops import ssm

    step_jnp, step_pallas = ((ssm.ssd_step_jnp, ssm.ssd_step_pallas) if args.shape == "nemotron"
                             else (la.gdn_step_jnp, la.gdn_step_pallas))
    hbm_bytes_per_s = device_peaks(device_info()["kind"]).hbm_gbps * 1e9
    rows = []
    for lanes in (int(n) for n in args.lanes.split(",")):
        case, need = make_case(args.shape, lanes, seed=lanes)
        want_o, want_state = jax.jit(step_jnp)(*case)
        impls = [("jnp", step_jnp)] + [
            (f"pallas/{g}", functools.partial(step_pallas, heads_per_block=g))
            for g in (int(n) for n in args.heads.split(","))]
        for name, call in impls:

            @functools.partial(jax.jit, donate_argnums=(0,))
            def loop(state, slots, first, *rest, call=call):
                def body(_, carry):   # each turn reads the state the last one wrote
                    state, o = carry
                    # (the first [lanes, H, width] operand: q, or a mamba step's x)
                    o, state = call(state, slots, first + 0 * jnp.sum(o), *rest)
                    return state, o
                return jax.lax.fori_loop(0, args.calls, body, (state, jnp.zeros_like(want_o)))

            try:
                o, state = jax.jit(call)(*case)
                diff = max(float(jnp.max(jnp.abs(o - want_o))),
                           float(jnp.max(jnp.abs(state - want_state))))
                state = jax.block_until_ready(loop(jnp.copy(case[0]), *case[1:]))[0]
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    state = jax.block_until_ready(loop(state, *case[1:]))[0]
                    best = min(best, time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — a grid Mosaic refuses is a row
                rows.append({"shape": args.shape, "lanes": lanes, "impl": name,
                             "error": str(e)[:300]})
                print(rows[-1], flush=True)
                continue
            ms = best / args.calls * 1e3
            rows.append({
                "shape": args.shape, "lanes": lanes, "impl": name, "ms_per_call": round(ms, 4),
                "roofline_pct": round(100 * need / hbm_bytes_per_s / (ms * 1e-3), 1),
                "max_abs_diff": diff,
            })
            print(rows[-1], flush=True)
    out = Path("chiprun_out/linear_step_bench")
    out.mkdir(parents=True, exist_ok=True)
    (out / "table.json").write_text(json.dumps(
        {"device": device_info(), "calls": args.calls, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
