"""The selective state-space recurrence of a Mamba-2 ("mamba") layer: a
state ``S [H, P, N]`` a sequence, FLOAT32, whatever the context (``H`` heads
of ``P`` channels, a state ``N`` wide, ``G`` groups of ``H / G`` heads that
share ``B`` and ``C``). For token ``t`` of a sequence, per head ``h`` of group
``g`` (``dt > 0`` after its softplus, ``a = exp(-exp(A_log) dt)`` in (0, 1)):

    S_t = a_t S_{t-1} + dt_t x_t B_t^T         (x_t [P], B_t [N] of group g)
    y_t = S_t C_t + D x_t                      (C_t [N] of group g, D a scalar a head)

**The slab** is ``ops/linear_attention.py``'s: one array a layer indexed by a
LANE SLOT, float32, ``[slots, H, P, N]`` (``N`` = 128 at the published width
is a whole lane row: no heads side by side), the last slot the garbage slot.
A row that opens a sequence (``fresh``: position 0) or reads the garbage
slot reads ZEROS, by a select and never by a product.

Two shapes, as that module has them:

- **step** (:func:`ssd_step`, the decode shape: one row a lane). Every live
  lane's state is read once and written once, in place. On a TPU, at tiles of
  whole float32 vregs, one Pallas kernel a layer a step
  (:func:`ssd_step_pallas`, on ``gdn_step_pallas``'s plan): slot ids by
  scalar prefetch, a grid over (lane, a block of heads), each head's ``[P,
  N]`` tile through VMEM once, the decay, the rank-one update and ``S C``
  from that one tile, float32 on the VPU throughout. :func:`ssd_step_jnp` is
  the CPU's path and what ``interpret=True`` tests hold the kernel to.
- **scan** (:func:`ssd_scan_jnp`, the ragged shape). The chunked form (the
  "state-space duality" of the Mamba-2 paper): rows are laid on CHUNKS of
  ``chunk`` rows that never straddle two sequences
  (``linear_attention.chunk_layout``; a sequence's last chunk is padded with
  rows that change nothing: ``dt`` 0, decay 1), everything inside a chunk is
  batched products computed for all chunks at once, and ONE state update a
  chunk runs in order over the chunks. Plain ``jax.numpy`` with float32
  products at ``highest`` precision; a Pallas scan is a later change's.

Which implementation a program traced is counted at trace time
(``dynamo_engine_ssm_calls_traced_total{shape="step"|"scan",
impl="pallas"|"jnp"}``).
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.linear_attention import chunk_layout, zero_where_fresh

# Heads a grid step of the step kernel at most: 32 heads of [64, 128] float32
# are 1 MB a block, 4 MB with both directions double-buffered.
_STEP_HEADS_PER_BLOCK = 32
_HIGHEST = jax.lax.Precision.HIGHEST

# Calls traced since the process started, by shape ("step" / "scan") and
# implementation ("pallas" / "jnp"): ops/linear_attention.py's scheme.
_TRACED: collections.Counter = collections.Counter()
_TRACED_IMPLS: dict[str, str] = {}
_TRACED_LOCK = threading.Lock()


def _count_traced(shape: str, impl: str) -> None:
    with _TRACED_LOCK:
        _TRACED[shape, impl] += 1
        _TRACED_IMPLS[shape] = "+".join(sorted(
            i for (s, i), n in _TRACED.items() if s == shape and n))


def traced_calls() -> dict[tuple[str, str], int]:
    """``{(shape, impl): calls traced}``."""
    with _TRACED_LOCK:
        return dict(_TRACED)


def traced_impl(shape: str) -> str:
    """The implementation(s) this process's programs got for ``shape``
    (``+``-joined if more than one; empty before any was traced)."""
    return _TRACED_IMPLS.get(shape, "")


def _by_group(x: jax.Array, G: int) -> jax.Array:
    """``[..., H, P]`` as ``[..., G, H / G, P]``: the heads by their group."""
    *lead, H, P = x.shape
    return x.reshape(*lead, G, H // G, P)


# -- the step ------------------------------------------------------------------

def ssd_step_jnp(state, slots, x, dt, a, Bm, Cm, D, fresh):
    """One token a lane. ``state [n_slots, H, P, N]`` float32; ``slots [B]``
    int32; ``x [B, H, P]``, ``dt``, ``a`` ``[B, H]``, ``Bm``, ``Cm`` ``[B, G,
    N]``, ``D [H]``, all float32; ``fresh [B]`` bool (the lane reads zeros).
    Returns ``(y [B, H, P] float32, state)``. Lanes that share a slot (the
    garbage slot) leave any one of their states there."""
    B, H, P = x.shape
    G = Bm.shape[1]
    S = zero_where_fresh(state[slots], fresh)                    # [B, H, P, N]
    S = S.reshape(B, G, H // G, P, -1)
    xdt = _by_group(x * dt[..., None], G)                        # [B, G, R, P]
    S = a.reshape(B, G, H // G, 1, 1) * S + xdt[..., None] * Bm[:, :, None, None, :]
    y = jnp.einsum("bgrpn,bgn->bgrp", S, Cm, precision=_HIGHEST).reshape(B, H, P)
    return y + D[:, None] * x, state.at[slots].set(S.reshape(B, H, P, -1))


def _step_kernel(slots_ref, cols_ref, bc_ref, state_ref, o_ref, out_ref, *,
                 Hb: int, P: int, heads_a_group: int):
    """Grid step ``(lane b, head block g)``: ``Hb`` heads of lane ``b``'s slot.
    ``cols_ref [1, 1, P + 8, Hb]``: per head a COLUMN of ``dt x`` (rows
    ``0..P-1``), then ``a`` and ``fresh`` (1.0 or 0.0) in rows ``P`` and ``P +
    1``; heads on lanes, so that a head's column is one masked lane reduction
    and broadcasts along the state's lanes. ``bc_ref [1, 1, 2 Gb, N]``: the
    block's groups' ``B`` rows, then their ``C`` rows. ``o_ref [1, 1, P, Hb]``:
    ``S C`` a head, a column each. Everything on the VPU in float32."""
    del slots_ref   # read by the index maps alone
    cols = cols_ref[0, 0]                                    # [P + 8, Hb]
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    o_lane = jax.lax.broadcasted_iota(jnp.int32, (P, Hb), 1)
    Gb = Hb // heads_a_group
    o = jnp.zeros((P, Hb), jnp.float32)
    for j in range(Hb):
        col = jnp.sum(jnp.where(lane == j, cols, 0.0), axis=1, keepdims=True)   # [P + 8, 1]
        xdt, a, fresh = col[:P], col[P:P + 1], col[P + 1:P + 2]
        r = j // heads_a_group
        S = state_ref[0, j]                                  # [P, N]
        S = jnp.where(fresh > 0.5, 0.0, S) * a + xdt * bc_ref[0, 0, r:r + 1, :]
        out_ref[0, j] = S
        y = jnp.sum(S * bc_ref[0, 0, Gb + r:Gb + r + 1, :], axis=1, keepdims=True)   # [P, 1]
        o = jnp.where(o_lane == j, y, o)
    o_ref[0, 0] = o


def _heads_a_block(H: int, G: int, heads_per_block: int) -> int:
    """Heads a grid step: the most whole groups' worth that divides ``H``
    under ``heads_per_block`` (one group's where that is already more)."""
    R = H // G
    fits = [n * R for n in range(1, G + 1) if G % n == 0 and n * R <= heads_per_block]
    return max(fits, default=R)


@functools.partial(jax.jit, static_argnames=("heads_per_block", "interpret"))
def ssd_step_pallas(state, slots, x, dt, a, Bm, Cm, D, fresh, *,
                    heads_per_block: int = _STEP_HEADS_PER_BLOCK, interpret: bool = False):
    """:func:`ssd_step_jnp` as one Pallas TPU kernel: ``slots`` by scalar
    prefetch, a grid over (lane, block of ``heads_per_block`` heads at most,
    whole groups), the block's state tiles read once and written once IN PLACE
    (``input_output_aliases``); dead lanes name the garbage slot and fall on
    it one after the other. Needs a shape :func:`step_impl` accepts."""
    B, H, P = x.shape
    G, N = Bm.shape[1:]
    R = H // G
    Hb = _heads_a_block(H, G, heads_per_block)
    nb, Gb = H // Hb, Hb // R
    # one column a head: dt x, then a, fresh; padded to whole sublanes
    tail = jnp.stack([a, jnp.broadcast_to(fresh[:, None], a.shape).astype(jnp.float32)],
                     axis=1)                                                 # [B, 2, H]
    cols = jnp.concatenate([
        jnp.swapaxes(x * dt[..., None], 1, 2), tail, jnp.zeros((B, 6, H), jnp.float32)],
        axis=1)                                                              # [B, P + 8, H]
    cols = jnp.swapaxes(cols.reshape(B, P + 8, nb, Hb), 1, 2)               # [B, nb, P + 8, Hb]
    bc = jnp.concatenate([Bm.reshape(B, nb, Gb, N), Cm.reshape(B, nb, Gb, N)], axis=2)
    slots = jnp.clip(slots.astype(jnp.int32), 0, state.shape[0] - 1)
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, Hb=Hb, P=P, heads_a_group=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((1, 1, P + 8, Hb), lambda b, g, s: (b, g, 0, 0)),
                pl.BlockSpec((1, 1, 2 * Gb, N), lambda b, g, s: (b, g, 0, 0)),
                pl.BlockSpec((1, Hb, P, N), lambda b, g, s: (s[b], g, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, P, Hb), lambda b, g, s: (b, g, 0, 0)),
                pl.BlockSpec((1, Hb, P, N), lambda b, g, s: (s[b], g, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, Hb), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar prefetch; the state is operand 3, output 1
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="ssd_step_kernel",
        interpret=interpret,
    )(slots, cols, bc, state)
    y = jnp.moveaxis(o, 2, 3).reshape(B, H, P)
    return y + D[:, None] * x, state


def step_impl(backend: str, state: jax.Array) -> str:
    """Which implementation a step over ``state [n_slots, H, P, N]`` gets on
    ``backend``: ``"pallas"`` on a TPU where a head's tile is whole float32
    vregs (``P`` a multiple of 8 sublanes, ``N`` of 128 lanes), else
    ``"jnp"`` (the CPU; a narrower state). The label of the call's counter."""
    _, _, P, N = state.shape
    fits = state.dtype == jnp.float32 and P % 8 == 0 and N % 128 == 0
    return "pallas" if backend == "tpu" and fits else "jnp"


def ssd_step(state, slots, x, dt, a, Bm, Cm, D, fresh):
    """The step, its implementation chosen from what the call can observe
    (:func:`step_impl`) and counted under ``shape="step"``."""
    impl = step_impl(jax.default_backend(), state)
    _count_traced("step", impl)
    if impl == "pallas":
        return ssd_step_pallas(state, slots, x, dt, a, Bm, Cm, D, fresh)
    return ssd_step_jnp(state, slots, x, dt, a, Bm, Cm, D, fresh)


# -- the scan ------------------------------------------------------------------

def ssd_scan_jnp(state, slots, fresh, x, dt, la, Bm, Cm, D, cu_q_lens, *, chunk: int):
    """The chunked recurrence over a flat ragged batch. ``x [T, H, P]``, ``dt``
    and ``la`` ``[T, H]`` (``la = log a`` <= 0), ``Bm``, ``Cm`` ``[T, G, N]``,
    ``D [H]``, float32; ``slots [S]``, ``fresh [S]`` (the sequence's rows
    start at position 0: it reads zeros); ``cu_q_lens [S + 1]``. Returns ``(y
    [T, H, P] float32, state)``; rows of no sequence come back zero.

    Within a chunk of rows ``1..C`` with incoming state ``S0``, ``L_i =
    sum_{j<=i} la_j``: ``y_i = e^{L_i} S0 C_i + sum_{j<=i} e^{L_i - L_j} (C_i .
    B_j) dt_j x_j + D x_i`` and ``S_C = e^{L_C} S0 + sum_j e^{L_C - L_j} dt_j x_j
    B_j^T``. Every decay is the exponential of a difference <= 0, taken after
    the mask; ``C_i . B_j`` is computed a GROUP, not a head."""
    T, H, P = x.shape
    G, N = Bm.shape[1:]
    R = H // G
    C = min(chunk, T)
    rows, slot_c, zero_c = chunk_layout(cu_q_lens, slots, fresh, T, C, state.shape[0] - 1)
    valid = rows < T
    at = jnp.minimum(rows, T - 1)

    def take(v):     # [T, ...] -> [n, C, ...], zero at padding
        return jnp.where(valid.reshape(valid.shape + (1,) * (v.ndim - 1)), v[at], 0.0)

    xdt = _by_group(take(x * dt[..., None]), G)             # [n, C, G, R, P]
    Bc, Cc = take(Bm), take(Cm)                             # [n, C, G, N]
    L = jnp.cumsum(take(la), axis=1).reshape(-1, C, G, R)   # [n, C, G, R]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    Lh = jnp.moveaxis(L, 1, -1)                             # [n, G, R, C]
    decay = jnp.exp(jnp.where(i >= j, Lh[..., :, None] - Lh[..., None, :], -jnp.inf))
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    CB = mm("nigs,njgs->ngij", Cc, Bc)                      # [n, G, C, C]
    Y = mm("ngrij,njgrp->nigrp", CB[:, :, None] * decay, xdt)
    eL = jnp.exp(L)                                         # [n, C, G, R]
    to_end = jnp.exp(L[:, -1:] - L)[..., None] * xdt        # [n, C, G, R, P]
    end = eL[:, -1]                                         # [n, G, R]

    def body(state, xs):
        slot, zero, Cc, Bc, eL, to_end, end = xs
        S0 = jnp.where(zero, 0.0, state[slot]).reshape(G, R, P, N)
        O = eL[..., None] * mm("igs,grps->igrp", Cc, S0)
        S1 = end[..., None, None] * S0 + mm("igrp,igs->grps", to_end, Bc)
        return jax.lax.dynamic_update_index_in_dim(state, S1.reshape(H, P, N), slot, 0), O

    state, O = jax.lax.scan(body, state, (slot_c, zero_c, Cc, Bc, eL, to_end, end))
    y = jnp.zeros((T, H, P), jnp.float32).at[rows.reshape(-1)].set(
        (Y + O).reshape(-1, H, P), mode="drop")
    return y + D[:, None] * x, state


def ssd_scan(state, slots, fresh, x, dt, la, Bm, Cm, D, cu_q_lens, *, chunk: int):
    """The scan, counted under ``shape="scan"`` (one implementation yet)."""
    _count_traced("scan", "jnp")
    return ssd_scan_jnp(state, slots, fresh, x, dt, la, Bm, Cm, D, cu_q_lens, chunk=chunk)


def ssd_recurrence(x, dt, la, Bm, Cm, D, S0=None):
    """The recurrence token by token over ONE sequence (``lax.scan`` over
    positions), from state ``S0 [H, P, N]`` (zeros where None): what the tests
    hold the step and the scan to. ``x [T, H, P]``, ``dt``, ``la`` ``[T, H]``,
    ``Bm``, ``Cm`` ``[T, G, N]``, ``D [H]``. Returns ``(y [T, H, P], S)``."""
    H, P = x.shape[1:]
    G, N = Bm.shape[1:]
    S0 = jnp.zeros((H, P, N), jnp.float32) if S0 is None else S0

    def body(S, t):
        x, dt, la, B, C = t
        B, C = jnp.repeat(B, H // G, axis=0), jnp.repeat(C, H // G, axis=0)   # [H, N]
        S = jnp.exp(la)[:, None, None] * S + (dt[:, None] * x)[..., None] * B[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C, precision=_HIGHEST) + D[:, None] * x

    S, y = jax.lax.scan(body, S0, (x, dt, la, Bm, Cm))
    return y, S
