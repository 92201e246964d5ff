"""The gated delta rule of a ``linear_attention`` layer: a recurrent state
``S [H, dk, dv]`` a sequence, FLOAT32, whatever the context. For token ``t``
of a sequence, per head (``alpha`` in (0, 1), ``beta`` in (0, 2), ``k`` of
unit length):

    S' = alpha_t S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

**The slab.** The state is too large to keep a block (2.2 MB a layer a
sequence at the published widths against 0.5 MB of K/V a 32-token block), so
it lives in ONE array a layer indexed by a LANE SLOT, float32: a sequence
holds a slot from admission to its end (``EngineCore``), the last slot is
the garbage slot that padding rows, dead megastep iterations and dead lanes
read and write. Its shape is ``[slots, H / p, dk, p dv]``: ``p`` heads lie
SIDE BY SIDE in a tile (:func:`heads_per_tile`, :func:`pack_heads`), as
many as make ``p dv`` whole 128-lane rows (2 at the published ``dv`` 192).
``[slots, H, dk, 192]`` is the same values, but the device pads a minor
dimension of 192 to 256 lanes: a third more memory (433 MB of this cell's
slab) and a third more bytes in every copy of a tile (the v5e, PR 50: the
step kernel read 61% of its counted bytes' rate on such tiles). A row that
opens a sequence (``fresh``: position 0) or reads the garbage slot reads
ZEROS, chosen by a select and never by a product: whatever the slot held, a
NaN among it, stays out.

Two shapes, as the attention modules have them:

- **step** (:func:`gdn_step`, the decode shape: one row a lane). The state of
  every live lane is read once and written once, in place; nothing else of
  the call is as large. On a TPU, at tiles of whole lane rows, one Pallas
  kernel a layer a step (:func:`gdn_step_pallas`): slot ids by scalar
  prefetch, a grid over (lane, a group of tiles), each head's ``[dk, dv]``
  tile (``p`` of them side by side) through VMEM once, ``S'^T k``, the
  rank-one update and ``S^T q`` from that one tile, float32 on the VPU
  throughout (no product of the state passes through the MXU's bfloat16).
  :func:`gdn_step_jnp` is the CPU's path, what ``interpret=True`` tests
  hold the kernel to, and what serves where Mosaic has no tile for the
  shape (:func:`step_impl`).
- **scan** (:func:`gdn_scan_jnp`, the ragged shape: a wave's prompts in one
  flat ``[T, ...]``, a prompt's chunk, a mixed batch). The chunked form:
  rows are laid on CHUNKS of ``SCAN_CHUNK`` that never straddle two
  sequences (a sequence's last chunk is padded with rows that change
  nothing: ``beta`` 0, ``alpha`` 1, ``k`` 0), everything inside a chunk is
  batched products and one unit-lower-triangular solve
  (:func:`unit_lower_solve`), computed for all chunks at once, and ONE state update a chunk runs in order over the
  chunks: a sequence's first chunk reads its slot (zeros where the chunk
  opens the sequence), every chunk writes it. Algebra on the recurrence
  above, held to it token by token in tests/test_linear_attention.py. Plain
  ``jax.numpy`` with float32 products at ``highest`` precision; a Pallas
  scan is a later change's.

Which implementation a program traced is counted at trace time
(``dynamo_engine_linear_calls_traced_total{shape="step"|"scan",
impl="pallas"|"jnp"}``), as the attention modules count theirs.
"""

from __future__ import annotations

import collections
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a chunk of the scan: the triangular solve and the in-chunk products
# are [64, 64] a head; the state is updated once a chunk.
SCAN_CHUNK = 64
# Heads a grid step of the step kernel: 10 heads of [96, 192] float32 (5 tiles
# of two) are 720 KB a block, 2.9 MB with both directions double-buffered.
_STEP_HEADS_PER_BLOCK = 10
_HIGHEST = jax.lax.Precision.HIGHEST

# Calls traced since the process started, by shape ("step" / "scan") and
# implementation ("pallas" / "jnp"): ops/ragged_attention.py's scheme.
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


def l2_normalize(x: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def heads_per_tile(H: int, dv: int) -> int:
    """``p``: heads that lie side by side in one tile of the slab, so that a
    tile's ``p dv`` values a row are whole 128-lane rows (2 at ``dv`` 192 or
    64, 1 at 128); 1 where ``H`` has no such divisor."""
    p = 128 // math.gcd(dv, 128)
    return p if H % p == 0 else 1


def pack_heads(S: jax.Array, p: int) -> jax.Array:
    """``[..., H, dk, dv]`` as the slab keeps it, ``[..., H / p, dk, p dv]``:
    heads ``p t .. p t + p - 1`` side by side in tile ``t``."""
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // p, p, dk, dv)
    return jnp.moveaxis(S, -3, -2).reshape(*lead, H // p, dk, p * dv)


def unpack_heads(S: jax.Array, p: int) -> jax.Array:
    """:func:`pack_heads` undone: ``[..., H / p, dk, p dv]`` as ``[..., H, dk, dv]``."""
    *lead, Hp, dk, pdv = S.shape
    S = S.reshape(*lead, Hp, dk, p, pdv // p)
    return jnp.moveaxis(S, -2, -3).reshape(*lead, Hp * p, dk, pdv // p)


def zero_where_fresh(state_rows: jax.Array, fresh: jax.Array) -> jax.Array:
    """``state_rows [n, ...]`` with the rows ``fresh [n]`` names replaced by
    zeros: a select, so that what a slot held (a NaN among it) stays out."""
    pick = fresh.reshape(fresh.shape + (1,) * (state_rows.ndim - 1))
    return jnp.where(pick, jnp.zeros((), state_rows.dtype), state_rows)


# -- the step ------------------------------------------------------------------

def gdn_step_jnp(state, slots, q, k, v, alpha, beta, fresh):
    """One token a lane. ``state [n_slots, H / p, dk, p dv]`` float32 (the
    slab's layout, :func:`pack_heads`); ``slots [B]`` int32; ``q``, ``k``
    ``[B, H, dk]``, ``v [B, H, dv]``, ``alpha``, ``beta`` ``[B, H]``, all
    float32; ``fresh [B]`` bool (the lane reads zeros). Returns ``(o [B, H,
    dv] float32, state)``. Lanes that share a slot (the garbage slot) leave
    any one of their states there."""
    p = q.shape[1] // state.shape[1]
    S = unpack_heads(zero_where_fresh(state[slots], fresh), p)
    S = alpha[..., None, None] * S
    kS = jnp.einsum("bhk,bhkv->bhv", k, S, precision=_HIGHEST)
    u = beta[..., None] * (v - kS)
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, S, precision=_HIGHEST)
    return o, state.at[slots].set(pack_heads(S, p))


def _step_kernel(slots_ref, cols_ref, rows_ref, state_ref, o_ref, out_ref, *,
                 G: int, p: int, dk: int, dv: int):
    """Grid step ``(lane b, tile group g)``: ``G`` tiles of lane ``b``'s slot,
    ``p`` heads side by side in each. ``cols_ref [1, 2 dk + 8, Hp]``: per head
    a COLUMN of ``k`` (rows ``0..dk-1``), of ``q`` (``dk..2dk-1``), then
    ``alpha``, ``beta`` and ``fresh`` (1.0 or 0.0) in rows ``2 dk``, ``2 dk +
    1``, ``2 dk + 2``; heads on lanes, so that a head's column is one masked
    lane reduction and broadcasts along lanes as a softmax's running maximum
    does. ``rows_ref [1, 1, G, p dv]``: the group's ``v``, a tile's heads side
    by side as its state's are. Everything on the VPU in float32."""
    del slots_ref   # read by the index maps alone
    g = pl.program_id(1)
    cols = cols_ref[0]                                      # [2 dk + 8, Hp]
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    side = jax.lax.broadcasted_iota(jnp.int32, (1, p * dv), 1) // dv   # which head a lane is of
    for j in range(G):
        col = None
        for t in range(p):   # the tile's heads' columns, each over its own lanes
            one = jnp.sum(jnp.where(lane == (g * G + j) * p + t, cols, 0.0),
                          axis=1, keepdims=True)            # [2 dk + 8, 1]
            col = one if col is None else jnp.where(side == t, one, col)
        k, q = col[:dk], col[dk:2 * dk]                    # [dk, 1] or [dk, p dv]
        alpha, beta = col[2 * dk:2 * dk + 1], col[2 * dk + 1:2 * dk + 2]
        fresh = col[2 * dk + 2:2 * dk + 3]
        S = state_ref[0, j]                                 # [dk, p dv]
        S = jnp.where(fresh > 0.5, 0.0, S) * alpha
        kS = jnp.sum(S * k, axis=0, keepdims=True)         # [1, p dv]
        u = beta * (rows_ref[0, 0, j:j + 1, :] - kS)
        S = S + k * u
        out_ref[0, j] = S
        o_ref[0, 0, j:j + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads_per_block", "interpret"))
def gdn_step_pallas(state, slots, q, k, v, alpha, beta, fresh, *,
                    heads_per_block: int = _STEP_HEADS_PER_BLOCK, interpret: bool = False):
    """:func:`gdn_step_jnp` as one Pallas TPU kernel: ``slots`` by scalar
    prefetch, a grid over (lane, group of tiles that hold about
    ``heads_per_block`` heads), the group's state tiles read once and written
    once IN PLACE (``input_output_aliases``); dead lanes name the garbage
    slot and fall on it one after the other. Needs a shape :func:`step_impl`
    accepts."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    Hp = state.shape[1]
    p = H // Hp
    G = max(g for g in range(1, max(1, min(heads_per_block // p, Hp)) + 1) if Hp % g == 0)
    # one column a head: k, q, then alpha, beta, fresh; padded to whole sublanes
    tail = jnp.stack([alpha, beta, jnp.broadcast_to(fresh[:, None], alpha.shape)
                      .astype(jnp.float32)], axis=1)                        # [B, 3, H]
    cols = jnp.concatenate([
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2), tail,
        jnp.zeros((B, 5, H), jnp.float32)], axis=1)                          # [B, 2 dk + 8, H]
    rows = v.reshape(B, Hp // G, G, p * dv)
    slots = jnp.clip(slots.astype(jnp.int32), 0, state.shape[0] - 1)
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, G=G, p=p, dk=dk, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hp // G),
            in_specs=[
                pl.BlockSpec((1, 2 * dk + 8, H), lambda b, g, s: (b, 0, 0)),
                pl.BlockSpec((1, 1, G, p * dv), lambda b, g, s: (b, g, 0, 0)),
                pl.BlockSpec((1, G, dk, p * dv), lambda b, g, s: (s[b], g, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, G, p * dv), lambda b, g, s: (b, g, 0, 0)),
                pl.BlockSpec((1, G, dk, p * dv), lambda b, g, s: (s[b], g, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(rows.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar prefetch; the state is operand 3, output 1
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="gdn_step_kernel",
        interpret=interpret,
    )(slots, cols, rows, state)
    return o.reshape(B, H, dv), state


def step_impl(backend: str, state: jax.Array) -> str:
    """Which implementation a step over ``state [n_slots, H / p, dk, p dv]``
    gets on ``backend``: ``"pallas"`` on a TPU where a tile is whole float32
    vregs (``dk`` a multiple of 8 sublanes, ``p dv`` of 128 lanes), else
    ``"jnp"`` (the CPU; a width no whole count of heads fills a lane row
    with). The label of the call's counter."""
    _, _, dk, pdv = state.shape
    fits = state.dtype == jnp.float32 and dk % 8 == 0 and pdv % 128 == 0
    return "pallas" if backend == "tpu" and fits else "jnp"


def gdn_step(state, slots, q, k, v, alpha, beta, fresh):
    """The step, its implementation chosen from what the call can observe
    (:func:`step_impl`) and counted under ``shape="step"``."""
    impl = step_impl(jax.default_backend(), state)
    _count_traced("step", impl)
    if impl == "pallas":
        return gdn_step_pallas(state, slots, q, k, v, alpha, beta, fresh)
    return gdn_step_jnp(state, slots, q, k, v, alpha, beta, fresh)


# -- the scan ------------------------------------------------------------------

def chunk_layout(cu_q_lens: jax.Array, slots: jax.Array, fresh: jax.Array,
                 T: int, chunk: int, garbage: int):
    """Where the rows of a flat ragged batch lie once every sequence starts
    on a chunk's edge. ``cu_q_lens [S + 1]`` (rows past the last sequence
    repeat its end), ``slots [S]``, ``fresh [S]``. ``N = ceil(T / chunk) +
    S`` chunks hold every row whatever the cut. Returns ``rows [N, chunk]``
    (the flat row at each place, ``T`` where the place is padding), ``slot
    [N]`` (the chunk's sequence's; ``garbage`` for a chunk no sequence
    uses) and ``zero [N]`` (the chunk opens a fresh sequence)."""
    S = slots.shape[0]
    N = -(-T // chunk) + S
    starts, ends = cu_q_lens[:-1], cu_q_lens[1:]
    n_chunks = (ends - starts + chunk - 1) // chunk
    chunk_end = jnp.cumsum(n_chunks)
    c = jnp.arange(N, dtype=jnp.int32)
    seq = jnp.minimum(jnp.sum(c[:, None] >= chunk_end[None, :], axis=1), S - 1)
    live = c < chunk_end[-1]
    j = c - (chunk_end - n_chunks)[seq]
    rows = (starts[seq] + j * chunk)[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    valid = live[:, None] & (rows < ends[seq][:, None])
    return (jnp.where(valid, rows, T), jnp.where(live, slots[seq], garbage),
            live & (j == 0) & fresh[seq])


# Rows a diagonal block of :func:`unit_lower_solve`.
_SOLVE_BLOCK = 16


def unit_lower_solve(A: jax.Array, rhs: jax.Array, block: int = _SOLVE_BLOCK) -> jax.Array:
    """``(I + A)^-1 rhs`` for STRICTLY lower-triangular ``A [..., C, C]`` and
    ``rhs [..., C, n]``, float32, by forward substitution in blocks of
    ``block`` rows: the diagonal blocks' inverses row by row (``block - 1``
    turns of one small batched product over every block of every chunk and
    head at once), then the blocks in order, two batched products each. The
    library's ``triangular_solve`` gives the same numbers and costs the v5e
    2.4 ms a layer at 16 chunks x 30 heads (a custom call that walks its
    ``[64, 64]`` systems one after another: 42% of a 512-row wave, PR 50);
    a Neumann product of ``(I - A)(I + A^2)(I + A^4) ..`` is faster still and
    is NOT used: with keys as alike as consecutive tokens' can be and beta
    near 2 its powers reach 1e8 before they cancel."""
    C = A.shape[-1]
    b = block if C % block == 0 else C
    nb = C // b
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    diag = jnp.stack([A[..., j * b:(j + 1) * b, j * b:(j + 1) * b] for j in range(nb)], axis=-3)

    def row(i, X):   # X_i = e_i - sum_{j<i} A_ij X_j (A_ij = 0 for j >= i)
        a = jax.lax.dynamic_index_in_dim(diag, i, axis=-2, keepdims=False)      # [..., nb, b]
        x = jax.lax.dynamic_index_in_dim(X, i, axis=-2, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            X, x - mm("...j,...jk->...k", a, X), i, axis=-2)

    X = jnp.broadcast_to(jnp.eye(b, dtype=A.dtype), diag.shape)
    X = jax.lax.fori_loop(1, b, row, X)                                       # (I + diag)^-1
    out = []
    for j in range(nb):
        R = rhs[..., j * b:(j + 1) * b, :]
        for i in range(j):
            R = R - mm("...ij,...jn->...in", A[..., j * b:(j + 1) * b, i * b:(i + 1) * b], out[i])
        out.append(mm("...ij,...jn->...in", X[..., j, :, :], R))
    return jnp.concatenate(out, axis=-2)


def gdn_scan_jnp(state, slots, fresh, q, k, v, g, beta, cu_q_lens, *,
                 chunk: int = SCAN_CHUNK):
    """The chunked gated delta rule over a flat ragged batch (``state`` in
    the slab's layout, :func:`pack_heads`). ``q``, ``k``
    ``[T, H, dk]``, ``v [T, H, dv]``, ``g [T, H]`` (``log alpha``, <= 0),
    ``beta [T, H]``, float32; ``slots [S]``, ``fresh [S]`` (the sequence's
    rows start at position 0: it reads zeros); ``cu_q_lens [S + 1]``.
    Returns ``(o [T, H, dv] float32, state)``; rows of no sequence come
    back zero.

    Within a chunk of rows ``1..C`` with incoming state ``S0``, ``G_i =
    sum_{j<=i} g_j``: ``U = (I + A)^-1 (beta V - (beta e^G K) S0)`` with ``A_ij
    = beta_i (k_i . k_j) e^{G_i - G_j}`` for ``j < i`` (the unit-lower-
    triangular solve); ``O = (e^G Q) S0 + M U`` with ``M_ij = (q_i . k_j)
    e^{G_i - G_j}`` for ``j <= i``; ``S_C = e^{G_C} S0 + (e^{G_C - G} K)^T
    U``. Every decay is the exponential of a difference <= 0, taken after
    the mask."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    p = H // state.shape[1]
    C = min(chunk, T)
    rows, slot_c, zero_c = chunk_layout(cu_q_lens, slots, fresh, T, C, state.shape[0] - 1)
    valid = rows < T
    at = jnp.minimum(rows, T - 1)

    def take(x):     # [T, H, ...] -> [N, H, C, ...], zero at padding
        x = jnp.where(valid.reshape(valid.shape + (1,) * (x.ndim - 1)), x[at], 0.0)
        return jnp.moveaxis(x, 2, 1)

    qc, kc, vc = take(q), take(k), take(v)                  # [N, H, C, d]
    bc = take(beta)                                          # [N, H, C]
    G = jnp.cumsum(take(g), axis=-1)                        # [N, H, C]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    decay = jnp.exp(jnp.where(i >= j, G[..., :, None] - G[..., None, :], -jnp.inf))
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    kk = mm("nhik,nhjk->nhij", kc, kc)
    A = jnp.where(i > j, bc[..., None] * kk * decay, 0.0)
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * eG * kc], axis=-1)
    W = unit_lower_solve(A, rhs)
    Wv, Wk = W[..., :dv], W[..., dv:]
    M = mm("nhik,nhjk->nhij", qc, kc) * decay
    Qg = eG * qc
    Kd = jnp.exp(G[..., -1:] - G)[..., None] * kc
    gC = jnp.exp(G[..., -1])                                # [N, H]

    def body(state, xs):
        slot, zero, Wv, Wk, M, Qg, Kd, gC = xs
        S0 = unpack_heads(jnp.where(zero, 0.0, state[slot]), p)     # [H, dk, dv]
        U = Wv - mm("hck,hkv->hcv", Wk, S0)
        O = mm("hck,hkv->hcv", Qg, S0) + mm("hij,hjv->hiv", M, U)
        S1 = gC[:, None, None] * S0 + mm("hck,hcv->hkv", Kd, U)
        return jax.lax.dynamic_update_index_in_dim(state, pack_heads(S1, p), slot, 0), O

    state, O = jax.lax.scan(body, state, (slot_c, zero_c, Wv, Wk, M, Qg, Kd, gC))
    o = jnp.zeros((T, H, dv), jnp.float32).at[rows.reshape(-1)].set(
        jnp.moveaxis(O, 1, 2).reshape(-1, H, dv), mode="drop")
    return o, state


def gdn_scan(state, slots, fresh, q, k, v, g, beta, cu_q_lens):
    """The scan, counted under ``shape="scan"`` (one implementation yet)."""
    _count_traced("scan", "jnp")
    return gdn_scan_jnp(state, slots, fresh, q, k, v, g, beta, cu_q_lens)


def gdn_recurrence(q, k, v, g, beta, S0=None):
    """The recurrence token by token over ONE sequence (``lax.scan`` over
    positions), from state ``S0 [H, dk, dv]`` (zeros where None): what the
    tests hold the step and the scan to. ``q``, ``k`` ``[T, H, dk]``, ``v
    [T, H, dv]``, ``g``, ``beta`` ``[T, H]``. Returns ``(o [T, H, dv], S)``."""
    H, dk = q.shape[1:]
    S0 = jnp.zeros((H, dk, v.shape[-1]), jnp.float32) if S0 is None else S0

    def body(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S, precision=_HIGHEST))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S, precision=_HIGHEST)

    S, o = jax.lax.scan(body, S0, (q, k, v, g, beta))
    return o, S
