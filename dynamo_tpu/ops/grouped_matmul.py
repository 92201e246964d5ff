"""A grouped matrix product: rows sorted by group, one weight a group.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])`` multiplies
rows ``offset[g] : offset[g] + group_sizes[g]`` of ``lhs`` by ``rhs[g]``
(``offset`` = the exclusive ``cumsum`` of ``group_sizes``), float32 out.
Rows past ``sum(group_sizes)`` belong to no group and are NOT computed:
what the result holds there is unspecified, and the caller masks it.

It is what a sparse MLP's prefill wave needs (``model._experts_grouped``):
the chosen (row, expert) pairs sorted by expert, every held expert's
weights read once whatever the wave's width, and only the tiles of rows
that hold a pair multiplied. Since PR 47 a wave whose groups are bound by
their weights' bytes (every cell's, today) takes the whole layer as ONE
kernel instead (``ops/expert_stream.py:expert_stream_grouped``); the two
products here serve the calls that kernel does not take (groups many tiles
tall, the CPU, int8, odd widths), and :func:`combine` and the counter serve
both.

One algorithm, the implementation chosen from what the call can observe
(:func:`impl`, as ``ops/latent_attention.py:decode_impl`` chooses):

- ``"pallas"`` on a TPU: the Pallas grouped matmul that ships with JAX
  (``jax.experimental.pallas.ops.tpu.megablox.gmm``). Its grid walks the
  (group, row tile) pairs that hold a row, ``K`` whole in one block, so a
  group's ``[K, tn]`` block of weights stays in VMEM while the group's row
  tiles pass and the weights are read once (:func:`tiling`).
- ``"ragged_dot"`` elsewhere (the CPU; operands the kernel's tiles do not
  divide): ``jax.lax.ragged_dot``, XLA's own.

The path a sparse layer got is counted at trace time, where the choice
is made (:func:`count_traced`; ``dynamo_engine_expert_calls_traced_total``
on /metrics).
"""

from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp

# Rows a tile of the Pallas kernel: the MXU's height. A group's rows fill
# whole tiles but for its two ends, so the kernel multiplies under
# ``pairs + 2 x G x _TILE_ROWS`` rows (:func:`rows_visited`).
_TILE_ROWS = 128
# Bytes of VMEM the kernel's blocks may take, double-buffered, under the
# compiler's scoped default of 16 MiB on a v5e (the library call takes no
# compiler parameter to raise it).
_VMEM_BUDGET = 12 * 2 ** 20


def tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int] | None:
    """(rows, ``K``, columns) a block of the Pallas kernel, or None where
    none fits: ``K`` WHOLE, so that consecutive row tiles of a group find
    their weights' block unchanged and it is not fetched again, and the
    widest column block (a multiple of 128 that divides ``n``) whose
    double-buffered operands and float32 result fit ``_VMEM_BUDGET``: the
    rows are read once a column block."""
    if k % 128 or n % 128:
        return None
    tm = _TILE_ROWS
    for parts in range(1, n // 128 + 1):
        if n % parts or (n // parts) % 128:
            continue
        tn = n // parts
        if 2 * (tm * k + k * tn) * itemsize + 3 * tm * tn * 4 <= _VMEM_BUDGET:
            return tm, k, tn
    return None


def impl(backend: str, dtype, *weights: jax.Array) -> str:
    """Which implementation the products of rows of ``dtype`` by each of
    ``weights`` ``[G, K, N]`` get on ``backend``: ``"pallas"`` on a TPU
    where rows and weights are floats of one width and a tiling fits each
    product (the caller pads its rows to :func:`tile_rows`); else
    ``"ragged_dot"``. The label of the call's counter."""
    fits = all(
        w.dtype == dtype and dtype in (jnp.bfloat16, jnp.float32)
        and tiling(w.shape[1], w.shape[2], w.dtype.itemsize) is not None
        for w in weights
    )
    return "pallas" if backend == "tpu" and fits else "ragged_dot"


def tile_rows(impl: str) -> int:
    """Rows the implementation multiplies at a time: what a caller pads
    its sorted rows to, and the grain of :func:`rows_visited`."""
    return _TILE_ROWS if impl == "pallas" else 1


def rows_visited(group_sizes: jax.Array, tile: int) -> jax.Array:
    """Rows the product runs on (int32 scalar): the rows of every (group,
    ``tile``-row tile) pair that holds a row of the group. ``tile`` 1:
    ``sum(group_sizes)``."""
    end = jnp.cumsum(group_sizes)
    start = end - group_sizes
    tiles = jnp.where(group_sizes > 0, (end - 1) // tile - start // tile + 1, 0)
    return (jnp.sum(tiles) * tile).astype(jnp.int32)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, impl: str) -> jax.Array:
    """``[M, N]`` float32 (module docstring). ``impl`` is :func:`impl`'s
    answer, stated by the caller so that a tool can time the other one."""
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(lhs, rhs, group_sizes, jnp.float32,
               tiling(rhs.shape[1], rhs.shape[2], rhs.dtype.itemsize))


# -- the way back: sorted places to rows ----------------------------------------

def combine(out: jax.Array, y: jax.Array, place: jax.Array, weight: jax.Array, *,
            full: bool) -> jax.Array:
    """``out[n] + sum_j weight[n, j] x y[place[n, j]]`` ``[N, h]`` float32,
    the terms of a row added in the order of ``j`` and one at a time (the
    caller lists a row's places in ascending group order, so a row's sum
    has ONE order whatever the width of the call). A place ``>= len(y)``
    is no place: its term is left out, not multiplied by zero (what ``y``
    holds past the groups is unspecified). One gather of ``[N, h]`` a
    ``j``. ``full``: every row holds all its ``k`` places (a chip that
    holds every expert), so the passes are unrolled and fuse; else a loop
    over the ``j`` at which some row holds a place: a chip that holds few
    of the router's experts (A.X-K1: 12 of 192, at most 8 a row and seldom
    more than 4) skips the passes no row needs. On the v5e (PERF.md section
    6, PR 36, calls p1, p3; ms a layer at 2,048 rows, unrolled / loop):
    LFM2 3.06 / 3.34, A.X-K1 5.90 / 3.83."""
    def add(j, out):
        at = jax.lax.dynamic_index_in_dim(place, j, axis=1, keepdims=False)
        w = jax.lax.dynamic_index_in_dim(weight, j, axis=1)
        term = w * y[jnp.minimum(at, y.shape[0] - 1)]
        return out + jnp.where(at[:, None] < y.shape[0], term, 0.0)

    if full:
        for j in range(place.shape[1]):
            out = add(j, out)
        return out
    held = jnp.any(place < y.shape[0], axis=0)              # [k]: some row holds a place at j
    js = jnp.arange(place.shape[1])
    return jax.lax.fori_loop(jnp.min(jnp.where(held, js, place.shape[1])),
                             jnp.max(jnp.where(held, js + 1, 0)), add, out)


# Sparse layers' expert calls traced since the process started, by the
# shape of the call ("wave": more rows than every-expert-on-every-row
# serves, a prefill wave; "step": a decode step's rows) and the path it
# got ("grouped/stream", ``ops/expert_stream.py``'s grouped kernel,
# "grouped/pallas" or "grouped/ragged_dot" for a wave; for a step
# "stream/pallas", ``ops/expert_stream.py``'s step kernel, or "all_rows", the
# loop of XLA products: every held expert on every row either way). Static
# per compiled program, so counted at trace time, as
# ``ops/ragged_attention.py`` counts the attention calls.
_TRACED: collections.Counter = collections.Counter()
_TRACED_LOCK = threading.Lock()


def count_traced(shape: str, impl: str) -> None:
    with _TRACED_LOCK:
        _TRACED[shape, impl] += 1


def traced_calls() -> dict[tuple[str, str], int]:
    """``{(shape, impl): calls traced}``."""
    with _TRACED_LOCK:
        return dict(_TRACED)


def traced_impl(shape: str) -> str:
    """The path(s) this process's programs got for ``shape`` (``+``-joined
    if more than one; empty before any was traced)."""
    with _TRACED_LOCK:
        return "+".join(sorted(i for (s, i), n in _TRACED.items() if s == shape and n))
