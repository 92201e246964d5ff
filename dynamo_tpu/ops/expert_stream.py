"""A decode step's routed experts as ONE stream of their weights.

``expert_stream(xf [N, h], w_held [N, Eh], w_gu [Eh, h, 2 im], w_down
[Eh, im, h])`` is ``sum_e w_held[:, e] x SwiGLU_e(xf)`` over EVERY held
expert on EVERY row, ``[N, h]`` float32: the mathematics, the operand
widths and the order of the experts' terms of ``model._experts_all_rows``,
which stays the definition (and the path of every backend but a TPU).

What differs is how the bytes arrive. The loop of XLA products reads an
expert's ``[h, 2 im]`` and ``[im, h]`` as two products whose pipelines fill
and drain each time: a 12.6 MB product at 128 rows reaches ~70% of a v5e's
HBM rate, an 88 MB one ~87% (PERF.md section 6, PRs 35-36). Here the two
arrays stay where they are in HBM and one Pallas TPU kernel
(:func:`_stream_kernel`) walks them as a single chain of blocks, whole rows
of the stored arrays (``[tk, 2 im]`` slabs of ``w_gu[e]``, then ``[ti, h]``
slabs of ``w_down[e]``: contiguous), each byte read once, through a ring of
VMEM buffers whose copies are asked for ``ring - 1`` blocks ahead of the
product that uses them, from an expert's gate/up into its down and from its
last block into the next expert's first alike. The rows, the float32
gate/up sums, ``silu(g) * u`` (cast to the rows' dtype, as ``_swiglu``
casts it), the float32 down sums and the float32 result stay in VMEM for
the whole call. Blocking a product's K changes the order of its float32
partial sums and nothing else.

One algorithm, the implementation chosen from what the call can observe
(:func:`impl`, as ``ops/grouped_matmul.py:impl`` and
``ops/latent_attention.py:decode_impl`` choose): ``"stream/pallas"`` on a
TPU where rows and weights are one float dtype, the widths are whole lanes
and the blocks fit the kernel's VMEM; ``"all_rows"`` elsewhere (the CPU
rehearsals, int8, odd widths). The label is the one a sparse layer's call
is counted under (``grouped_matmul.count_traced``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes a block of weights at most: the grain of the chain. Swept on the
# v5e with tools/experts_bench.py (PERF.md section 6, PR 38).
_BLOCK_BYTES = 4 * 2 ** 20
# Blocks in the ring: ``_RING - 1`` are in flight while one is multiplied.
_RING = 3
# VMEM the kernel may take (a v5e has 128 MiB; the compiler's scoped
# default of 16 MiB does not hold A.X-K1's 4 MB blocks three deep beside
# its rows): the limit handed to Mosaic, and what :func:`blocks` fits.
_VMEM_LIMIT = 96 * 2 ** 20


def _widest(n: int, row_bytes: int) -> int:
    """The largest multiple of 128 that divides ``n`` and whose rows hold
    at most ``_BLOCK_BYTES`` (128 where none does)."""
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and t * row_bytes <= _BLOCK_BYTES]
    return max(fits, default=128)


def vmem_bytes(rows: int, h: int, im: int, itemsize: int, tk: int, ti: int,
               ring: int = _RING) -> int:
    """What the kernel holds in VMEM at ``rows`` rows: both rings, the rows,
    the weights' columns, the three float32 sums, the activation, and as
    much again as the widest sum for the products' own temporaries."""
    sums = rows * (2 * im + 2 * h) * 4
    return (ring * (tk * 2 * im + ti * h) * itemsize + rows * (h + im) * itemsize
            + rows * 128 * 4 + sums + rows * max(2 * im, h) * 4)


def blocks(rows: int, h: int, im: int, itemsize: int) -> tuple[int, int] | None:
    """(``tk``, ``ti``): rows of ``w_gu[e]`` and of ``w_down[e]`` a block, the
    largest whole-lane divisors of ``h`` and ``im`` under ``_BLOCK_BYTES``;
    None where a width is no multiple of 128 lanes or the kernel would not
    fit ``_VMEM_LIMIT`` at ``rows`` rows."""
    if h % 128 or im % 128:
        return None
    tk, ti = _widest(h, 2 * im * itemsize), _widest(im, h * itemsize)
    if vmem_bytes(rows, h, im, itemsize, tk, ti) > _VMEM_LIMIT:
        return None
    return tk, ti


def impl(backend: str, dtype, rows: int, w_gu: jax.Array, w_down: jax.Array) -> str:
    """Which implementation ``rows`` rows of ``dtype`` against every held
    expert get on ``backend``: ``"stream/pallas"`` on a TPU where rows and
    weights are floats of one width and :func:`blocks` finds a fit; else
    ``"all_rows"`` (``model._experts_all_rows``). The label of the call's
    counter."""
    fits = (
        w_gu.dtype == w_down.dtype == dtype and dtype in (jnp.bfloat16, jnp.float32)
        and blocks(rows, w_gu.shape[1], w_down.shape[1], jnp.dtype(dtype).itemsize) is not None
    )
    return "stream/pallas" if backend == "tpu" and fits else "all_rows"


def _stream_kernel(
    x_ref,       # VMEM [A, N, tk] — the rows, a K slab of gate/up a leading index
    w_ref,       # VMEM [N, Eh] f32 — a row's weight on each held expert
    gu_hbm,      # HBM  [Eh, h, 2 im]
    down_hbm,    # HBM  [Eh, im, h]
    out_ref,     # VMEM [N, h] f32
    gu_buf,      # VMEM [R, tk, 2 im] — the ring's slots where a link is gate/up's
    down_buf,    # VMEM [R, ti, h]    — ... and where it is down's
    sems,        # DMA semaphores [R], one a slot
    gu_ref,      # VMEM [N, 2 im] f32 — x Wgu, summed over the K slabs
    act_ref,     # VMEM [B, N, ti] — silu(g) * u, a K slab of down a leading index
    y_ref,       # VMEM [N, h] f32 — act Wdown, summed over the K slabs
):
    """Every block of every held expert is one LINK of a chain: expert
    ``e``'s ``A = h / tk`` gate/up slabs, then its ``B = im / ti`` down
    slabs, then expert ``e + 1``'s. Link ``L`` lands in slot ``L mod R`` of
    the ring of its kind and is asked for when link ``L - (R - 1)`` is about
    to be multiplied, into the slot link ``L - R`` has left, so ``R - 1``
    copies are in flight whatever a product takes and the chain does not
    drain between an expert's two products nor between two experts."""
    A, N, tk = x_ref.shape
    B, _, ti = act_ref.shape
    R, Eh = gu_buf.shape[0], gu_hbm.shape[0]
    im = gu_buf.shape[2] // 2
    links = A + B
    total = Eh * links

    def fetch(link):
        """Ask for ``link``'s block, if the chain has one."""
        @pl.when(link < total)
        def _():
            e, at = link // links, link % links
            slot = link % R

            @pl.when(at < A)
            def _():
                pltpu.make_async_copy(
                    gu_hbm.at[e, pl.ds(pl.multiple_of(at * tk, tk), tk)],
                    gu_buf.at[slot], sems.at[slot]).start()

            @pl.when(at >= A)
            def _():
                pltpu.make_async_copy(
                    down_hbm.at[e, pl.ds(pl.multiple_of((at - A) * ti, ti), ti)],
                    down_buf.at[slot], sems.at[slot]).start()

    def landed(buf, slot):
        # (a wait reads its descriptor's size alone)
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot]).wait()

    for link in range(R - 1):
        fetch(jnp.int32(link))
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    column = jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 1)

    def expert(e, _):
        first = e * links

        def gate_up(j, _):
            link = first + j
            slot = link % R
            fetch(link + R - 1)
            landed(gu_buf, slot)
            gu_ref[...] += jnp.dot(x_ref[j], gu_buf[slot], preferred_element_type=jnp.float32)

        gu_ref[...] = jnp.zeros(gu_ref.shape, gu_ref.dtype)
        jax.lax.fori_loop(0, A, gate_up, None)
        act = (jax.nn.silu(gu_ref[:, :im]) * gu_ref[:, im:]).astype(act_ref.dtype)
        for b in range(B):
            act_ref[b] = act[:, b * ti:(b + 1) * ti]

        def down(b, _):
            link = first + A + b
            slot = link % R
            fetch(link + R - 1)
            landed(down_buf, slot)
            y_ref[...] += jnp.dot(act_ref[b], down_buf[slot], preferred_element_type=jnp.float32)

        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
        jax.lax.fori_loop(0, B, down, None)
        # column e of the weights: the one lane that is e's, the others exact zeros
        w = jnp.sum(jnp.where(column == e, w_ref[...], 0.0), axis=1, keepdims=True)
        out_ref[...] += w * y_ref[...]

    jax.lax.fori_loop(0, Eh, expert, None)


# jitted so that a program's sparse layers, and every program of a width,
# share ONE trace and lowering of the kernel
@functools.partial(jax.jit, static_argnames=("tk", "ti", "ring", "interpret"))
def expert_stream(xf, w_held, w_gu, w_down, *, tk: int | None = None, ti: int | None = None,
                  ring: int = _RING, interpret: bool = False):
    """``[N, h]`` float32 (module docstring). ``tk``, ``ti``: rows of
    ``w_gu[e]`` and ``w_down[e]`` a block (:func:`blocks`' unless stated: a
    tool sweeps them); ``ring``: blocks in the ring. Needs shapes
    :func:`impl` accepts; the rows are padded to whole sublane tiles."""
    N, h = xf.shape
    Eh, im = w_down.shape[:2]
    if tk is None or ti is None:
        tk, ti = blocks(N, h, im, xf.dtype.itemsize)
    A, B = h // tk, im // ti
    pad = (-N) % (32 // xf.dtype.itemsize)      # whole sublane tiles: 16 rows of bf16, 8 of f32
    x = jnp.pad(xf, ((0, pad), (0, 0))).reshape(N + pad, A, tk).swapaxes(0, 1)
    w = jnp.pad(w_held.astype(jnp.float32), ((0, pad), (0, 0)))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _stream_kernel,
        in_specs=[whole, whole, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((N + pad, h), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((ring, tk, 2 * im), w_gu.dtype),
            pltpu.VMEM((ring, ti, h), w_down.dtype),
            pltpu.SemaphoreType.DMA((ring,)),
            pltpu.VMEM((N + pad, 2 * im), jnp.float32),
            pltpu.VMEM((B, N + pad, ti), xf.dtype),
            pltpu.VMEM((N + pad, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="expert_stream_kernel",
        interpret=interpret,
    )(x, w, w_gu, w_down)
    return out[:N]
