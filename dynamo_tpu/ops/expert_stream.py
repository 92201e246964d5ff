"""A sparse layer's routed experts as ONE stream of their weights: a
decode step's every expert on every row (:func:`expert_stream`), and a
wave's, or a block model's pass's, chosen pairs alone
(:func:`expert_stream_grouped`).

**The step.** ``expert_stream(xf [N, h], w_held [N, Eh], w_gu [Eh, h, 2 im],
w_down [Eh, im, h])`` is ``sum_e w_held[:, e] x SwiGLU_e(xf)`` over EVERY held
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
last block into the next expert's first alike (:func:`_chain`). The rows,
the float32 gate/up sums, ``silu(g) * u`` (cast to the rows' dtype, as
``_swiglu`` casts it), the float32 down sums and the float32 result stay in
VMEM for the whole call. Blocking a product's K changes the order of its
float32 partial sums and nothing else.

**The chosen pairs** (PR 47). ``expert_stream_grouped(x [P, h], start [Eh],
count [Eh], w_gu, w_down)`` is ``y[start[e] + r] = SwiGLU_e(x[start[e] + r])``
for ``r < count[e]``, ``[P, h]`` float32: the rows sorted by expert
(``model._sorted_pairs``; still one XLA gather) in, one row a sorted place
out, which ``grouped_matmul.combine`` adds back a token's terms from in
ascending expert order, one order in a wave and in a step. The same chain
through the same ring, the same ``tk`` and ``ti`` (they follow the widths
alone: a row's digits depend on that row and its experts, whatever its
neighbours or the width of the call), with three differences
(:func:`_grouped_kernel`):

- the chain's turns are WORK ITEMS read from a table in SMEM (scalar
  prefetch), one touched expert and up to ``_ITEM_ROWS`` of its rows each:
  an expert no row chose is in no item, so it is neither fetched nor
  multiplied, and the chain runs from an item's last block into the next
  TOUCHED expert's first. A group of at most ``_ITEM_ROWS`` rows reads its
  weights once; a taller one once an item;
- an item's rows come by DMA into one half of a double buffer while the
  item before is multiplied, and its float32 results leave by DMA from one
  half of another while the next item is; the products run on the item's
  rows rounded up to whole 64s, in chunks of 512 / 256 / 128 / 64 rows
  (``_CHUNKS``), so a group of 70 rows costs the MXU 128 and not 512;
- **ragged ends: group starts rounded up, not masked tiles.** The caller
  puts a group's first place on a multiple of ``GROUP_ALIGN`` = 16 rows (a
  whole sublane tile of bf16, two of float32), so every copy of rows starts
  on a tile of the stored arrays and a group's chunks count from ITS first
  row: ``P`` = ``N k + 15 Eh`` places at most (SDAR's pass: 8,192 + 1,920)
  where unaligned places need ``N k``; what is COPIED is a group's rows
  rounded up to 16 (they end before the next group starts: no row of ``y``
  is written twice, and the rows of ``y`` that hold no pair are never
  written), what is MULTIPLIED its rows rounded up to 64
  (:func:`grouped_rows_visited`: under ``pairs + 64 Eh``; the library's
  aligned tiles of 128 with masked edges visit up to a tile MORE a group,
  ``grouped_matmul.rows_visited``). The rows of a chunk past the group's
  16 are whatever the buffer held: multiplied, never copied out.

One algorithm a shape, the implementation chosen from what the call can
observe (:func:`impl` for a step, :func:`grouped_impl` for a wave, as
``ops/grouped_matmul.py:impl`` and ``ops/latent_attention.py:decode_impl``
choose): the kernels on a TPU where rows and weights are one float dtype, the
widths are whole lanes and the blocks fit the kernel's VMEM (and, for a wave,
the static expected rows a held expert say its weights' bytes bind, not its
products); ``"all_rows"`` / the grouped products of ``ops/grouped_matmul.py``
elsewhere (the CPU rehearsals, int8, odd widths). The label is the one a
sparse layer's call is counted under (``grouped_matmul.count_traced``).

Why two kernels and not one whose step is the case "every held expert's
range is ``(0, N)``": a step's result is the WEIGHTED SUM over the experts,
kept in VMEM and written once (``[N, h]``); through the grouped kernel it
would be ``Eh x N`` rows of ``y`` written to HBM and gathered back (LFM2: 67
MB a layer beside 1.2 GB of weights, on the four cells whose step stands at
93-97% of the HBM rate), and the sum cannot move INTO the grouped kernel
because a wave's rows differ a group: adding a place's row to its token's is
a one-row update of an (8, 128)-tiled float32 array at a dynamic row, which
Mosaic refuses (PR 36). So they share the chain, the blocks, the ring and
the mathematics, and differ in where the rows come from and the result goes.

**An un-gated expert** (``gated=False``, ``ModelConfig.mlp_activation``
"relu2"; PR 54): ``relu(x Wu)^2 Wd``, so ``w_gu`` is ``Wu [Eh, h, im]`` alone,
the first product's sums are ``[N, im]`` and the activation is their clamped
square; the chain, the ring and the blocks are the same. Where the published
width is no whole count of 128-lane rows (nemotron_h: 1,856 = 14.5 rows) the
weights are STORED with zero columns of ``Wu`` and zero rows of ``Wd`` up to
the next whole row (1,920): ``relu(0)^2`` times a zero row adds exactly
nothing, and both kernels take the layer as it is.
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


def _gu_width(im: int, gated: bool) -> int:
    """Columns of ``w_gu[e]``: ``[Wg | Wu]`` of a gated expert, ``Wu`` alone of
    an un-gated one (``relu(x Wu)^2 Wd``)."""
    return 2 * im if gated else im


def vmem_bytes(rows: int, h: int, im: int, itemsize: int, tk: int, ti: int,
               ring: int = _RING, gated: bool = True) -> int:
    """What the kernel holds in VMEM at ``rows`` rows: both rings, the rows,
    the weights' columns, the three float32 sums, the activation, and as
    much again as the widest sum for the products' own temporaries."""
    W = _gu_width(im, gated)
    sums = rows * (W + 2 * h) * 4
    return (ring * (tk * W + ti * h) * itemsize + rows * (h + im) * itemsize
            + rows * 128 * 4 + sums + rows * max(W, h) * 4)


def _k_blocks(h: int, im: int, itemsize: int, gated: bool = True) -> tuple[int, int] | None:
    """(``tk``, ``ti``): rows of ``w_gu[e]`` and of ``w_down[e]`` a block, the
    largest whole-lane divisors of ``h`` and ``im`` under ``_BLOCK_BYTES``;
    None where a width is no multiple of 128 lanes (``im`` as STORED: an
    un-gated expert 1,856 wide is kept 1,920 wide, zeros behind it:
    ``ModelConfig.expert_stored_width``). They follow the widths alone, so
    both kernels cut a row's float32 sums alike."""
    if h % 128 or im % 128:
        return None
    return _widest(h, _gu_width(im, gated) * itemsize), _widest(im, h * itemsize)


def blocks(rows: int, h: int, im: int, itemsize: int,
           gated: bool = True) -> tuple[int, int] | None:
    """:func:`_k_blocks`' (``tk``, ``ti``), or None where there are none or
    the kernel would not fit ``_VMEM_LIMIT`` at ``rows`` rows."""
    cut = _k_blocks(h, im, itemsize, gated)
    if cut is None or vmem_bytes(rows, h, im, itemsize, *cut, gated=gated) > _VMEM_LIMIT:
        return None
    return cut


def _one_float_dtype(dtype, w_gu: jax.Array, w_down: jax.Array) -> bool:
    """Rows and weights are floats of one width the kernels take."""
    return w_gu.dtype == w_down.dtype == dtype and dtype in (jnp.bfloat16, jnp.float32)


def impl(backend: str, dtype, rows: int, w_gu: jax.Array, w_down: jax.Array,
         gated: bool = True) -> str:
    """Which implementation ``rows`` rows of ``dtype`` against every held
    expert get on ``backend``: ``"stream/pallas"`` on a TPU where rows and
    weights are floats of one width and :func:`blocks` finds a fit; else
    ``"all_rows"`` (``model._experts_all_rows``). The label of the call's
    counter."""
    fits = _one_float_dtype(dtype, w_gu, w_down) and blocks(
        rows, w_gu.shape[1], w_down.shape[1], jnp.dtype(dtype).itemsize, gated) is not None
    return "stream/pallas" if backend == "tpu" and fits else "all_rows"


def _chain(gu_hbm, down_hbm, gu_buf, down_buf, sems, *, total, expert_of, A: int, tk: int,
           ti: int):
    """(``fetch``, ``landed``) of a chain of ``total`` links, ``A`` gate/up
    slabs then ``B`` down slabs a turn of ``links = A + B``, turn ``t``'s
    from expert ``expert_of(t)``: ``fetch(link)`` asks for ``link``'s block
    into slot ``link mod R`` of the ring of its kind, if the chain has such a
    link; ``landed(buf, slot)`` waits for the block in ``slot``."""
    R = gu_buf.shape[0]
    links = A + down_hbm.shape[1] // ti

    def fetch(link):
        @pl.when(link < total)
        def _():
            e, at = expert_of(link // links), link % links
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

    return fetch, landed


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
    *, gated: bool = True,
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
    im = B * ti
    links = A + B
    total = Eh * links

    fetch, landed = _chain(gu_hbm, down_hbm, gu_buf, down_buf, sems, total=total,
                           expert_of=lambda e: e, A=A, tk=tk, ti=ti)

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
        if gated:
            act = (jax.nn.silu(gu_ref[:, :im]) * gu_ref[:, im:]).astype(act_ref.dtype)
        else:   # relu(x Wu)^2: gu_ref is [N, im]
            act = jnp.square(jnp.maximum(gu_ref[...], 0.0)).astype(act_ref.dtype)
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
@functools.partial(jax.jit, static_argnames=("tk", "ti", "ring", "interpret", "gated"))
def expert_stream(xf, w_held, w_gu, w_down, *, tk: int | None = None, ti: int | None = None,
                  ring: int = _RING, interpret: bool = False, gated: bool = True):
    """``[N, h]`` float32 (module docstring). ``tk``, ``ti``: rows of
    ``w_gu[e]`` and ``w_down[e]`` a block (:func:`blocks`' unless stated: a
    tool sweeps them); ``ring``: blocks in the ring; ``gated`` False: ``w_gu``
    is ``Wu [Eh, h, im]`` alone and an expert is ``relu(x Wu)^2 Wd``. Needs shapes
    :func:`impl` accepts; the rows are padded to whole sublane tiles."""
    N, h = xf.shape
    Eh, im = w_down.shape[:2]
    W = _gu_width(im, gated)
    if tk is None or ti is None:
        tk, ti = blocks(N, h, im, xf.dtype.itemsize, gated)
    A, B = h // tk, im // ti
    pad = (-N) % (32 // xf.dtype.itemsize)      # whole sublane tiles: 16 rows of bf16, 8 of f32
    x = jnp.pad(xf, ((0, pad), (0, 0))).reshape(N + pad, A, tk).swapaxes(0, 1)
    w = jnp.pad(w_held.astype(jnp.float32), ((0, pad), (0, 0)))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_stream_kernel, gated=gated),
        in_specs=[whole, whole, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((N + pad, h), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((ring, tk, W), w_gu.dtype),
            pltpu.VMEM((ring, ti, h), w_down.dtype),
            pltpu.SemaphoreType.DMA((ring,)),
            pltpu.VMEM((N + pad, W), jnp.float32),
            pltpu.VMEM((B, N + pad, ti), xf.dtype),
            pltpu.VMEM((N + pad, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="expert_stream_kernel",
        interpret=interpret,
    )(x, w, w_gu, w_down)
    return out[:N]


# -- a wave's (and a block pass's) routed experts: the chosen pairs alone -------

# Rows a product of the grouped kernel may run on: a work item's rows, rounded
# up to whole 64s, are multiplied in chunks of these sizes, largest first (the
# rounded count is their bit mask). A product costs about a microsecond
# whatever its rows and 0.016 more a row (a ``[M, 1024] x [1024, 1536]`` slab
# on the v5e, us: 1.22 / 1.33 / 1.65 / 2.78 / 4.93 / 8.97 at M = 16 / 32 / 64 /
# 128 / 256 / 512: PERF.md section 6, PR 47), so a chunk under 64 rows would
# save little, and each size is one more copy of both products for Mosaic to
# compile (2-3 s a size a kernel). An item's rows are COPIED in and out in
# chunks down to ``GROUP_ALIGN`` rows (a copy is a descriptor, not a product):
# what moves is the item's rows rounded up to 16, which end before the next
# group starts, so no row of ``y`` is written twice.
_CHUNKS = (512, 256, 128, 64)
_COPY_CHUNKS = (512, 256, 128, 64, 32, 16)
# Rows a work item at most: a group of no more rows has its weights read
# ONCE; a taller one is cut into items of this many and reads them once an
# item (1,024 ran 2.3 x slower on the v5e, every item of the call: PERF.md
# section 6, PR 47). Blocks in the ring at most, as the kernel's VMEM allows
# (:func:`grouped_blocks`): a deep ring lets the copies run on while a tall
# item's products hold the MXU.
_ITEM_ROWS = _CHUNKS[0]
_GROUPED_RING = 6
# What a caller rounds a slab of sorted places to (a multiple of
# ``GROUP_ALIGN``: a slab's end must not cut a copy).
SLAB_ROWS = _CHUNKS[-1]
# A group's first sorted place is a multiple of this many rows: a whole
# sublane tile of bf16 (and two of float32), so that every copy of rows the
# kernel makes starts on a tile of the stored arrays.
GROUP_ALIGN = 16
# Expected rows a held expert up to which a wave takes the grouped kernel: up
# to there a group's weights' bytes bind, past it its products do, and the
# library's grouped matmul, whose grid keeps the MXU fed tile after tile, is
# as fast or faster. Swept on the v5e with tools/experts_bench.py (PERF.md
# section 6, PR 47; even routing, ms a layer-call, library / this kernel, by
# expected rows a held expert): 64 (SDAR, 1,024 rows) 2.40 / 2.23; 128 (LFM2,
# 2,048) 3.00 / 2.30; 256: LFM2 5.43 / 4.10, SDAR 7.14 / 7.04; 512: LFM2 10.63
# / 10.61, SDAR 16.13 / **18.61**. The five sparse cells stand at 64-128.
_GROUPED_ROWS_MAX = 256


def grouped_rows_visited(count: jax.Array) -> jax.Array:
    """Rows :func:`expert_stream_grouped`'s products run on (int32 scalar):
    each group's rows rounded up to whole 64s from ITS first row (an item's
    rows are a multiple of 64, so the cuts add nothing)."""
    return (-(-count // _CHUNKS[-1]) * _CHUNKS[-1]).sum().astype(jnp.int32)


def grouped_vmem_bytes(h: int, im: int, itemsize: int, tk: int, ti: int, item_rows: int,
                       ring: int, gated: bool = True) -> int:
    """What :func:`_grouped_kernel` holds in VMEM: both rings, an item's rows
    twice (the next item's land while this one's are multiplied), its float32
    gate/up sums, its activation, its float32 results twice (the item
    before's leave while this one's are summed), and as much again as the
    widest sum for the products' own temporaries."""
    W = _gu_width(im, gated)
    return (ring * (tk * W + ti * h) * itemsize
            + item_rows * (2 * h * itemsize + W * 4 + im * itemsize + 2 * h * 4)
            + item_rows * max(W, h) * 4)


def grouped_blocks(h: int, im: int, itemsize: int, item_rows: int = _ITEM_ROWS,
                   ring: int = _GROUPED_RING,
                   gated: bool = True) -> tuple[int, int, int, int] | None:
    """(``tk``, ``ti``, rows an item, blocks in the ring) of the grouped
    kernel: :func:`blocks`' ``tk`` and ``ti`` (they follow the widths alone,
    so a row's float32 sums are cut as a step's are), the tallest item of
    ``_CHUNKS`` up to ``item_rows`` and then the deepest ring up to ``ring``
    (at least ``_RING``, or ``ring`` if less) that fit ``_VMEM_LIMIT``; None
    where a width is no multiple of 128 lanes or nothing fits."""
    cut = _k_blocks(h, im, itemsize, gated)
    if cut is None:
        return None
    tk, ti = cut
    fits = lambda t, r: grouped_vmem_bytes(h, im, itemsize, tk, ti, t, r, gated) <= _VMEM_LIMIT
    for t in (c for c in _CHUNKS if c <= item_rows):
        if fits(t, min(ring, _RING)):
            return tk, ti, t, max(r for r in range(min(ring, _RING), ring + 1) if fits(t, r))
    return None


def grouped_impl(backend: str, dtype, rows_a_group: float, w_gu: jax.Array,
                 w_down: jax.Array, gated: bool = True) -> str | None:
    """``"stream"`` where a wave's chosen pairs get :func:`expert_stream_grouped`
    on ``backend``: a TPU, rows and weights floats of one width,
    :func:`grouped_blocks` finds a fit, and a held expert is expected to hold
    at most ``_GROUPED_ROWS_MAX`` rows (``rows_a_group``: the call's rows x the
    experts a token / the experts the router chooses among, static); else None,
    and the two grouped products of ``ops/grouped_matmul.py`` (whose ``impl``
    then says which) serve the call."""
    fits = _one_float_dtype(dtype, w_gu, w_down) and grouped_blocks(
        w_gu.shape[1], w_down.shape[1], jnp.dtype(dtype).itemsize, gated=gated) is not None
    return "stream" if backend == "tpu" and fits and rows_a_group <= _GROUPED_ROWS_MAX else None


def _grouped_kernel(
    table_ref,   # SMEM [4 I + 1] i32 (scalar prefetch): the items' expert, first row, rows
                 # rounded up to 64 (multiplied) and to 16 (copied), then how many items
    x_hbm,       # HBM  [P, h] — the sorted rows
    gu_hbm,      # HBM  [Eh, h, 2 im]
    down_hbm,    # HBM  [Eh, im, h]
    y_hbm,       # HBM  [P, h] f32 — a sorted place's SwiGLU by its expert
    x_buf,       # VMEM [2, T, h] — an item's rows, and the next item's
    gu_buf,      # VMEM [R, tk, 2 im]
    down_buf,    # VMEM [R, ti, h]
    sems,        # DMA semaphores [R], one a slot of the rings
    x_sems,      # DMA semaphores [2], one a half of ``x_buf``
    y_sems,      # DMA semaphores [2], one a half of ``y_ref``
    gu_ref,      # VMEM [T, 2 im] f32
    act_ref,     # VMEM [T, im]
    y_ref,       # VMEM [2, T, h] f32 — an item's results, and the item before's on their way out
    *, tk: int, ti: int, gated: bool = True,
):
    """A work ITEM is one touched expert and up to ``T`` of its rows. The
    items' blocks of weights are the links of ONE chain, as
    :func:`_stream_kernel`'s are: item ``i``'s ``A`` gate/up slabs, its ``B``
    down slabs, then item ``i + 1``'s, whichever expert that is. An item's
    rows are copied in, multiplied and copied out in the CHUNKS its rounded
    counts of rows name (the sum of a set of powers of two is their bit
    mask), each a static size at a row offset that is the sum of the larger
    ones."""
    _, T, h = x_buf.shape
    R = gu_buf.shape[0]
    im = act_ref.shape[1]
    A, B = h // tk, im // ti
    links = A + B
    I = (table_ref.shape[0] - 1) // 4
    n_items = table_ref[4 * I]
    fetch, landed = _chain(gu_hbm, down_hbm, gu_buf, down_buf, sems, total=n_items * links,
                           expert_of=lambda item: table_ref[item], A=A, tk=tk, ti=ti)

    def each_chunk(item, body, copied=False):
        """``body(rows of the item's buffers, the same rows of x and y)`` for
        each chunk of ``item``'s rows as they are multiplied, or ``copied``."""
        plan, first = table_ref[(3 if copied else 2) * I + item], table_ref[I + item]
        at = jnp.int32(0)
        # (no copy taller than ``x`` itself: its size is static)
        for size in (c for c in (_COPY_CHUNKS if copied else _CHUNKS)
                     if c <= (min(T, x_hbm.shape[0]) if copied else T)):
            @pl.when(plan & size != 0)
            def _(at=at, size=size):
                body(pl.ds(pl.multiple_of(at, GROUP_ALIGN), size),
                     pl.ds(pl.multiple_of(first + at, GROUP_ALIGN), size))
            at = at + (plan & size)

    def rows_in(item, rows, places):
        return pltpu.make_async_copy(
            x_hbm.at[places], x_buf.at[item % 2, rows], x_sems.at[item % 2])

    def results_out(item, rows, places):
        return pltpu.make_async_copy(
            y_ref.at[item % 2, rows], y_hbm.at[places], y_sems.at[item % 2])

    def fetch_rows(item):
        @pl.when(item < n_items)
        def _():
            each_chunk(item, lambda *at: rows_in(item, *at).start(), copied=True)

    def results_left(item):
        each_chunk(item, lambda *at: results_out(item, *at).wait(), copied=True)

    for link in range(R - 1):
        fetch(jnp.int32(link))
    fetch_rows(jnp.int32(0))

    def item(i, _):
        first = i * links
        half = i % 2
        fetch_rows(i + 1)
        each_chunk(i, lambda *at: rows_in(i, *at).wait(), copied=True)

        def zero(rows, places):
            gu_ref[rows] = jnp.zeros((rows.size, gu_ref.shape[1]), jnp.float32)

        each_chunk(i, zero)

        def gate_up(j, _):
            slot = (first + j) % R
            fetch(first + j + R - 1)
            landed(gu_buf, slot)

            def product(rows, places):
                gu_ref[rows] += jnp.dot(
                    x_buf[half, rows, pl.ds(pl.multiple_of(j * tk, tk), tk)], gu_buf[slot],
                    preferred_element_type=jnp.float32)

            each_chunk(i, product)

        jax.lax.fori_loop(0, A, gate_up, None)

        # this half of ``y_ref`` held the results of the item before the last: gone by now
        @pl.when(i > 1)
        def _():
            results_left(i - 2)

        def activate(rows, places):
            if gated:
                act_ref[rows] = (jax.nn.silu(gu_ref[rows, :im]) * gu_ref[rows, im:]).astype(
                    act_ref.dtype)
            else:   # relu(x Wu)^2: gu_ref is [T, im]
                act_ref[rows] = jnp.square(jnp.maximum(gu_ref[rows], 0.0)).astype(
                    act_ref.dtype)
            y_ref[half, rows] = jnp.zeros((rows.size, h), jnp.float32)

        each_chunk(i, activate)

        def down(b, _):
            slot = (first + A + b) % R
            fetch(first + A + b + R - 1)
            landed(down_buf, slot)

            def product(rows, places):
                y_ref[half, rows] += jnp.dot(
                    act_ref[rows, pl.ds(pl.multiple_of(b * ti, ti), ti)], down_buf[slot],
                    preferred_element_type=jnp.float32)

            each_chunk(i, product)

        jax.lax.fori_loop(0, B, down, None)

        each_chunk(i, lambda *at: results_out(i, *at).start(), copied=True)

    jax.lax.fori_loop(0, n_items, item, None)

    @pl.when(n_items > 1)
    def _():
        results_left(n_items - 2)

    @pl.when(n_items > 0)
    def _():
        results_left(n_items - 1)


def _items(start, count, slots: int, item_rows: int):
    """The kernel's table (``[4 slots + 1]`` int32): each work item's expert,
    first sorted row and rows (rounded up to whole 64s, and to whole
    ``GROUP_ALIGN``s), touched experts in ascending order and an expert's
    items in the order of its rows, then the count of items. Slots past it
    are not read."""
    n_e = -(-count // item_rows)
    end = jnp.cumsum(n_e)
    at = jnp.arange(slots, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(at[:, None] >= end[None, :], axis=1, dtype=jnp.int32),
                    count.shape[0] - 1)
    t = at - (end - n_e)[e]
    rows = jnp.clip(count[e] - t * item_rows, 0, item_rows)
    up = lambda to: -(-rows // to) * to
    return jnp.concatenate(
        [e, start[e] + t * item_rows, up(_CHUNKS[-1]), up(GROUP_ALIGN), end[-1:]])


@functools.partial(jax.jit, static_argnames=(
    "tk", "ti", "ring", "item_rows", "interpret", "gated"))
def expert_stream_grouped(x, start, count, w_gu, w_down, *, tk: int | None = None,
                          ti: int | None = None, ring: int = _GROUPED_RING,
                          item_rows: int = _ITEM_ROWS, interpret: bool = False,
                          gated: bool = True):
    """``y [P, h]`` float32 with ``y[start[e] + r] = SwiGLU_e(x[start[e] + r])``
    (``relu(x Wu)^2 Wd`` where not ``gated``: :func:`expert_stream`'s)
    for ``r < count[e]``, every held expert ``e`` (module docstring); what the
    other rows of ``y`` hold is unspecified (they are never written). ``start``
    ascends with ``e``, each a multiple of ``GROUP_ALIGN`` as ``P`` is, the groups
    apart. ``item_rows`` and ``ring`` are upper bounds (:func:`grouped_blocks` fits
    them); they, ``tk`` and ``ti`` are the module's own unless a tool sweeps them."""
    P, h = x.shape
    Eh, im = w_down.shape[:2]
    fit = grouped_blocks(h, im, x.dtype.itemsize, item_rows, ring, gated)
    item_rows, ring = fit[2:]
    W = _gu_width(im, gated)
    if tk is None or ti is None:
        tk, ti = fit[:2]
    table = _items(start.astype(jnp.int32), count.astype(jnp.int32),
                   Eh + P // item_rows, item_rows)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, tk=tk, ti=ti, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[hbm, hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((2, item_rows, h), x.dtype),
                pltpu.VMEM((ring, tk, W), w_gu.dtype),
                pltpu.VMEM((ring, ti, h), w_down.dtype),
                pltpu.SemaphoreType.DMA((ring,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((item_rows, W), jnp.float32),
                pltpu.VMEM((item_rows, im), x.dtype),
                pltpu.VMEM((2, item_rows, h), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((P, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name="expert_stream_grouped_kernel",
        interpret=interpret,
    )(table, x, w_gu, w_down)
