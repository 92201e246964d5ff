"""Decode attention of MULTI-HEAD layers (a GQA group of one: every query
head has a KV head of its own) over the plain page, as one first-party Pallas
TPU kernel. ops/ragged_attention.py takes it for a decode-shaped call where
:func:`fits` holds and keeps the library kernel everywhere else.

Why a kernel of its own: at group 1 a KV head's pass of the library kernel is
ONE query row against the block, its K and V converted to float32 and
multiplied in float32, every block masked and divided. Here the page is read
AS IT IS (``[n_pages, page_size, 2 n, 128]``, K even and V odd on the
combined-head axis: the waves, the writer, the prefix cache and the transfer
format keep their page), by the recipe of the other first-party decode
kernels (ops/page_ring.py): tables and lengths by scalar prefetch, pages by
DMA into a ring of VMEM blocks, one chain of (lane, block) links across
lanes and grid steps.

**The products.** In a bfloat16 page the rows ``2 h`` and ``2 h + 1`` of a
token (head ``h``'s key and value) share the 32-bit words of one sublane, so
one vreg of the page AS IT LIES, read as words, is a token's keys and values
of an OCTET of heads (heads ``8 o .. 8 o + 7``, a head a sublane). Two
tokens' vregs are repacked by two shifts and masks into one bfloat16 key
tile and one value tile of 16 rows (head ``i`` of token ``a`` in row ``2 i``,
of token ``b`` in row ``2 i + 1``). No load is strided: a strided sublane
load of one head's rows out of the interleaved block reads every row from
one bank of VMEM and held the first build at 43% of Olmo's bytes, 2,862 us
where the page's plain loads read 1,421 (PERF.md section 6, PR 51).

K and V go to the MXU as bfloat16 with float32 sums, an octet at a time:
the octet's eight queries against the key rows of 16 tokens x 8 heads (a
weight tile of 128 rows) give ``[8, 128]`` scores of which a head's OWN
columns are kept (a select and one sublane sum a tile), so a block's scores
are ``[tokens / 16, 128]`` a octet, a head a column: dense vregs for the
softmax's maximum, exponential and sum (a head's 16 columns of a row are
combined by lane rolls). The weights go back on their head's row (a select
against the same pattern) for the product with the value rows, whose result
is ``[8, 128]``: a head a row, as the output wants it. The order of tokens
inside a tile is the repack's, the same for keys and values, and the mask
of a lane's last block follows it. Running maximum, denominator and
accumulator float32, the mask in a lane's last block only, one divide a
lane. Every byte of K/V passes the MXU once as weights, the ratio at which
the wide-key kernel reads 88% of the HBM rate. A page that keeps more KV
heads than the model has (Olmo-Hybrid: 30 in 32) is told so (``q`` has the
published heads): the spare heads' queries are zeros in VMEM and their rows
of the result are never written out.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.page_ring import page_chain, start_chain
from dynamo_tpu.ops.ragged_attention import _NEG_INF

_LANES = 128
_LOW, _HIGH = np.uint32(0xFFFF), np.uint32(0xFFFF0000)   # the halves of a word
# Tokens the kernel computes on at a time: a whole number of pages that fill
# the lanes of a row of scores. A lane's last block is computed on as many of
# them as hold its pages.
_GRANULE_TOKENS = 128
# Pages a KV block of the ring, in bytes, and blocks in the ring: constants of
# the shape (the sweep passes its own), swept on the v5e by
# tools/attn_decode_bench.py --mha-pages (PERF.md section 5, PR 51).
_KERNEL_BLOCK_BYTES = 2048 * 1024
_KERNEL_BLOCKS_IN_RING = 3


def granule_pages(page_size: int) -> int:
    return max(1, _GRANULE_TOKENS // page_size)


def fits(backend: str, q: jax.Array, kv_pages: jax.Array) -> bool:
    """Whether a decode-shaped call of ``q [B, H, d]`` over ``kv_pages``
    takes this kernel, from what the call can observe: a TPU, one KV head a
    query head among the heads the page keeps (``H`` at most the page's:
    the caller knows that they are the model's KV heads and no group),
    128-wide heads, bfloat16 pages whose token is whole octets of heads (16
    combined rows, a sublane tile) and whose page is whole tiles of tokens
    that fill or divide a granule."""
    ps, comb, d = kv_pages.shape[1:]
    return (backend == "tpu" and d == _LANES and kv_pages.dtype == jnp.bfloat16
            and q.dtype == jnp.bfloat16 and comb % 16 == 0
            and q.shape[1] <= comb // 2
            and ps % 16 == 0 and (_GRANULE_TOKENS % ps == 0 or ps % _GRANULE_TOKENS == 0))


def _decode_kernel(
    lens_ref,      # SMEM [B] i32 (scalar prefetch)
    tables_ref,    # SMEM [B * width] i32 (scalar prefetch), lane after lane
    live_ref,      # SMEM [1] i32 (scalar prefetch) — the live lanes come first
    q_ref,         # VMEM [1, H, 128] — this grid step's lane, as the program holds it
    pages_ref,     # HBM  [n_pages, ps, 2 Hc, 128] — the cache as it is
    out_ref,       # VMEM [1, H, 128]
    buf,           # VMEM [K, N, ps, 2 Hc, 128] — a ring of K KV blocks of N pages
    sems,          # DMA semaphores [K], one a buffer
    ring_ref,      # SMEM [4] — the ring's state from one grid step to the next
    q8_ref,        # VMEM [Hc, 128] f32 — the lane's queries, zeros for heads the model lacks
    s_ref,         # VMEM [O, N ps / 16, 128] f32 — a block's scores, then its weights
    m_ref, l_ref,  # VMEM [O, 8, 128] f32 — running maximum and sum, a head a column (row 0)
    acc_ref,       # VMEM [Hc, 128] f32 — a head a row
    *, sm_scale: float, width: int, interpret: bool,
):
    """A grid step is one lane, which walks its own KV blocks through the
    ring (ops/page_ring.py). A block is computed on an OCTET of heads at a
    time (the eight heads whose words share a vreg of a token), module
    docstring, "The products"."""
    K, N, ps = buf.shape[:3]
    H = q_ref.shape[1]
    Hc = buf.shape[3] // 2                # KV heads the page keeps
    O = Hc // 8                           # octets of them
    G = granule_pages(ps)
    span = N * ps
    lane, B = pl.program_id(0), pl.num_programs(0)
    n_live = jnp.clip(live_ref[0], 0, B)

    def words(slot, p, o):
        """``[ps, 8, 128]`` u32: (key | value << 16) of octet ``o``'s heads, a
        head a sublane, for every token of page ``p`` of buffer ``slot``: the
        page's own vregs, as they lie."""
        if interpret:   # Pallas' interpreter reads no bitcast ref: the words from their halves
            x = jax.lax.bitcast_convert_type(
                buf[slot, p, :, pl.ds(16 * o, 16), :], jnp.uint16).astype(jnp.uint32)
            return x[:, 0::2] | (x[:, 1::2] << 16)
        return buf.bitcast(jnp.uint32)[slot, p, :, pl.ds(8 * o, 8), :]

    def tiles(x, keys: bool):
        """A page's words ``[ps, 8, 128]`` as bfloat16 rows ``[8 ps, 128]``,
        the keys or the values: 128 rows a tile of 16 tokens, token ``v`` of
        the tile beside token ``8 + v`` in the words of vreg ``v``."""
        x = x.reshape(ps // 16, 2, 8, 8, _LANES)
        a, b = x[:, 0], x[:, 1]
        w = (a & _LOW) | (b << 16) if keys else (a >> 16) | (b & _HIGH)
        return pltpu.bitcast(w.reshape(ps * 4, _LANES), buf.dtype)

    def tokens(lane):
        return jnp.maximum(lens_ref[lane], 1)

    each_page, fetch = page_chain(tokens, tables_ref, pages_ref, buf, sems, width=width,
                                  page_tokens=ps, lanes=n_live)

    @pl.when(lane == 0)
    def _():
        start_chain(fetch, buf, ring_ref)
        q8_ref[...] = jnp.zeros(q8_ref.shape, jnp.float32)

    nt = (((1,), (1,)), ((), ()))      # q . k^T: both contract their lanes
    # Column c of a tile's scores is row c of its key tile: word c // 2 of
    # vreg c // 16, so head (c % 16) // 2 of the octet; the low half of a
    # word is token c // 16 of the tile's 16, the high half token 8 + c // 16.
    col = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1)
    own = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0) == (col % 16) // 2
    even = col[:1] % 2 == 0

    def per_head(x, op):
        """``x [1, 128]``, a head a column: ``op`` over each head's 16
        columns, on every one of them."""
        x = op(x, jnp.where(even, pltpu.roll(x, _LANES - 1, 1), pltpu.roll(x, 1, 1)))
        for by in (16, 32, 64):
            x = op(x, pltpu.roll(x, by, 1))
        return x

    def by_row(x):
        """``x [1, 128]`` a head a column (non-negative) -> ``[8, 1]`` a head a row."""
        return jnp.max(jnp.where(own, jnp.broadcast_to(x, own.shape), 0.0), axis=1, keepdims=True)

    def attend(i, slot, n: int, masked: bool, n_tok):
        """Block ``i`` of this lane, the first ``n`` pages of buffer
        ``slot``, into the running max, sum and accumulator."""
        T = n * ps
        R = T // 16                        # tiles of 16 tokens: rows of the octet's scores
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, (R, _LANES), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (R, _LANES), 1)
            live = i * span + 16 * row + c // 16 + 8 * (c % 2) < n_tok
        for o in range(O):
            k = jnp.concatenate(
                [tiles(words(slot, p, o), True) for p in range(n)], axis=0)       # [8 T, 128]
            q = q8_ref[pl.ds(8 * o, 8), :].astype(buf.dtype)
            s = jax.lax.dot_general(q, k, nt, preferred_element_type=jnp.float32)  # [8, 8 T]
            for j in range(R):   # a head's own columns of a tile, one row of the scores
                s_ref[o, pl.ds(j, 1), :] = jnp.sum(
                    jnp.where(own, s[:, j * _LANES:(j + 1) * _LANES], 0.0), axis=0, keepdims=True)
            s = s_ref[o, pl.ds(0, R), :] * sm_scale                                # [R, 128]
            if masked:
                s = jnp.where(live, s, _NEG_INF)
            m_prev = m_ref[o, pl.ds(0, 1), :]
            m_new = jnp.maximum(m_prev, per_head(jnp.max(s, axis=0, keepdims=True), jnp.maximum))
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(live, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[o, pl.ds(0, 1), :] = l_ref[o, pl.ds(0, 1), :] * alpha + per_head(
                jnp.sum(p, axis=0, keepdims=True), jnp.add)
            m_ref[o, pl.ds(0, 1), :] = m_new
            s_ref[o, pl.ds(0, R), :] = p
            # a head's weights on its own row, zeros on the others'
            pw = jnp.concatenate(
                [jnp.where(own, jnp.broadcast_to(s_ref[o, pl.ds(j, 1), :], own.shape), 0.0)
                 for j in range(R)], axis=1).astype(buf.dtype)                     # [8, 8 T]
            v = jnp.concatenate(   # (read again: a block's words are not kept in vregs)
                [tiles(words(slot, p, o), False) for p in range(n)], axis=0)      # [8 T, 128]
            rows = pl.ds(8 * o, 8)
            acc_ref[rows, :] = acc_ref[rows, :] * by_row(alpha) + jnp.dot(
                pw, v, preferred_element_type=jnp.float32)

    @pl.when(lane < n_live)
    def _():
        n_tok = lens_ref[lane]
        q8_ref[pl.ds(0, H), :] = q_ref[0].astype(jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def block(i, ring):
            slot, *ahead = ring
            ahead = fetch(ahead)          # into the buffer the link before this one left
            each_page(lane, i, slot, True)
            # A lane's last block alone is masked, and computed on as many
            # granules of the buffer as hold its pages.
            have = pl.cdiv(tokens(lane), ps) - i * N
            pl.when(have > N)(lambda: attend(i, slot, N, False, n_tok))
            for n in range(G, N + 1, G):
                pl.when((have > n - G) & (have <= n))(
                    lambda n=n: attend(i, slot, n, True, n_tok))
            return (jnp.where(slot + 1 == K, 0, slot + 1), *ahead)

        ring = jax.lax.fori_loop(0, pl.cdiv(tokens(lane), span), block,
                                 tuple(ring_ref[i] for i in range(4)))
        for i in range(4):
            ring_ref[i] = ring[i]
        for o in range(O):
            rows = pl.ds(8 * o, 8)
            acc_ref[rows, :] = acc_ref[rows, :] / jnp.maximum(
                by_row(l_ref[o, pl.ds(0, 1), :]), 1e-30)
        out_ref[0] = acc_ref[pl.ds(0, H), :].astype(out_ref.dtype)

    @pl.when(lane >= n_live)
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)


def block_pages(kv_pages, table_width: int) -> int:
    """Pages a KV block of the ring over ``kv_pages`` (its shape alone is
    read): ``_KERNEL_BLOCK_BYTES`` of them in whole granules, one granule at
    least, and no more than a table holds (in whole granules where it holds
    one)."""
    ps = kv_pages.shape[1]
    G = granule_pages(ps)
    page_bytes = math.prod(kv_pages.shape[1:]) * kv_pages.dtype.itemsize
    n = max(G, _KERNEL_BLOCK_BYTES // page_bytes // G * G)
    return max(G, min(n, table_width // G * G))


# jitted so that a program's layers, and every program of a width, share ONE
# trace of the kernel
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "pages_per_block", "blocks_in_ring", "interpret"))
def mha_decode_pallas(
    q: jax.Array,             # [B, H, 128] — one query a sequence
    kv_pages: jax.Array,      # [n_pages, page_size, 2 Hc, 128], Hc >= H
    kv_lens: jax.Array,       # [B] i32 — tokens the table holds incl. this one
    page_indices: jax.Array,  # [B, pages_per_seq] i32
    num_seqs: jax.Array,      # [1] i32 — lanes at and past it come out zero
    *, sm_scale: float, pages_per_block: int | None = None,
    blocks_in_ring: int = _KERNEL_BLOCKS_IN_RING, interpret: bool = False,
) -> jax.Array:               # [B, H, 128]
    """The decode attention of group-1 layers as one Pallas TPU kernel
    (:func:`_decode_kernel`, the module docstring). ``q`` goes in and the
    output comes back in the program's own ``[B, H, 128]``, the pages go in as
    the cache holds them: no layout pass of XLA's on either side. A lane
    walks its own pages, and its digits depend on them and on its length
    alone. ``interpret`` runs it under Pallas' TPU interpreter (the tests, on
    the CPU)."""
    B, H, d = q.shape
    n_pages, ps, comb, _ = kv_pages.shape
    G = granule_pages(ps)
    N = pages_per_block or block_pages(kv_pages, page_indices.shape[1])
    if N % G:
        raise ValueError(f"a KV block is whole granules of {G} pages; got {N}")
    Hc, O = comb // 2, comb // 16
    # A table names pages of this array or the DMA engine faults.
    tables = jnp.clip(page_indices, 0, n_pages - 1).astype(jnp.int32)
    if tables.shape[1] < N:
        tables = jnp.pad(tables, ((0, 0), (0, N - tables.shape[1])))
    by_lane = lambda b, *_: (b, 0, 0)
    stat = pltpu.VMEM((O, 8, _LANES), jnp.float32)
    heads = pltpu.VMEM((Hc, _LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, width=tables.shape[1],
                          interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, d), by_lane),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, d), by_lane),
            scratch_shapes=[
                pltpu.VMEM((blocks_in_ring, N, ps, comb, d), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((blocks_in_ring,)),
                pltpu.SMEM((4,), jnp.int32),
                heads,
                pltpu.VMEM((O, max(8, N * ps // 16), _LANES), jnp.float32),
                stat, stat, heads,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # The chain of copies runs from one grid step into the next. The
        # tables were clipped above: no copy's bounds are checked.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        name="ragged_paged_attention_mha_decode_kernel",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(kv_lens.astype(jnp.int32), tables.reshape(-1), num_seqs.astype(jnp.int32), q, kv_pages)
