"""Decode attention of GROUPED-query layers (several query heads a KV head:
a group of 2 to 16 in the cells) over the plain page, as one first-party
Pallas TPU kernel. ops/ragged_attention.py takes it for a decode-shaped call
where :func:`fits` holds; a group of ONE has ops/mha_attention.py, every
other call the library kernel.

Why a kernel of its own: the library kernel moves a decode call's K/V in
blocks of 16 pages, whole, whatever a lane holds of its last one (1.26 x the
bytes in use at the 7B's contexts of 384-1,536 tokens), converts each block to
float32 and waits on its DMA (PERF.md section 5, PR 28: 71% of the HBM rate
and no grid left to sweep). Here the page is read AS IT IS (``[n_pages,
page_size, 2 n_kv, 128]``, K even and V odd on the combined-head axis: the
waves, the writer, the prefix cache and the transfer format keep their page)
by the recipe of the other first-party decode kernels (ops/page_ring.py):
tables and lengths by scalar prefetch, the pages in use and no others by DMA
into a ring of VMEM blocks, one chain of (lane, block) links across lanes and
grid steps.

**The page as words.** In a bfloat16 page the rows ``2 g`` and ``2 g + 1`` of
a token (KV head ``g``'s key and value) share the 32-bit words of one sublane,
and a token follows a token, so the whole page read as 32-bit words is ``[ps
n_kv, 128]``: row ``t n_kv + g`` is token ``t``'s KV head ``g``, the key in a
word's low half and the value in its high half (a vreg of words is two
tokens at 4 KV heads, four at 2, one at 8). The kernel hands the DMA the page
under that shape (``[n_pages, 2 ps n_kv, 128]`` bfloat16: the same bytes, a
bitcast for XLA) and works on the words' own vregs: no load is strided (a
strided sublane load of one head's rows reads every row from one bank of
VMEM: PERF.md section 6, PR 51).

**The products.** 128 word rows (two halves of 64) are repacked by two shifts
and masks into one bfloat16 key tile and one value tile of 128 rows (word row
``j`` of the first half in row ``2 j``, of the second in row ``2 j + 1``),
the same order in both. K and V go to the MXU as bfloat16 with float32 sums,
and the MXU does the sorting: ALL the lane's query heads (padded in VMEM to
whole sublane tiles, never in HBM) against the tile as it lies give scores
``[heads, 128]`` in which a head keeps the columns of its own KV head (an
additive pattern of 0 and -inf kept in VMEM: one add a vreg) and every other
column's weight is exp(-inf) = 0, so the product of the weights with the
value tile sums a head's own values alone. A head is a row: maximum and sum
are lane reductions, the result is ``[heads, 128]`` as the output wants it.
That is ``n_kv`` x the useful FLOPs, 32 a K/V byte at the 7B's 28 heads
against the chip's ~240; every byte of K/V passes the MXU once as weights, as
in the group-1 and the wide-key kernels. Running maximum, denominator and
accumulator float32, the length's mask in a lane's last block only, one
divide a lane.

A block of the ring is ``N`` pages, awaited by one wait and computed on in
one piece: the products and the softmax of a piece are one chain of long
latencies (MXU, lane reductions, exponentials), and a first build that walked
a block in pieces of 512 word rows stood at 55-61% of every shape's bytes
whatever the ring (PERF.md section 6, PR 55). A lane's last block is computed
on as many QUARTERS of the buffer as hold its pages.
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
# Bytes a KV block of the ring and blocks in the ring: constants of the shape
# (the sweep passes its own), swept on the v5e by tools/attn_decode_bench.py
# --gqa-pages 4,8,16,32,64 --rings 2,3 at the cells' decode shapes (PERF.md
# section 5, PR 55; us a call alone, the library kernel at its decode grid
# first, then block bytes x ring; the least bytes at the HBM's 819 GB/s last):
#   shape (lanes, heads)         library | 256K x3  512K x2/x3   1M x2/x3    2M x2/x3    4M x2/x3 | floor
#   7b (32, 28/4)                  109.8 |  151.5  115.1 / 92.8  98.4 / 88.0  97.7 / 89.3  91.2 / 91.5 |  78.1
#   lfm2-128 (128, 32/4 paired)    653.9 |  937.6  714.1 / 566.7 603.0 / 543.6 584.4 / 545.1 591.3 / 551.4 | 496.8
#   1p5b-32 (32, 12/2)              65.2 |  74.1   60.9 / 51.0   54.8 / 45.6  56.1 / 49.1     -     |  35.3
#   1p5b-16 (16, 12/2)              32.5 |    -      - / 25.5      - / 23.1     -           -     |  17.5
#   1p5b-8 (8, 12/2)                17.6 |    -      - / 13.8      - / 12.7     -           -     |   8.7
#   nemotron-128 (128, 32/2)       319.0 |  394.6  308.4 / 257.2 265.2 / 218.4 241.4 / 221.1    -     | 186.9
#   laguna-full-48 (48, 48/8)    2,001.2 |    -  2,421 / 1,943 1,961 / 1,938 1,959 / 1,939 1,964 / 1,942 | 1,786
# 1 MB x 3 is the best or within 1.5% of it at every shape (3 MB of Mosaic's
# 16); a ring of two loses 6-18%, a ring of four gains nothing (7b 88.2, 1p5b-8
# 12.9 at 1 MB x 4). With no product at all (the copies alone)
# the 7B's call takes 85.9 us: the chain of page copies is the bound, the
# products hide behind it.
_KERNEL_BLOCK_BYTES = 1024 * 1024
_KERNEL_BLOCKS_IN_RING = 3


def quarter_pages(page_size: int, n_kv: int) -> int:
    """Pages a quarter of a KV block holds at least: whole tiles of 128 word
    rows."""
    return max(1, _LANES // (page_size * n_kv))


def fits(backend: str, q: jax.Array, kv_pages: jax.Array) -> bool:
    """Whether a decode-shaped call of ``q [B, H, d]`` over ``kv_pages`` takes
    this kernel, from what the call can observe: a TPU, 128-wide heads,
    bfloat16 queries and pages, a whole group of 2 or more query heads a KV
    head of the page, 2 KV heads or more that divide a half tile of words (a
    power of two up to 64; one KV head, a tensor-parallel shard's page of 8
    KB a DMA, was not measured), and a page whose words are whole vregs and
    a whole tile of 128 word rows or a whole fraction of one. No shape of a
    cell is left to the library kernel by the measurement: the candidate,
    Laguna's full layers (contexts of 4,096-10,752, where 16-page blocks
    waste little), reads 1,938 us here against the library's 2,001 (the
    table below :func:`block_pages`), so the table's width is not asked."""
    ps, comb, d = kv_pages.shape[1:]
    n_kv, H = comb // 2, q.shape[1]
    if not (backend == "tpu" and d == _LANES and kv_pages.dtype == jnp.bfloat16
            and q.dtype == jnp.bfloat16 and comb % 2 == 0 and n_kv >= 2 and 64 % n_kv == 0
            and H % n_kv == 0 and H // n_kv > 1):
        return False
    return (ps * n_kv) % 8 == 0 and (quarter_pages(ps, n_kv) * ps * n_kv) % _LANES == 0


def _decode_kernel(
    lens_ref,      # SMEM [B] i32 (scalar prefetch)
    tables_ref,    # SMEM [B * width] i32 (scalar prefetch), lane after lane
    live_ref,      # SMEM [1] i32 (scalar prefetch) — the live lanes come first
    q_ref,         # VMEM [1, H, 128] — this grid step's lane, as the program holds it
    pages_ref,     # HBM  [n_pages, 2 ps n_kv, 128] — the cache as it is, a page's rows flat
    out_ref,       # VMEM [1, H, 128]
    buf,           # VMEM [K, N, 2 ps n_kv, 128] — a ring of K KV blocks of N pages
    sems,          # DMA semaphores [K], one a buffer
    ring_ref,      # SMEM [4] — the ring's state from one grid step to the next
    qp_ref,        # VMEM [Hp, 128] f32 — the lane's queries, zeros in the padding's rows
    own_ref,       # VMEM [Hp, 128] f32 — 0 where a tile's column is of the row's KV head, else -inf
    m_ref, l_ref,  # VMEM [Hp, 128] f32 — running maximum and sum, every lane of a row the same
    acc_ref,       # VMEM [Hp, 128] f32
    *, sm_scale: float, width: int, n_kv: int, ps: int, interpret: bool,
):
    """A grid step is one lane, which walks its own KV blocks through the
    ring (ops/page_ring.py); a block is computed on in one piece (module
    docstring, "The products")."""
    K, N = buf.shape[:2]
    H, Hp = q_ref.shape[1], qp_ref.shape[0]
    shift = n_kv.bit_length() - 1
    span = N * ps
    lane, B = pl.program_id(0), pl.num_programs(0)
    n_live = jnp.clip(live_ref[0], 0, B)

    def words(slot, n: int):
        """``[n ps n_kv, 128]`` u32: (key | value << 16), a word row a (token,
        KV head), of the first ``n`` pages of buffer ``slot``: the pages' own
        vregs."""
        if interpret:   # Pallas' interpreter reads no bitcast ref: the words from their halves
            x = jax.lax.bitcast_convert_type(
                buf[slot, pl.ds(0, n)], jnp.uint16).astype(jnp.uint32)
            x = x[:, 0::2] | (x[:, 1::2] << 16)
        else:
            x = buf.bitcast(jnp.uint32)[slot, pl.ds(0, n)]
        return x.reshape(n * ps * n_kv, _LANES)

    def tiles(x):
        """Words ``[C, 128]`` as bfloat16 rows ``[C, 128]``, the keys and the
        values: column ``c`` of a tile of 128 is word row ``c // 2`` of the
        tile's first half (``c`` even) or second (odd)."""
        C = x.shape[0]
        x = x.reshape(C // _LANES, 2, _LANES // 2, _LANES)
        a, b = x[:, 0], x[:, 1]
        pack = lambda w: pltpu.bitcast(w.reshape(C // 2, _LANES), buf.dtype)
        return pack((a & _LOW) | (b << 16)), pack((a >> 16) | (b & _HIGH))

    def tokens(lane):
        return jnp.maximum(lens_ref[lane], 1)

    each_page, fetch = page_chain(tokens, tables_ref, pages_ref, buf, sems, width=width,
                                  page_tokens=ps, lanes=n_live)

    @pl.when(lane == 0)
    def _():
        start_chain(fetch, buf, ring_ref)
        qp_ref[...] = jnp.zeros(qp_ref.shape, jnp.float32)
        # column c is word row c // 2 (+ 64) of its tile: KV head (c // 2) % n_kv
        head = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 0)
        kv_head = (jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 1) >> 1) & (n_kv - 1)
        first = kv_head * (H // n_kv)
        own_ref[...] = jnp.where((head >= first) & (head < first + H // n_kv), 0.0, _NEG_INF)

    nt = (((1,), (1,)), ((), ()))      # q . k^T: both contract their lanes

    def attend(i, slot, n: int, masked: bool, n_tok):
        """Block ``i`` of this lane, the first ``n`` pages of buffer
        ``slot``, into the running max, sum and accumulator."""
        C = n * ps * n_kv
        k, v = tiles(words(slot, n))
        s = jax.lax.dot_general(qp_ref[...].astype(buf.dtype), k, nt,
                                preferred_element_type=jnp.float32)        # [Hp, C]
        s = s * sm_scale + jnp.tile(own_ref[...], (1, C // _LANES))
        if masked:
            c = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
            word = (c >> 7 << 7) + ((c & 127) >> 1) + ((c & 1) << 6)
            live = i * span + (word >> shift) < n_tok
            s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        if masked:
            p = jnp.where(live, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(buf.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(lane < n_live)
    def _():
        n_tok = lens_ref[lane]
        qp_ref[pl.ds(0, H), :] = q_ref[0].astype(jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def block(i, ring):
            slot, *ahead = ring
            ahead = fetch(ahead)          # into the buffer the link before this one left
            each_page(lane, i, slot, True)
            # A lane's last block alone is masked, and computed on as many
            # quarters of the buffer as hold its pages.
            have = pl.cdiv(tokens(lane), ps) - i * N
            pl.when(have > N)(lambda: attend(i, slot, N, False, n_tok))
            for n in range(N // 4, N + 1, N // 4):
                pl.when((have > n - N // 4) & (have <= n))(
                    lambda n=n: attend(i, slot, n, True, n_tok))
            return (jnp.where(slot + 1 == K, 0, slot + 1), *ahead)

        ring = jax.lax.fori_loop(0, pl.cdiv(tokens(lane), span), block,
                                 tuple(ring_ref[i] for i in range(4)))
        for i in range(4):
            ring_ref[i] = ring[i]
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out[:H].astype(out_ref.dtype)

    @pl.when(lane >= n_live)
    def _():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)


def block_pages(kv_pages, table_width: int) -> int:
    """Pages a KV block of the ring over ``kv_pages`` (its shape alone is
    read): ``_KERNEL_BLOCK_BYTES`` of them in whole quarters of whole tiles,
    four quarters at least, and no more than a table holds (in whole
    quarters where it holds them)."""
    ps, comb = kv_pages.shape[1:3]
    Q = 4 * quarter_pages(ps, comb // 2)
    page_bytes = math.prod(kv_pages.shape[1:]) * kv_pages.dtype.itemsize
    n = max(Q, _KERNEL_BLOCK_BYTES // page_bytes // Q * Q)
    return max(Q, min(n, table_width // Q * Q))


# jitted so that a program's layers, and every program of a width, share ONE
# trace of the kernel
@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "pages_per_block", "blocks_in_ring", "interpret"))
def grouped_decode_pallas(
    q: jax.Array,             # [B, H, 128] — one query a sequence
    kv_pages: jax.Array,      # [n_pages, page_size, 2 n_kv, 128], H a multiple of n_kv
    kv_lens: jax.Array,       # [B] i32 — tokens the table holds incl. this one
    page_indices: jax.Array,  # [B, pages_per_seq] i32
    num_seqs: jax.Array,      # [1] i32 — lanes at and past it come out zero
    *, sm_scale: float, pages_per_block: int | None = None,
    blocks_in_ring: int = _KERNEL_BLOCKS_IN_RING, interpret: bool = False,
) -> jax.Array:               # [B, H, 128]
    """The decode attention of grouped-query layers as one Pallas TPU kernel
    (:func:`_decode_kernel`, the module docstring). ``q`` goes in and the
    output comes back in the program's own ``[B, H, 128]``; the pages go in
    as the cache holds them, a page's rows flat (the same bytes: a bitcast).
    A lane walks its own pages, and its digits depend on them and on its
    length alone. ``interpret`` runs it under Pallas' TPU interpreter (the
    tests, on the CPU)."""
    B, H, d = q.shape
    n_pages, ps, comb, _ = kv_pages.shape
    n_kv = comb // 2
    N = pages_per_block or block_pages(kv_pages, page_indices.shape[1])
    if N % (4 * quarter_pages(ps, n_kv)):
        raise ValueError(f"a KV block is four quarters of whole tiles of 128 word rows: "
                         f"{4 * quarter_pages(ps, n_kv)} pages; got {N}")
    Hp = -(-H // 16) * 16                 # whole sublane tiles of bfloat16
    # A table names pages of this array or the DMA engine faults.
    tables = jnp.clip(page_indices, 0, n_pages - 1).astype(jnp.int32)
    if tables.shape[1] < N:
        tables = jnp.pad(tables, ((0, 0), (0, N - tables.shape[1])))
    by_lane = lambda b, *_: (b, 0, 0)
    heads = pltpu.VMEM((Hp, _LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, width=tables.shape[1],
                          n_kv=n_kv, ps=ps, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, d), by_lane),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, d), by_lane),
            scratch_shapes=[
                pltpu.VMEM((blocks_in_ring, N, ps * comb, d), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((blocks_in_ring,)),
                pltpu.SMEM((4,), jnp.int32),
                heads, heads, heads, heads, heads,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # The chain of copies runs from one grid step into the next. The
        # tables were clipped above: no copy's bounds are checked.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        name="ragged_paged_attention_grouped_decode_kernel",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(kv_lens.astype(jnp.int32), tables.reshape(-1), num_seqs.astype(jnp.int32), q,
      kv_pages.reshape(n_pages, ps * comb, d))
