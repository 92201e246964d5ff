"""Latent (MLA) attention over pages of ``[ckv | kr]``: one compressed
K/V vector a token (``kv_lora_rank`` = ``r`` values, after its norm)
beside one rope key shared by all heads (``qk_rope_head_dim`` = ``dr``
values, after rope): ``r + dr`` values a token a layer, no more.

**The page.** ``[rows, w]`` with ``w = 2 dr`` lanes (128 at the published
sizes) and ``rows = ps r / w + ps / 2`` (144 for 32 tokens), written and
read by this module alone (:func:`latent_page_shape`,
:func:`write_latent_rows`): token ``t``'s ``ckv`` lies in rows ``j ps +
t`` for lane-tile ``j`` of its ``r / w``, and its ``kr`` in the left
(``t < ps / 2``) or right half of row ``(r / w) ps + t mod (ps / 2)``.
Why not ``[ps, r + dr]``: 576 is no multiple of the TPU's 128 lanes, and
the device's default layout for ``[n_pages, 32, 576]`` then makes the
PAGE axis the minor one (less padding), which scatters a page over the
whole array: every program would re-lay the cache out on entry and again
on exit (the v5e's compiler, PR 32: 640 MB of copies a layer a call).
``[n_pages, 144, 128]`` has no padding, keeps a page contiguous, holds
exactly ``r + dr`` values a token, and every slice this module takes of
it is a whole tile but the two halves of a ``kr`` row.

Two shapes, as :mod:`dynamo_tpu.ops.ragged_attention` has them:

- **decode** (:func:`latent_decode_attention`, one query token a
  sequence), ABSORBED: the caller folds the K up-projection into the
  query (``q' = q_nope Wkvb_k^T``, ``[H, r]``), the scores are ``q' . ckv
  + q_rope . kr`` against the pages as stored, the values are the ``ckv``
  themselves (``o' = P ckv``), and the caller applies the V
  up-projection to ``o'``. Every query head reads the same key and value
  straight from the latent pages; nothing is expanded.
- **ragged** (:func:`latent_ragged_attention`, prefill waves, chunks,
  verify rows, mixed batches), EXPANDED: ``k_nope`` and ``v`` of every
  head from ``ckv``. The caller writes the step's rows FIRST and every
  key, this step's or an earlier one's, is read from the pages, a
  sequence at a time, a chunk of its pages at a time (expanded once),
  against that sequence's own query rows in blocks. A row's arithmetic
  is then a function of its position and its sequence's pages alone, not
  of how many of the prompt's blocks were found in the cache or of what
  else rides in the wave: a prefix hit gives the first send's digits,
  as the paged kernel does for the dense layer. No work is spent on
  another sequence's keys.

The ragged call is plain ``jax.numpy``. The decode call is one algorithm
with two implementations, chosen from what the call can observe
(:func:`decode_impl`, as ops/ragged_attention.py chooses the library
kernel): on a TPU, for a page whose slices are whole tiles, a first-party
Pallas kernel (:func:`latent_decode_pallas`, PR 34): the block table and
the lengths by scalar prefetch, the pages left in HBM and fetched a page a
DMA into a ring of VMEM blocks, each lane walking its own pages and no
further, every cached byte read once and used there for the scores AND the
values, all 64 heads against the one key (M = 64 on the MXU, no head
loop). Anywhere else (the CPU, the tiny rehearsal's ``[20, 16]`` page)
:func:`latent_decode_jnp`, which stays the definition the tests hold the
kernel to: it sorts its lanes by context and walks
``_DECODE_PAGES_PER_CHUNK`` gathered pages a turn of
``_DECODE_LANES_PER_GROUP`` lanes at a time, each group up to its own
longest context and no further. Either way the call's work follows the
contexts in flight and neither the block table's width nor the one
longest stream. Which path a program traced is counted like the paged
kernel's (``dynamo_engine_attention_calls_traced_total`` with ``shape``
``latent-decode`` / ``latent-ragged``, ``impl`` ``pallas`` / ``jnp``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.page_ring import page_chain, start_chain
from dynamo_tpu.ops.ragged_attention import _NEG_INF, _count_traced

# Pages of every lane gathered a turn of the decode loop: 8 x 32 tokens x
# 576 values x 2 B = 295 KB a lane, 38 MB at 128 lanes.
_DECODE_PAGES_PER_CHUNK = 8
# Lanes a loop of the decode call: the batch is sorted by context and cut
# into groups of this many, each walking its pages as far as ITS longest
# context reaches. One loop over the whole batch does the longest lane's
# work for every lane: at 128 lanes of 300-2,080 tokens that is 1.5 x the
# pages in use, and a step's time then follows the ONE longest stream in
# flight, which differs from seed to seed (PERF.md, PR 32).
_DECODE_LANES_PER_GROUP = 32
# Query rows a block and pages a chunk of the ragged call: a turn is 128
# rows against 256 keys of their own sequence.
_RAGGED_QUERIES_PER_BLOCK = 128
_RAGGED_PAGES_PER_CHUNK = 8


def latent_page_shape(page_size: int, r: int, dr: int) -> tuple[int, int]:
    """``(rows, lanes)`` of one page of ``page_size`` tokens (module
    docstring, "The page")."""
    w = 2 * dr
    if r % w or page_size % 2:
        raise ValueError(
            f"a latent page needs kv_lora_rank={r} to be a multiple of "
            f"2 x qk_rope_head_dim={w} and an even page_size={page_size}"
        )
    return page_size * r // w + page_size // 2, w


def _geometry(pages: jax.Array, r: int) -> tuple[int, int, int]:
    """(page_size, lane tiles of ckv, lanes) of a page array for rank ``r``."""
    rows, w = pages.shape[-2:]
    tiles = r // w
    return 2 * rows // (2 * tiles + 1), tiles, w


def write_latent_rows(
    pages: jax.Array,        # [n_pages, rows, w]
    write_pages: jax.Array,  # [T] i32
    write_offs: jax.Array,   # [T] i32 — the token's slot in its page
    ckv: jax.Array,          # [T, r]
    kr: jax.Array,           # [T, dr]
) -> jax.Array:
    """Scatter ``T`` tokens' ``[ckv | kr]`` into their pages as WHOLE rows,
    ``r / w + 1`` a token, in one scatter. A ``kr`` row holds two tokens
    (slots ``t`` and ``t + ps / 2`` of a page), so a token's row is built
    first: its own half beside its partner's, taken from this step's rows
    where the partner is written in the same step (the flat batch keeps a
    sequence's rows in position order, so the partner is ``ps / 2`` rows
    away, and both then write the same row), else from the page as it
    stands. A half-row scatter at a lane offset runs one token at a time
    on the v5e: 0.5 ms a layer at 128 lanes, a sixth of the decode step
    (PERF.md, PR 32)."""
    T, r = ckv.shape
    ps, tiles, w = _geometry(pages, r)
    half = ps // 2
    kr = kr.astype(pages.dtype)
    kr_row = tiles * ps + write_offs % half
    upper = write_offs >= half                               # which half is mine
    i = jnp.arange(T, dtype=jnp.int32)
    j = jnp.clip(jnp.where(upper, i - half, i + half), 0, T - 1)
    together = (
        (jnp.abs(i - j) == half)
        & (write_pages[j] == write_pages)
        & (write_offs[j] == jnp.where(upper, write_offs - half, write_offs + half))
    )
    stands = pages[write_pages, kr_row]                      # [T, w]
    other = jnp.where(
        together[:, None], kr[j],
        jnp.where(upper[:, None], stands[:, : w // 2], stands[:, w // 2:]),
    )
    kr_full = jnp.where(
        upper[:, None],
        jnp.concatenate([other, kr], axis=-1), jnp.concatenate([kr, other], axis=-1),
    )
    rows = jnp.concatenate(
        [jnp.arange(tiles, dtype=jnp.int32)[None, :] * ps + write_offs[:, None],
         kr_row[:, None]], axis=1,
    )                                                         # [T, tiles + 1]
    values = jnp.concatenate(
        [ckv.reshape(T, tiles, w).astype(pages.dtype), kr_full[:, None]], axis=1
    )
    return pages.at[write_pages[:, None], rows].set(values)


def _split(g: jax.Array, r: int):
    """Gathered pages ``[..., rows, w]`` as (``ckv`` ``[..., tiles, ps,
    w]``, ``kr`` ``[..., ps, dr]``), tokens in order."""
    ps, tiles, w = _geometry(g, r)
    ckv = g[..., : tiles * ps, :].reshape(*g.shape[:-2], tiles, ps, w)
    halves = g[..., tiles * ps:, :]
    kr = jnp.concatenate([halves[..., : w // 2], halves[..., w // 2:]], axis=-2)
    return ckv, kr


def _chunk_tables(block_tables: jax.Array, pages: int) -> jax.Array:
    """``[S, P]`` padded to a whole number of ``pages``-wide chunks (the
    padding repeats page 0: it is masked by position)."""
    pad = (-block_tables.shape[1]) % pages
    return jnp.pad(block_tables, ((0, 0), (0, pad)))


def decode_impl(backend: str, pages: jax.Array, r: int) -> str:
    """Which implementation a decode call over ``pages`` gets on
    ``backend``: ``"pallas"`` on a TPU where the page's slices are whole
    tiles for the kernel (128 lanes a row, hence ``r`` a multiple of 128,
    and half a page's tokens a whole number of the dtype's sublane tiles:
    16 rows of bf16, 8 of f32), else ``"jnp"`` (the CPU; the tiny
    rehearsal's ``[20, 16]`` page). The label of the call's counter."""
    ps, _, w = _geometry(pages, r)
    sublanes = 32 // pages.dtype.itemsize
    fits = (w == 128 and jnp.issubdtype(pages.dtype, jnp.floating)
            and (ps // 2) % sublanes == 0)
    return "pallas" if backend == "tpu" and fits else "jnp"


def latent_decode_attention(
    q_lat: jax.Array,         # [B, H, r] — q_nope through Wkvb_k (absorbed)
    q_rope: jax.Array,        # [B, H, dr]
    pages: jax.Array,         # [n_pages, rows, w] (:func:`latent_page_shape`)
    kv_lens: jax.Array,       # [B] i32 — tokens in cache incl. this one (>= 1)
    block_tables: jax.Array,  # [B, pages_per_seq] i32
    *,
    sm_scale: float,
) -> jax.Array:               # [B, H, r] — P ckv, before Wkvb_v
    """One algorithm, the implementation chosen from what the call can
    observe (:func:`decode_impl`), as ops/ragged_attention.py chooses the
    library kernel; :func:`latent_decode_jnp` is the definition."""
    with jax.named_scope("latent_paged_attention"):
        impl = decode_impl(jax.default_backend(), pages, q_lat.shape[-1])
        _count_traced("latent-decode", impl)
        fn = latent_decode_pallas if impl == "pallas" else latent_decode_jnp
        return fn(q_lat, q_rope, pages, kv_lens, block_tables, sm_scale=sm_scale)


def latent_decode_jnp(q_lat, q_rope, pages, kv_lens, block_tables, *, sm_scale: float):
    """Plain ``jax.numpy``: the lanes sorted by context and walked
    ``_DECODE_PAGES_PER_CHUNK`` gathered pages a turn,
    ``_DECODE_LANES_PER_GROUP`` lanes at a time."""
    B, H, r = q_lat.shape
    ps, tiles, w = _geometry(pages, r)
    C = min(_DECODE_PAGES_PER_CHUNK, block_tables.shape[1])
    offs = jnp.arange(C * ps, dtype=jnp.int32).reshape(C, ps)
    # Lanes in order of context: a group's loop then ends at ITS longest.
    order = jnp.argsort(kv_lens)
    tables = _chunk_tables(block_tables, C)[order]
    lens = kv_lens[order]
    q_tiles = q_lat[order].reshape(B, H, tiles, w)
    q_rope = q_rope[order]

    def group(a: int, b: int):
        """Lanes ``a:b`` of the sorted batch: (sum ``[n, H]``,
        numerator ``[n, H, tiles, w]``), chunk by chunk of ``C`` pages
        up to the group's longest context."""
        def body(c, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(tables[a:b], c * C, C, axis=1)
            ckv, kr = _split(pages[ids], r)     # [n, C, tiles, ps, w], [n, C, ps, dr]
            s = jnp.einsum("bhd,bptd->bhpt", q_rope[a:b], kr,
                           preferred_element_type=jnp.float32)
            for j in range(tiles):              # one K = w product a lane tile
                s = s + jnp.einsum("bhc,bptc->bhpt", q_tiles[a:b, :, j], ckv[:, :, j],
                                   preferred_element_type=jnp.float32)
            s = s * sm_scale
            live = ((c * C * ps + offs)[None] < lens[a:b, None, None])[:, None]
            s = jnp.where(live, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=(-2, -1)))
            p = jnp.where(live, jnp.exp(s - m_new[..., None, None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=(-2, -1))
            pv = p.astype(pages.dtype)
            acc = acc * alpha[..., None, None] + jnp.stack(
                [jnp.einsum("bhpt,bptc->bhc", pv, ckv[:, :, j],
                            preferred_element_type=jnp.float32)
                 for j in range(tiles)], axis=2)
            return m_new, l, acc

        n_chunks = (lens[b - 1] + C * ps - 1) // (C * ps)
        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((b - a, H), _NEG_INF, jnp.float32),
             jnp.zeros((b - a, H), jnp.float32),
             jnp.zeros((b - a, H, tiles, w), jnp.float32)),
        )
        return l, acc

    parts = [group(a, min(B, a + _DECODE_LANES_PER_GROUP))
             for a in range(0, B, _DECODE_LANES_PER_GROUP)]
    l = jnp.concatenate([p[0] for p in parts])
    acc = jnp.concatenate([p[1] for p in parts])
    out = (acc / jnp.maximum(l, 1e-30)[..., None, None]).reshape(B, H, r)
    return out[jnp.argsort(order)].astype(q_lat.dtype)


def _decode_kernel(
    lens_ref,      # SMEM [B] i32 (scalar prefetch)
    tables_ref,    # SMEM [B * width] i32 (scalar prefetch), lane after lane
    q_ref,         # VMEM [1, H, r] — this grid step's lane
    qr_ref,        # VMEM [1, H, 2 w] — [q_rope | 0 | 0 | q_rope]
    pages_ref,     # HBM  [n_pages, rows, w]
    out_ref,       # VMEM [1, H, r]
    buf,           # VMEM [K, N, rows, w] — a ring of K KV blocks of N pages
    sems,          # DMA semaphores [K], one a buffer
    ring_ref,      # SMEM [4] — the ring's state from one grid step to the next
    m_ref, l_ref,  # VMEM [H, 128] f32, every lane of a row the same
    acc_ref,       # VMEM [H, r] f32
    *, sm_scale: float, width: int,
):
    """A grid step is one lane, which walks its own ``ceil(kv_len / (N
    ps))`` KV blocks. Every (lane, block) of the call is one link of a
    chain through the ring of buffers: a link's pages are asked for ``K -
    1`` links before it is computed on, across lanes and grid steps alike,
    so that ``(K - 1) N`` pages are in flight whatever one block's
    arithmetic takes (a v5e streams at 83% of its HBM rate from ~24 up).

    A block is computed on as two HALVES, the tokens of each page's slots
    ``0 .. ps/2`` and ``ps/2 .. ps``: a ``kr`` row holds one token of each
    (left and right 64 lanes), so half ``h``'s keys are ``[N ps/2, r + w]``
    = the half's rows of the four ``ckv`` tiles beside the ``kr`` rows AS
    STORED, against ``[q_lat | q_rope | 0]`` or ``[q_lat | 0 | q_rope]``:
    one K = 640 product a half with no slice inside a row. The values are
    the same rows' first ``r`` lanes. The order of the keys inside a block
    is then (half, page, slot), which a softmax does not see but the mask
    of a lane's last block must."""
    K, N = buf.shape[:2]
    ps, tiles, w = _geometry(buf, q_ref.shape[-1])
    half, span = ps // 2, N * ps
    quarters = sorted({max(1, N * k // 4) for k in (1, 2, 3, 4)})
    lane, B = pl.program_id(0), pl.num_programs(0)

    each_page, fetch = page_chain(lambda lane: lens_ref[lane], tables_ref, pages_ref, buf, sems,
                                  width=width, page_tokens=ps, lanes=B)
    pl.when(lane == 0)(lambda: start_chain(fetch, buf, ring_ref))

    n_tok = lens_ref[lane]
    n_blk = pl.cdiv(n_tok, span)
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(i, slot, n: int, masked: bool):
        """Block ``i`` of this lane, the first ``n`` pages of buffer
        ``slot``, into the running max, sum and accumulator."""
        s, v, live = [], [], []
        for h in range(2):
            rows = [buf[slot, pl.ds(0, n), pl.ds(j * ps + h * half, half), :].reshape(n * half, w)
                    for j in range(tiles)]
            kr = buf[slot, pl.ds(0, n), pl.ds(tiles * ps, half), :].reshape(n * half, w)
            q = jnp.concatenate([q_ref[0], qr_ref[0, :, pl.ds(h * w, w)]], axis=1)
            s_h = jax.lax.dot_general(
                q, jnp.concatenate(rows + [kr], axis=1), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                col = jax.lax.broadcasted_iota(jnp.int32, s_h.shape, 1)
                pos = i * span + (col // half) * ps + h * half + col % half
                live.append(pos < n_tok)
                s_h = jnp.where(live[h], s_h, _NEG_INF)
            s.append(s_h)
            v.append(jnp.concatenate(rows, axis=1))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.maximum(
            jnp.max(s[0], axis=1, keepdims=True), jnp.max(s[1], axis=1, keepdims=True)))
        p = [jnp.exp(s_h - m_new[:, :1]) for s_h in s]
        if masked:
            p = [jnp.where(live_h, p_h, 0.0) for live_h, p_h in zip(live, p)]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + (
            jnp.sum(p[0], axis=1, keepdims=True) + jnp.sum(p[1], axis=1, keepdims=True))
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + (
            jnp.dot(p[0].astype(buf.dtype), v[0], preferred_element_type=jnp.float32)
            + jnp.dot(p[1].astype(buf.dtype), v[1], preferred_element_type=jnp.float32))

    def block(i, ring):
        slot, *ahead = ring
        ahead = fetch(ahead)          # into the buffer the link before this one left
        each_page(lane, i, slot, True)
        # A lane's last block alone is masked, and computed on as many
        # quarters of the buffer as hold its pages.
        have = pl.cdiv(n_tok, ps) - i * N
        pl.when(have > N)(lambda: attend(i, slot, N, False))
        for lo, n in zip([0, *quarters], quarters):
            pl.when((have > lo) & (have <= n))(lambda n=n: attend(i, slot, n, True))
        return (jnp.where(slot + 1 == K, 0, slot + 1), *ahead)

    ring = jax.lax.fori_loop(0, n_blk, block, tuple(ring_ref[i] for i in range(4)))
    for i in range(4):
        ring_ref[i] = ring[i]
    out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(out_ref.dtype)


# Pages a KV block and blocks in the ring of the kernel: constants of the
# shape, swept on the v5e by tools/attn_decode_bench.py (PERF.md section 5,
# PR 34); the sweep passes its own.
_KERNEL_PAGES_PER_BLOCK = 32
_KERNEL_BLOCKS_IN_RING = 3


# jitted so that a program's layers, and every program of a width, share ONE
# trace of the kernel (0.7 s each otherwise, paid in every warm start-up)
@functools.partial(jax.jit, static_argnames=("sm_scale", "pages_per_block", "blocks_in_ring"))
def latent_decode_pallas(
    q_lat, q_rope, pages, kv_lens, block_tables, *, sm_scale: float,
    pages_per_block: int = _KERNEL_PAGES_PER_BLOCK,
    blocks_in_ring: int = _KERNEL_BLOCKS_IN_RING,
):
    """The absorbed decode attention as one Pallas TPU kernel over the paged
    latent cache (:func:`_decode_kernel`): ``block_tables`` and ``kv_lens``
    by scalar prefetch, ``pages`` left in HBM and fetched a page a DMA
    through the block table, each cached byte read once and used in VMEM
    for scores and values. f32 scores, running max and sum, and
    accumulator; the weights cast to the page's dtype for the value
    products, as :func:`latent_decode_jnp` does. No sort and no groups: a
    lane walks its own pages, and its digits depend on them and on its
    length alone. Needs a geometry :func:`decode_impl` accepts."""
    B, H, r = q_lat.shape
    w = pages.shape[-1]
    N = max(1, min(pages_per_block, block_tables.shape[1]))
    zeros = jnp.zeros_like(q_rope)
    q_rope = jnp.concatenate([q_rope, zeros, zeros, q_rope], axis=-1)
    # A table names pages of this array or the DMA engine faults.
    block_tables = jnp.clip(block_tables, 0, pages.shape[0] - 1)
    by_lane = lambda b, *_: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, width=block_tables.shape[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, r), by_lane),
                pl.BlockSpec((1, H, 2 * w), by_lane),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, r), by_lane),
            scratch_shapes=[
                pltpu.VMEM((blocks_in_ring, N, *pages.shape[1:]), pages.dtype),
                pltpu.SemaphoreType.DMA((blocks_in_ring,)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, r), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        # The chain of copies runs from one grid step into the next. The
        # tables were clipped above: a check of each copy's bounds is 12 of
        # the ~20 scalar bundles a page costs.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        name="latent_decode_attention_kernel",
    )(kv_lens.astype(jnp.int32), block_tables.astype(jnp.int32).reshape(-1), q_lat, q_rope, pages)


def latent_ragged_attention(
    q_nope: jax.Array,        # [T, H, dn]
    q_rope: jax.Array,        # [T, H, dr]
    wk: jax.Array,            # [H, dn, r] — Wkvb's K part
    wv: jax.Array,            # [H, r, dv] — Wkvb's V part
    pages: jax.Array,         # [n_pages, rows, w], this step's rows WRITTEN
    kv_lens: jax.Array,       # [S] i32 — tokens in cache incl. this step's
    block_tables: jax.Array,  # [S, pages_per_seq] i32
    cu_q_lens: jax.Array,     # [S + 1] i32
    num_seqs: jax.Array,      # [1] i32
    *,
    sm_scale: float,
) -> jax.Array:               # [T, H, dv]; rows past the last sequence zero
    with jax.named_scope("latent_ragged_attention"):
        _count_traced("latent-ragged", "jnp")
        T, H, _ = q_nope.shape
        r, dv, dt = wk.shape[-1], wv.shape[-1], q_nope.dtype
        ps = _geometry(pages, r)[0]
        C = min(_RAGGED_PAGES_PER_CHUNK, block_tables.shape[1])
        span = C * ps
        tables = _chunk_tables(block_tables, C)
        QB = min(_RAGGED_QUERIES_PER_BLOCK, T)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # Scores in powers of two against a WHOLE-numbered maximum: a
        # chunk's weights are rescaled by exact powers of two, so a chunk
        # that holds nothing for a row leaves its digits alone.
        log2_scale = sm_scale * math.log2(math.e)
        key_off = jnp.arange(span, dtype=jnp.int32)
        row_off = jnp.arange(QB, dtype=jnp.int32)

        def sequence(s, out):
            q0 = cu_q_lens[s]
            qn = cu_q_lens[s + 1] - q0
            before = kv_lens[s] - qn           # tokens in cache before this step's rows
            n_blocks = (qn + QB - 1) // QB

            def block_rows(i):
                """Block ``i`` of the sequence's rows as a window of ``QB``
                flat rows that stays inside the batch: (first flat row,
                which rows are the block's, each row's position)."""
                start = q0 + i * QB
                first = jnp.minimum(start, T - QB)
                rows = first + row_off
                return first, (rows >= start) & (rows < q0 + qn), before + rows - q0

            def chunk(c, carry):
                ids = jax.lax.dynamic_slice(tables, (s, c * C), (1, C))[0]
                ckv, kr = _split(pages[ids], r)      # [C, tiles, ps, w], [C, ps, dr]
                ckv = jnp.moveaxis(ckv, 1, 2).reshape(span, r)
                k = jnp.concatenate(
                    [jnp.einsum("jr,hdr->jhd", ckv, wk,
                                preferred_element_type=jnp.float32).astype(dt),
                     jnp.broadcast_to(kr.reshape(span, 1, -1), (span, H, kr.shape[-1]))],
                    axis=-1,
                )
                v = jnp.einsum("jr,hrd->jhd", ckv, wv,
                               preferred_element_type=jnp.float32).astype(dt)
                key_pos = c * span + key_off

                def block(i, carry):
                    first, mine, pos = block_rows(i)
                    m, l, acc = (jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
                                 for x in carry)
                    sc = jnp.einsum(
                        "qhd,jhd->hqj", jax.lax.dynamic_slice_in_dim(q, first, QB), k,
                        preferred_element_type=jnp.float32) * log2_scale
                    live = (mine[:, None] & (key_pos[None, :] <= pos[:, None]))[None]
                    sc = jnp.where(live, sc, _NEG_INF)
                    m_new = jnp.maximum(m, jnp.ceil(jnp.max(sc, axis=-1)))
                    p = jnp.where(live, jnp.exp2(sc - m_new[..., None]), 0.0)
                    alpha = jnp.exp2(m - m_new)
                    new = (
                        m_new, l * alpha + jnp.sum(p, axis=-1),
                        acc * alpha[..., None] + jnp.einsum(
                            "hqj,jhd->hqd", p.astype(dt), v,
                            preferred_element_type=jnp.float32),
                    )
                    return tuple(jax.lax.dynamic_update_index_in_dim(x, y, i, 0)
                                 for x, y in zip(carry, new))

                # the first block with a row at or past this chunk's first key
                return jax.lax.fori_loop(
                    jnp.maximum(c * span - before, 0) // QB, n_blocks, block, carry)

            n = -(-T // QB)
            _, l, acc = jax.lax.fori_loop(
                0, (kv_lens[s] + span - 1) // span, chunk,
                (jnp.full((n, H, QB), _NEG_INF, jnp.float32),
                 jnp.zeros((n, H, QB), jnp.float32),
                 jnp.zeros((n, H, QB, dv), jnp.float32)),
            )

            def store(i, out):
                first, mine, _ = block_rows(i)
                o = (jax.lax.dynamic_index_in_dim(acc, i, keepdims=False)
                     / jnp.maximum(jax.lax.dynamic_index_in_dim(l, i, keepdims=False),
                                   1e-30)[..., None])
                o = jnp.swapaxes(o, 0, 1).astype(dt)                 # [QB, H, dv]
                here = jax.lax.dynamic_slice_in_dim(out, first, QB)
                return jax.lax.dynamic_update_slice_in_dim(
                    out, jnp.where(mine[:, None, None], o, here), first, 0)

            return jax.lax.fori_loop(0, n_blocks, store, out)

        return jax.lax.fori_loop(0, num_seqs[0], sequence, jnp.zeros((T, H, dv), dt))
