"""Grouped-query attention over pages whose KEY is wider than their VALUE
(``dk`` = 192 beside ``dv`` = 128: MiMo-V2), with an optional SINK: a
learned logit a query head that joins the softmax's maximum and
denominator and carries no value. The library kernel
(``jax.experimental.pallas.ops.tpu.ragged_paged_attention``) takes one
width for K and V, a multiple of 128, and Mosaic refuses it at 256, so this
layer's attention is first-party, as ops/latent_attention.py is for the
latent layer.

**The page.** ``[rows, w]`` with ``w = dv`` lanes (128) and ``rows = 2.5
n_kv ps``: ``n_kv x (dk + dv)`` values a token and not one more (1,280 = 10
rows a token at 4 KV heads, 2,560 = 20 at 8), written and read by this
module alone (:func:`gqa_page_shape`, :func:`write_gqa_rows`). A page is
``2.5 n_kv`` TILES of ``[ps, w]``, token ``t`` in row ``t`` of each:

- tile ``h`` (``h < n_kv``): the first ``w`` values of KV head ``h``'s key;
- tile ``n_kv + j`` (``j < n_kv / 2``): the keys' other ``dk - w = w / 2``
  values of heads ``2 j`` (left half of the row) and ``2 j + 1`` (right);
- tile ``1.5 n_kv + h``: head ``h``'s value.

Every row a token writes is a WHOLE row of its own (no partner token, as a
latent page's ``kr`` row has), every slice the kernel takes is a whole tile,
and a head's scores are two products of 128 lanes: ``q[:w] . k_lo`` and
``[q[w:] | 0]`` or ``[0 | q[w:]]`` against the pair's row as stored. Why not
``[ps, n_kv, dk + dv]``: 320 is no multiple of the TPU's 128 lanes
(ops/latent_attention.py, "The page", says what that cost on the v5e), and
padding V to 192 or both to 256 caches values nobody published.

Two shapes, as ops/ragged_attention.py has them:

- **decode** (:func:`gqa_decode_attention`, one query a sequence): on a TPU,
  for a page whose tiles are whole (:func:`decode_impl`), ONE Pallas kernel
  (:func:`gqa_decode_pallas`): table and lengths by scalar prefetch, the
  pages left in HBM and fetched a page a DMA into a ring of VMEM blocks,
  each lane walking its own pages and no further, every cached byte read
  once and used there for scores and values, a KV head's whole group of
  query heads against it as one product. The sink starts the running
  maximum and denominator. A window layer's call is handed the window's
  table and the shortened ``kv_lens`` (model.split_tables) and masks the
  keys before ``kv_len - window``.
- **ragged** (:func:`gqa_ragged_attention`: prefill waves, chunks, mixed
  batches; causal, windowed, the sink): on a TPU, for the same pages, ONE
  Pallas kernel too (:func:`gqa_ragged_pallas`, PR 48). Its unit of work is
  an ITEM: the rows of one sequence inside one tile of 128 flat rows, every
  query head of them. XLA lists the items from the batch's own lengths
  (:func:`_wave_items`: tile, sequence, rows, first position, the page its
  keys start on and how many KV blocks they fill) and hands the list in by
  scalar prefetch; the kernel walks each item's blocks and no other
  (causality and the window are loop bounds, a mask only in the blocks the
  bounds cut), pages by DMA through the table into a ring as the decode
  kernel's, each block used by every KV head's group, the scores and their
  sums in VMEM, queries read and output written in the batch's own ``[T,
  H, d]`` (no layout pass of XLA's on either side), the
  item's rows stored once. Elsewhere (the CPU, the tiny rehearsal's page)
  a chunked ``jax.numpy`` walk (:func:`gqa_ragged_jnp`) that the tests hold
  the kernel to: a sequence at a time, a block of its queries at a time, a
  chunk of its pages at a time and only the chunks that block can see,
  never ``[T, span]``; its scores pass through HBM. The walk is also the
  decode call's path where the kernel's geometry does not fit.

:func:`gqa_attention_ref` gathers every page a table names for every row:
the definition the tests hold both to, for the CPU only, and an error on a
TPU. Which path a program traced is counted like the other kernels'
(``dynamo_engine_attention_calls_traced_total``: ``shape`` ``gqa-decode`` /
``gqa-ragged``, with ``window-`` before it for a window layer's call,
``impl`` ``pallas`` / ``jnp`` in both shapes).
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

# Query rows a block and pages a chunk of the ragged walk: a turn is 128
# rows x the group against 512 keys of their own sequence (a window layer's
# chunks are 4 pages: a block of 128 queries sees 255 keys).
_RAGGED_QUERIES_PER_BLOCK = 128
_RAGGED_PAGES_PER_CHUNK = 16
_RAGGED_WINDOW_PAGES_PER_CHUNK = 4
# Bytes a KV block of the decode kernel's ring (the pages a block follow
# from it: 32 of a full layer's 80 KB, 16 of a window layer's 160 KB at the
# published sizes) and blocks in the ring: constants of the shape, swept on
# the v5e by tools/gqa_decode_bench.py at the cell's shapes, 32 lanes (PERF.md
# section 5, PR 46; us a call, pages a block x ring): a full layer 8 x 2 / 8 x 3
# / 16 x 2 / 16 x 3 / 32 x 2 / 32 x 3: 1,444 / 1,199 / 1,257 / 1,188 / 1,212 /
# 1,192 (1,040 at the HBM rate); a window layer 8 x 3 / 16 x 3 / 32 x 3: 127 /
# 105 / 112 (a lane has one block of 5 or 6 pages: what is left is a lane's
# fixed cost). Three blocks of 2.5 MB are 7.5 MB of Mosaic's 16.
_KERNEL_BLOCK_BYTES = 2560 * 1024
_KERNEL_BLOCKS_IN_RING = 3
# Rows a KV head's group of query heads is padded to in the kernel: a whole
# tile of bf16 sublanes.
_GROUP_ROWS = 16
# The wave kernel's shape (:func:`gqa_ragged_pallas`): flat query rows an
# item, pages a KV block of a full and of a window layer, blocks in the ring,
# and the rows (queries x a KV head's group) a product takes at a time. A
# window layer's block of 8 pages holds every key an item sees (127 before
# its first row and its 128 rows) where the item's first row stands on a
# multiple of 128, as a prompt's chunks do; an item elsewhere takes two.
# Swept on the v5e by tools/gqa_wave_bench.py (PERF.md section 5, PR 48), one
# 2,048-row chunk behind 4,096 tokens, ms a call, the walk 6.63 / 1.44 (full /
# window): pages a block 8 / 12 / 16 / 24 / 32: 3.80 / 3.82 / 3.79 / 3.92 /
# 4.07 and 0.71 / 0.89 / 0.96 / 1.20 / 1.40; rows an item 64 / 128 / 256: 3.84
# / 3.79 / 3.92 and 0.84 / 0.89 / 1.00; rows a product 512 / 1,024 / 2,048:
# 3.79 / 3.79 / 3.78 and 0.80 / 0.89 / 0.89; a ring of 2 / 3: 3.80 / 3.79 and
# 0.79 / 0.89 (that sweep with the queries padded to 256 lanes by XLA; as
# they are, 3.62 and 0.67, 0.57 at 8 pages and a ring of 2).
_WAVE_QUERIES_PER_ITEM = 128
_WAVE_PAGES_PER_BLOCK = 16
_WAVE_WINDOW_PAGES_PER_BLOCK = 8
_WAVE_BLOCKS_IN_RING = 2
_WAVE_PRODUCT_ROWS = 1024


def gqa_page_shape(page_size: int, n_kv: int, dk: int, dv: int) -> tuple[int, int]:
    """``(rows, lanes)`` of one page of ``page_size`` tokens (module
    docstring, "The page")."""
    if 2 * dk != 3 * dv or n_kv % 2:
        raise ValueError(
            f"a wide-key page needs a key of 1.5 values' width and an even count "
            f"of KV heads; got dk={dk}, dv={dv}, n_kv={n_kv}")
    return n_kv * 5 // 2 * page_size, dv


def _page_size(pages: jax.Array, n_kv: int) -> int:
    return pages.shape[-2] // (n_kv * 5 // 2)


def write_gqa_rows(
    pages: jax.Array,        # [n_pages, rows, w]
    write_pages: jax.Array,  # [T] i32
    write_offs: jax.Array,   # [T] i32 — the token's slot in its page
    k: jax.Array,            # [T, n_kv, dk]
    v: jax.Array,            # [T, n_kv, dv]
) -> jax.Array:
    """Scatter ``T`` tokens' keys and values into their pages as whole rows,
    ``2.5 n_kv`` a token, in one scatter."""
    T, n_kv, _ = k.shape
    w = v.shape[-1]
    ps = _page_size(pages, n_kv)
    values = jnp.concatenate(
        [k[..., :w], k[..., w:].reshape(T, n_kv // 2, w), v], axis=1).astype(pages.dtype)
    rows = (jnp.arange(values.shape[1], dtype=jnp.int32)[None, :] * ps
            + write_offs[:, None])
    return pages.at[write_pages[:, None], rows].set(values)


def _split(g: jax.Array, n_kv: int):
    """Gathered pages ``[..., rows, w]`` as (``k`` ``[..., n_kv, ps, dk]``,
    ``v`` ``[..., n_kv, ps, dv]``), tokens in order."""
    ps, w = _page_size(g, n_kv), g.shape[-1]
    lead = g.shape[:-2]
    tiles = g.reshape(*lead, -1, ps, w)
    k_hi = tiles[..., n_kv:n_kv + n_kv // 2, :, :].reshape(*lead, n_kv // 2, ps, 2, w // 2)
    k_hi = jnp.moveaxis(k_hi, -2, -3).reshape(*lead, n_kv, ps, w // 2)
    k = jnp.concatenate([tiles[..., :n_kv, :, :], k_hi], axis=-1)
    return k, tiles[..., n_kv + n_kv // 2:, :, :]


def _shape_name(decode: bool, window: int | None) -> str:
    return ("window-" if window else "") + ("gqa-decode" if decode else "gqa-ragged")


def _refuse_gather_on_tpu() -> None:
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "gqa_attention_ref gathers every page of a table for every row: it is the "
            "CPU's and the tests' definition, never a TPU program's path")


def gqa_attention_ref(
    q: jax.Array,             # [T, H, dk]
    pages: jax.Array,         # [n_pages, rows, w]
    kv_lens: jax.Array,       # [S] i32
    block_tables: jax.Array,  # [S, pages_per_seq] i32
    cu_q_lens: jax.Array | None,  # [S + 1] i32; None: the decode shape
    num_seqs: jax.Array,      # [1] i32
    *,
    n_kv: int,
    sm_scale: float,
    window: int | None = None,
    sinks: jax.Array | None = None,   # [H] f32
) -> jax.Array:               # [T, H, dv]
    """The whole-gather definition (ops/ragged_attention.py's
    ``ragged_paged_attention_ref`` at this page): float32 throughout."""
    _refuse_gather_on_tpu()
    T, H, _ = q.shape
    if cu_q_lens is None:
        cu_q_lens = jnp.arange(T + 1, dtype=jnp.int32)
    S, width = block_tables.shape
    ps = _page_size(pages, n_kv)
    span = width * ps
    t = jnp.arange(T, dtype=jnp.int32)
    seq = jnp.minimum(jnp.sum(t[:, None] >= cu_q_lens[None, 1:], axis=1), S - 1)
    valid = t < cu_q_lens[num_seqs[0]]
    q_len = cu_q_lens[seq + 1] - cu_q_lens[seq]
    pos = kv_lens[seq] - q_len + (t - cu_q_lens[seq])
    k, v = _split(pages[block_tables].astype(jnp.float32), n_kv)   # [S, width, n_kv, ps, d]
    k = jnp.moveaxis(k, 2, 1).reshape(S, n_kv, span, -1)[seq]       # [T, n_kv, span, dk]
    v = jnp.moveaxis(v, 2, 1).reshape(S, n_kv, span, -1)[seq]
    qg = q.reshape(T, n_kv, H // n_kv, -1).astype(jnp.float32)
    s = jnp.einsum("thgd,thjd->thgj", qg, k) * sm_scale
    j = jnp.arange(span, dtype=jnp.int32)
    seen = (j[None, :] <= pos[:, None]) & valid[:, None]
    if window is not None:
        seen = seen & (j[None, :] > pos[:, None] - window)
    s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
    if sinks is not None:
        b = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(1, n_kv, -1, 1), (*s.shape[:3], 1))
        s = jnp.concatenate([s, b], axis=-1)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("thgj,thjd->thgd", p[..., :span], v)
    out = jnp.where(valid[:, None, None, None], out, 0.0)
    return out.reshape(T, H, -1).astype(q.dtype)


def gqa_ragged_attention(
    q, pages, kv_lens, block_tables, cu_q_lens, num_seqs, *,
    n_kv: int, sm_scale: float, window: int | None = None, sinks=None,
) -> jax.Array:
    """The ragged shape, the implementation chosen as the decode shape's is
    (:func:`ragged_impl`): the wave kernel (:func:`gqa_ragged_pallas`) on a
    TPU over whole tiles, the chunked walk (:func:`gqa_ragged_jnp`) on the
    CPU and at the tiny rehearsal's page. Under a scope of its own: the
    decode kernel's metrics read ``gqa_paged_attention`` alone."""
    with jax.named_scope("gqa_ragged_attention"):
        impl = ragged_impl(jax.default_backend(), pages, n_kv, q.shape[1])
        _count_traced(_shape_name(False, window), impl)
        if impl == "pallas":
            return gqa_ragged_pallas(q, pages, kv_lens, block_tables, cu_q_lens, num_seqs,
                                     sinks, n_kv=n_kv, sm_scale=sm_scale, window=window)
        return gqa_ragged_jnp(q, pages, kv_lens, block_tables, cu_q_lens, num_seqs,
                              n_kv=n_kv, sm_scale=sm_scale, window=window, sinks=sinks)


def gqa_ragged_jnp(
    q, pages, kv_lens, block_tables, cu_q_lens, num_seqs, *,
    n_kv: int, sm_scale: float, window: int | None = None, sinks=None,
) -> jax.Array:
    """Plain ``jax.numpy``, never ``[T, span]``: a sequence at a time, a
    block of ``_RAGGED_QUERIES_PER_BLOCK`` of its queries at a time, and of
    its pages only the chunks that hold a key that block sees: from the
    chunk of its first query's oldest visible key (the table's first for a
    full layer) to the chunk of its last query's own. Positions are relative
    to the table's first page (a query's is ``kv_lens - q_len + i``), so a
    window layer's shifted table and shortened ``kv_lens`` need no word
    here. Float32 scores, running maximum, sum and accumulator; the weights
    cast to the page's dtype for the value products. Rows past the last
    sequence come out zero."""
    T, H, _ = q.shape
    G, dt, dv = H // n_kv, q.dtype, pages.shape[-1]
    if cu_q_lens is None:
        cu_q_lens = jnp.arange(T + 1, dtype=jnp.int32)
    ps = _page_size(pages, n_kv)
    C = min(_RAGGED_WINDOW_PAGES_PER_CHUNK if window else _RAGGED_PAGES_PER_CHUNK,
            block_tables.shape[1])
    span = C * ps
    tables = jnp.pad(block_tables, ((0, 0), (0, (-block_tables.shape[1]) % C)))
    QB = min(_RAGGED_QUERIES_PER_BLOCK, T)
    key_off = jnp.arange(span, dtype=jnp.int32)
    row_off = jnp.arange(QB, dtype=jnp.int32)
    if sinks is None:
        m0 = jnp.full((n_kv, QB, G), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((n_kv, QB, G), jnp.float32)
    else:   # the sink: a column of the softmax with no value
        m0 = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(n_kv, 1, G), (n_kv, QB, G))
        l0 = jnp.ones((n_kv, QB, G), jnp.float32)

    def sequence(s, out):
        q0 = cu_q_lens[s]
        qn = cu_q_lens[s + 1] - q0
        before = kv_lens[s] - qn           # tokens in cache before this step's rows

        def block(i, out):
            # the block's rows as a window of QB flat rows inside the batch
            start = q0 + i * QB
            first = jnp.minimum(start, T - QB)
            rows = first + row_off
            mine = (rows >= start) & (rows < q0 + qn)
            pos = before + rows - q0
            qb = jax.lax.dynamic_slice_in_dim(q, first, QB).reshape(QB, n_kv, G, -1)
            qb = jnp.moveaxis(qb, 1, 0)                                    # [n_kv, QB, G, dk]
            lo = before + i * QB
            hi = jnp.minimum(lo + QB, kv_lens[s]) - 1
            c_lo = 0 if window is None else jnp.maximum(lo - window + 1, 0) // span

            def chunk(c, carry):
                m, l, acc = carry
                ids = jax.lax.dynamic_slice(tables, (s, c * C), (1, C))[0]
                k, v = _split(pages[ids], n_kv)                # [C, n_kv, ps, d]
                k = jnp.moveaxis(k, 1, 0).reshape(n_kv, span, -1)
                v = jnp.moveaxis(v, 1, 0).reshape(n_kv, span, -1)
                sc = jnp.einsum("hqgd,hjd->hqgj", qb, k,
                                preferred_element_type=jnp.float32) * sm_scale
                key_pos = c * span + key_off
                live = mine[:, None] & (key_pos[None, :] <= pos[:, None])
                if window is not None:
                    live = live & (key_pos[None, :] > pos[:, None] - window)
                live = live[None, :, None, :]
                sc = jnp.where(live, sc, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
                p = jnp.where(live, jnp.exp(sc - m_new[..., None]), 0.0)
                alpha = jnp.exp(m - m_new)
                return (m_new, l * alpha + jnp.sum(p, axis=-1),
                        acc * alpha[..., None] + jnp.einsum(
                            "hqgj,hjd->hqgd", p.astype(pages.dtype), v,
                            preferred_element_type=jnp.float32))

            _, l, acc = jax.lax.fori_loop(
                c_lo, hi // span + 1, chunk,
                (m0, l0, jnp.zeros((n_kv, QB, G, dv), jnp.float32)))
            o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dt)
            o = jnp.moveaxis(o, 0, 1).reshape(QB, H, dv)
            here = jax.lax.dynamic_slice_in_dim(out, first, QB)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(mine[:, None, None], o, here), first, 0)

        return jax.lax.fori_loop(0, (qn + QB - 1) // QB, block, out)

    return jax.lax.fori_loop(0, num_seqs[0], sequence, jnp.zeros((T, H, dv), dt))


def decode_impl(backend: str, pages: jax.Array, n_kv: int) -> str:
    """Which implementation a decode call over ``pages`` gets on
    ``backend``: ``"pallas"`` on a TPU where the page's tiles are whole for
    the kernel (128 lanes a row and a page's tokens a whole number of the
    dtype's sublane tiles: 16 rows of bf16, 8 of f32), else ``"jnp"`` (the
    CPU; the tiny rehearsal's 16-lane page). The label of the call's
    counter."""
    sublanes = 32 // pages.dtype.itemsize
    fits = (pages.shape[-1] == 128 and jnp.issubdtype(pages.dtype, jnp.floating)
            and _page_size(pages, n_kv) % sublanes == 0)
    return "pallas" if backend == "tpu" and fits else "jnp"


def ragged_impl(backend: str, pages: jax.Array, n_kv: int, heads: int) -> str:
    """Which implementation a ragged call of ``heads`` query heads gets:
    the wave kernel where the decode kernel's geometry holds AND a KV head's
    group is whole sublane tiles of float32 (8 rows: the kernel takes a
    group's rows out of the batch's own ``[T, H, dk]`` and puts them back),
    else the walk."""
    fits = decode_impl(backend, pages, n_kv) == "pallas" and heads // n_kv % 8 == 0
    return "pallas" if fits else "jnp"


def gqa_decode_attention(
    q: jax.Array,             # [B, H, dk] — one query a sequence
    pages: jax.Array,         # [n_pages, rows, w] (:func:`gqa_page_shape`)
    kv_lens: jax.Array,       # [B] i32 — tokens the table holds incl. this one (>= 1)
    block_tables: jax.Array,  # [B, pages_per_seq] i32
    *,
    n_kv: int,
    sm_scale: float,
    window: int | None = None,
    sinks: jax.Array | None = None,   # [H] f32
) -> jax.Array:               # [B, H, dv]
    """One algorithm, the implementation chosen from what the call can
    observe (:func:`decode_impl`). The kernel under ONE scope name in both
    layer kinds (``gqa_paged_attention``): a trace's reader divides the
    scope's seconds by the step's attention calls."""
    with jax.named_scope("gqa_paged_attention"):
        impl = decode_impl(jax.default_backend(), pages, n_kv)
        _count_traced(_shape_name(True, window), impl)
        if impl == "pallas":
            return gqa_decode_pallas(q, pages, kv_lens, block_tables, sinks, n_kv=n_kv,
                                     sm_scale=sm_scale, window=window)
        return gqa_ragged_jnp(q, pages, kv_lens, block_tables, None,
                              jnp.asarray([q.shape[0]], jnp.int32), n_kv=n_kv,
                              sm_scale=sm_scale, window=window, sinks=sinks)


def _decode_kernel(
    lens_ref,      # SMEM [B] i32 (scalar prefetch)
    tables_ref,    # SMEM [B * width] i32 (scalar prefetch), lane after lane
    q_lo_ref,      # VMEM [1, n_kv R, w] — each KV head's group, its first w values
    q_hi_ref,      # VMEM [1, n_kv R, w] — the other w / 2, left or right by the head's parity
    sink_ref,      # VMEM [n_kv R, w] f32 — a head's sink on every lane (_NEG_INF: none)
    pages_ref,     # HBM  [n_pages, rows, w]
    out_ref,       # VMEM [1, n_kv R, w]
    buf,           # VMEM [K, N, rows, w] — a ring of K KV blocks of N pages
    sems,          # DMA semaphores [K], one a buffer
    ring_ref,      # SMEM [4] — the ring's state from one grid step to the next
    m_ref, l_ref,  # VMEM [n_kv R, w] f32, every lane of a row the same
    acc_ref,       # VMEM [n_kv R, w] f32
    *, sm_scale: float, width: int, n_kv: int, window: int | None,
):
    """A grid step is one lane, which walks its own ``ceil(kv_len / (N
    ps))`` KV blocks; every (lane, block) of the call is one link of a chain
    through the ring of buffers, asked for ``K - 1`` links before it is
    computed on, across lanes and grid steps alike
    (ops/latent_attention.py's ``_decode_kernel``, whose ring this is).

    A block is computed on a PAIR of KV heads at a time: the pair's
    ``k_hi`` tile as stored against both heads' ``[q_hi | 0]`` / ``[0 |
    q_hi]`` rows in one product, each head's ``k_lo`` tile against its group,
    each head's weights against its ``v`` tile. ``R`` rows a head: its group
    padded to a whole sublane tile (the padding's scores are zeros, its
    output dropped by the caller). A lane's last block is masked past
    ``kv_len``; a window layer's every block before ``kv_len - window``
    too."""
    K, N = buf.shape[:2]
    w = buf.shape[-1]
    ps = _page_size(buf, n_kv)
    R = q_lo_ref.shape[1] // n_kv
    span = N * ps
    quarters = sorted({max(1, N * k // 4) for k in (1, 2, 3, 4)})
    lane, B = pl.program_id(0), pl.num_programs(0)

    each_page, fetch = page_chain(lambda lane: lens_ref[lane], tables_ref, pages_ref, buf, sems,
                                  width=width, page_tokens=ps, lanes=B)
    pl.when(lane == 0)(lambda: start_chain(fetch, buf, ring_ref))

    n_tok = lens_ref[lane]
    n_blk = pl.cdiv(n_tok, span)
    sink = sink_ref[...]
    m_ref[...] = sink
    l_ref[...] = jnp.where(sink > 0.5 * _NEG_INF, 1.0, 0.0)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    nt = (((1,), (1,)), ((), ()))      # q . k^T: both contract their lanes

    def attend(i, slot, n: int, masked: bool):
        """Block ``i`` of this lane, the first ``n`` pages of buffer
        ``slot``, into the running max, sum and accumulator."""
        def tile(t):
            return buf[slot, pl.ds(0, n), pl.ds(t * ps, ps), :].reshape(n * ps, w)

        if masked:
            col = jax.lax.broadcasted_iota(jnp.int32, (R, n * ps), 1)
            live = i * span + col < n_tok
            if window is not None:
                live = live & (i * span + col >= n_tok - window)
        for j in range(n_kv // 2):
            rows2 = pl.ds(2 * j * R, 2 * R)
            s_hi = jax.lax.dot_general(q_hi_ref[0, rows2, :], tile(n_kv + j), nt,
                                       preferred_element_type=jnp.float32)
            for h in (2 * j, 2 * j + 1):
                rows = pl.ds(h * R, R)
                s = (jax.lax.dot_general(q_lo_ref[0, rows, :], tile(h), nt,
                                         preferred_element_type=jnp.float32)
                     + s_hi[(h - 2 * j) * R:(h - 2 * j + 1) * R]) * sm_scale
                if masked:
                    s = jnp.where(live, s, _NEG_INF)
                m_prev = m_ref[rows, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new[:, :1])
                if masked:
                    p = jnp.where(live, p, 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
                m_ref[rows, :] = m_new
                acc_ref[rows, :] = acc_ref[rows, :] * alpha + jnp.dot(
                    p.astype(buf.dtype), tile(n_kv + n_kv // 2 + h),
                    preferred_element_type=jnp.float32)

    def block(i, ring):
        slot, *ahead = ring
        ahead = fetch(ahead)          # into the buffer the link before this one left
        each_page(lane, i, slot, True)
        # A lane's last block is masked (a window layer's every block), and
        # computed on as many quarters of the buffer as hold its pages.
        have = pl.cdiv(n_tok, ps) - i * N
        pl.when(have > N)(lambda: attend(i, slot, N, window is not None))
        for lo, n in zip([0, *quarters], quarters):
            pl.when((have > lo) & (have <= n))(lambda n=n: attend(i, slot, n, True))
        return (jnp.where(slot + 1 == K, 0, slot + 1), *ahead)

    ring = jax.lax.fori_loop(0, n_blk, block, tuple(ring_ref[i] for i in range(4)))
    for i in range(4):
        ring_ref[i] = ring[i]
    out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


# jitted so that a program's layers of a kind, and every program of a width,
# share ONE trace of the kernel
@functools.partial(jax.jit, static_argnames=(
    "n_kv", "sm_scale", "window", "pages_per_block", "blocks_in_ring"))
def gqa_decode_pallas(
    q, pages, kv_lens, block_tables, sinks=None, *, n_kv: int, sm_scale: float,
    window: int | None = None, pages_per_block: int | None = None,
    blocks_in_ring: int = _KERNEL_BLOCKS_IN_RING,
):
    """The decode attention as one Pallas TPU kernel over the wide-key pages
    (:func:`_decode_kernel`). f32 scores, running max and sum, and
    accumulator; the weights cast to the page's dtype for the value
    products, as :func:`gqa_ragged_jnp` does. A lane walks its own pages,
    and its digits depend on them and on its length alone. Needs a geometry
    :func:`decode_impl` accepts."""
    B, H, dk = q.shape
    w = pages.shape[-1]
    G = H // n_kv
    R = -(-G // _GROUP_ROWS) * _GROUP_ROWS
    page_bytes = pages.shape[-2] * w * pages.dtype.itemsize
    N = pages_per_block or max(1, _KERNEL_BLOCK_BYTES // page_bytes)
    N = max(1, min(N, block_tables.shape[1]))
    # each KV head's group, padded to R rows; the key's last w / 2 values
    # on the half of the row the pair's tile keeps that head's on
    qg = jnp.pad(q.reshape(B, n_kv, G, dk), ((0, 0), (0, 0), (0, R - G), (0, 0)))
    hi, zeros = qg[..., w:], jnp.zeros((B, n_kv, R, w // 2), q.dtype)
    odd = (jnp.arange(n_kv) % 2 == 1)[None, :, None, None]
    q_hi = jnp.where(odd, jnp.concatenate([zeros, hi], -1), jnp.concatenate([hi, zeros], -1))
    if sinks is None:
        sink = jnp.full((n_kv * R, w), _NEG_INF, jnp.float32)
    else:
        sink = jnp.pad(sinks.astype(jnp.float32).reshape(n_kv, G), ((0, 0), (0, R - G)))
        sink = jnp.broadcast_to(sink.reshape(n_kv * R, 1), (n_kv * R, w))
    # A table names pages of this array or the DMA engine faults.
    block_tables = jnp.clip(block_tables, 0, pages.shape[0] - 1)
    by_lane = lambda b, *_: (b, 0, 0)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, width=block_tables.shape[1],
                          n_kv=n_kv, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, n_kv * R, w), by_lane),
                pl.BlockSpec((1, n_kv * R, w), by_lane),
                pl.BlockSpec((n_kv * R, w), lambda b, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, n_kv * R, w), by_lane),
            scratch_shapes=[
                pltpu.VMEM((blocks_in_ring, N, *pages.shape[1:]), pages.dtype),
                pltpu.SemaphoreType.DMA((blocks_in_ring,)),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((n_kv * R, w), jnp.float32),
                pltpu.VMEM((n_kv * R, w), jnp.float32),
                pltpu.VMEM((n_kv * R, w), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_kv * R, w), q.dtype),
        # The chain of copies runs from one grid step into the next. The
        # tables were clipped above: no copy's bounds are checked.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        name="gqa_decode_attention_kernel",
    )(kv_lens.astype(jnp.int32), block_tables.astype(jnp.int32).reshape(-1),
      qg[..., :w].reshape(B, n_kv * R, w), q_hi.reshape(B, n_kv * R, w), sink, pages)
    return out.reshape(B, n_kv, R, w)[:, :, :G].reshape(B, H, w)


def _turn_lanes(x: jax.Array, by: int) -> jax.Array:
    """``x`` with its lanes turned by ``by`` (Mosaic turns 32-bit lanes: a
    narrower dtype goes as the words its sublanes pack into)."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, by, 1)
    return pltpu.bitcast(pltpu.roll(pltpu.bitcast(x, jnp.uint32), by, 1), x.dtype)


_ITEM_FIELDS = 7   # tile, seq, row_lo, row_hi, pos0, first_page, n_blocks


def _wave_items(cu_q_lens, kv_lens, num_seqs, *, rows: int, QB: int, ps: int, N: int,
                table_pages: int, window: int | None):
    """The wave kernel's work list, by XLA from the batch's own lengths.

    The flat rows are cut at every tile of ``QB`` rows and at every live
    sequence's end: ``rows / QB + S`` pieces, a LIVE one holding rows of one
    sequence inside one tile. For each: the tile, the sequence, the rows'
    places in the tile ``[row_lo, row_hi)``, the position ``pos0`` the tile's
    row 0 would have in that sequence (relative to the table's first page,
    as everywhere in this module) and the keys it can see as ``n_blocks`` KV
    blocks of ``N`` pages from column ``first_page`` of the table: from the
    table's first page (from the page of its first row's oldest key in the
    window, for a window layer: blocks start where the item's keys do, not
    on a multiple of ``N``) up to the page of its last row's own key. Live
    items come first, in row order; the dead ones behind them keep the last
    live tile, so that the kernel's pipeline moves nothing for them.
    Returns ``(n_live [1], items [7 I])``, field after field."""
    S = kv_lens.shape[0]
    n_tiles = rows // QB
    live_seq = jnp.arange(S, dtype=jnp.int32) < num_seqs[0]
    ends = jnp.where(live_seq, jnp.minimum(cu_q_lens[1:S + 1], rows), rows)
    lo = jnp.sort(jnp.concatenate([jnp.arange(n_tiles, dtype=jnp.int32) * QB, ends]))
    hi = jnp.concatenate([lo[1:], jnp.full((1,), rows, jnp.int32)])
    seq = jnp.minimum(jnp.sum(ends[None, :] <= lo[:, None], axis=1), S - 1).astype(jnp.int32)
    tile = jnp.minimum(lo // QB, n_tiles - 1)
    q0 = cu_q_lens[seq]
    pos0 = kv_lens[seq] - (cu_q_lens[seq + 1] - q0) + tile * QB - q0
    first_pos, last_pos = pos0 + lo - tile * QB, pos0 + hi - tile * QB - 1
    live = (hi > lo) & live_seq[seq] & (lo >= q0) & (last_pos >= 0)
    first_key = 0 if window is None else jnp.maximum(first_pos - window + 1, 0)
    first_page = jnp.clip(first_key // ps, 0, table_pages - 1)
    n_blocks = jnp.minimum(last_pos // ps - first_page, table_pages - 1 - first_page) // N + 1
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True)
    at_last = order[jnp.maximum(n_live - 1, 0)]
    fields = [jnp.where(live, f, 0)[order] for f in (
        tile, seq, lo - tile * QB, hi - tile * QB, pos0, first_page, n_blocks)]
    fields[0] = jnp.where(jnp.arange(lo.shape[0]) < n_live, fields[0], tile[at_last])
    return n_live.reshape(1), jnp.concatenate(fields).astype(jnp.int32)


def _ragged_kernel(
    n_live_ref,    # SMEM [1] i32 (scalar prefetch) — live items, the first of the grid
    items_ref,     # SMEM [7 I] i32 (scalar prefetch) — :func:`_wave_items`
    tables_ref,    # SMEM [S * width] i32 (scalar prefetch), sequence after sequence
    q_ref,         # VMEM [QB, H, dk] — a tile's queries as the batch holds them
    sink_ref,      # VMEM [n_kv QB G, w] f32 — a row's head's sink on every lane (_NEG_INF: none)
    pages_ref,     # HBM  [n_pages, rows, w]
    out_ref,       # VMEM [QB, H, w]
    buf,           # VMEM [K, N, rows, w] — a ring of K KV blocks of N pages
    sems,          # DMA semaphores [K], one a buffer
    ring_ref,      # SMEM [4] — the ring's state from one grid step to the next
    m_ref, l_ref,  # VMEM [n_kv QB G, w] f32, every lane of a row the same
    acc_ref,       # VMEM [n_kv QB G, w] f32
    *, sm_scale: float, width: int, n_kv: int, group: int, window: int | None, product_rows: int,
):
    """A grid step is one ITEM (:func:`_wave_items`): ``QB`` flat rows of
    one tile, those of ``[row_lo, row_hi)`` one sequence's, every query head
    of them against the KV blocks the item can see and no other: causality
    and the window are the loop's bounds, and a mask only in the blocks the
    bounds cut. Every (item, block) of the call is one link of a chain
    through the ring of buffers, asked for ``K - 1`` links before it is
    computed on, across items and grid steps alike (:func:`_decode_kernel`'s
    ring; here every block is ``N`` whole pages, the table padded for it).

    A block is computed on ``product_rows`` rows at a time: ``IB`` queries'
    group of one KV head ``h``, taken out of the tile as the batch holds it
    (``[QB, H, dk]``: no pass of XLA's lays the queries out). Their scores
    are two products, ``q[:w] . k_lo`` and ``q[w:]`` against the first ``w /
    2`` lanes of the pair's tile, which hold the even head's keys and, with
    the tile's lanes turned half way, the odd head's; then, a vreg's lanes
    of keys at a time, the scale, the mask, the running maximum, the
    exponentials and their sum in float32; the
    weights cast to the page's dtype against tile ``1.5 n_kv + h`` onto the
    accumulator: :func:`gqa_ragged_jnp`'s statements on
    :func:`_decode_kernel`'s page. The two heads of a pair stand side by
    side in a turn of the loop: independent work for the scheduler to lay
    one's products under the other's exponentials. The states' rows run (h,
    i, g). The item's rows are stored once, at its end, into the tile as
    the batch holds it and under a mask: another sequence's rows of the
    same tile are another item's, and the tile stays in VMEM between
    them."""
    K, N = buf.shape[:2]
    w = buf.shape[-1]
    ps = _page_size(buf, n_kv)
    span = N * ps
    QB = q_ref.shape[0]
    GQ = group * QB                    # a KV head's rows, (i, g)
    RC = product_rows                  # rows a product: IB queries' group
    IB = RC // group
    I = items_ref.shape[0] // _ITEM_FIELDS
    item, n_live = pl.program_id(0), n_live_ref[0]

    def field(f: int, i):
        return items_ref[f * I + i]

    def start(it, blk, slot):
        # every entry read before the first copy starts: a start is a fence
        # to the scheduler, the reads and their sums are not
        entry = field(1, it) * width + field(5, it) + blk * N
        ids = [tables_ref[entry + p] for p in range(N)]
        for p in range(N):
            pltpu.make_async_copy(pages_ref.at[ids[p]], buf.at[slot, p], sems.at[slot]).start()

    def fetch(ahead):
        """Ask for the link the fetch cursor ``ahead`` = (item, block, slot)
        stands on, if there is one, and move it on a link."""
        f_item, f_blk, f_slot = ahead
        pl.when(f_item < n_live)(lambda: start(f_item, f_blk, f_slot))
        more = f_blk + 1 < field(6, jnp.minimum(f_item, I - 1))
        return (jnp.where(more, f_item, jnp.minimum(f_item + 1, I)),
                jnp.where(more, f_blk + 1, 0),
                jnp.where(f_slot + 1 == K, 0, f_slot + 1))

    @pl.when(item == 0)
    def _():
        ahead = (0, 0, 0)
        for _ in range(K - 1):
            ahead = fetch(ahead)
        ring_ref[0] = 0
        for i in range(3):
            ring_ref[1 + i] = ahead[i]

    row_lo, row_hi, pos0 = field(2, item), field(3, item), field(4, item)
    first_key = field(5, item) * ps
    nt = (((1,), (1,)), ((), ()))      # q . k^T: both contract their lanes
    turns = n_kv // 2 * (QB // IB)     # a turn: a PAIR of KV heads, IB queries

    def pair_and_queries(t):
        return t // (QB // IB), t % (QB // IB)

    def attend(blk, slot, masked: bool):
        """KV block ``blk`` of the item, in buffer ``slot``, into every
        row's running max, sum and accumulator."""
        def tile(t):
            return buf[slot, :, pl.ds(pl.multiple_of(t * ps, ps), ps), :].reshape(span, w)

        def product(h, x, k_hi):
            """``IB`` queries from query ``x IB`` of the tile, KV head
            ``h``'s group of each."""
            i0 = pl.multiple_of(x * IB, IB)
            rows_c = pl.ds(pl.multiple_of(h * GQ + x * RC, RC), RC)
            q = q_ref[pl.ds(i0, IB), pl.ds(pl.multiple_of(h * group, group), group), :]
            q = q.reshape(RC, q.shape[-1])
            scores = (jax.lax.dot_general(q[:, :w], tile(h), nt,
                                          preferred_element_type=jnp.float32)
                      + jax.lax.dot_general(q[:, w:], k_hi[:, :q.shape[-1] - w], nt,
                                            preferred_element_type=jnp.float32))
            if masked:
                pos = pos0 + i0 + jax.lax.broadcasted_iota(jnp.int32, (RC, w), 0) // group
                key = first_key + blk * span + jax.lax.broadcasted_iota(jnp.int32, (RC, w), 1)
            s, live = [], []
            for j in range(span // w):     # a vreg's lanes of keys at a time
                s_j = scores[:, j * w:(j + 1) * w] * sm_scale
                if masked:
                    live_j = key + j * w <= pos
                    if window is not None:
                        live_j = live_j & (key + j * w > pos - window)
                    s_j = jnp.where(live_j, s_j, _NEG_INF)
                    live.append(live_j)
                s.append(s_j)
            m_prev = m_ref[rows_c, :]
            m_new = jnp.maximum(m_prev, jnp.max(
                functools.reduce(jnp.maximum, s), axis=1, keepdims=True))
            p = [jnp.exp(s_j - m_new) for s_j in s]
            if masked:
                p = [jnp.where(live_j, p_j, 0.0) for live_j, p_j in zip(live, p)]
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows_c, :] = l_ref[rows_c, :] * alpha + jnp.sum(
                functools.reduce(jnp.add, p), axis=1, keepdims=True)
            m_ref[rows_c, :] = m_new
            acc_ref[rows_c, :] = acc_ref[rows_c, :] * alpha + jnp.dot(
                jnp.concatenate([p_j.astype(buf.dtype) for p_j in p], axis=1),
                tile(n_kv + n_kv // 2 + h), preferred_element_type=jnp.float32)

        def turn(t, _):
            # a PAIR of KV heads side by side, independent for the scheduler:
            # the pair's tile holds the even head's last w / 2 values of a
            # key on its first lanes; the odd head's come there by a turn
            j, x = pair_and_queries(t)
            k_hi = tile(n_kv + j)
            product(2 * j, x, k_hi)
            product(2 * j + 1, x, _turn_lanes(k_hi, w // 2))

        jax.lax.fori_loop(0, turns, turn, None)

    def block(blk, ring):
        slot, *ahead = ring
        ahead = fetch(ahead)          # into the buffer the link before this one left
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot]).wait()
        if window is not None:
            attend(blk, slot, True)
        else:   # masked where the block holds a key the item's first row cannot see
            cut = first_key + (blk + 1) * span - 1 > pos0 + row_lo
            pl.when(cut)(lambda: attend(blk, slot, True))
            pl.when(jnp.logical_not(cut))(lambda: attend(blk, slot, False))
        return (jnp.where(slot + 1 == K, 0, slot + 1), *ahead)

    @pl.when(item < n_live)
    def _():
        sink = sink_ref[...]
        m_ref[...] = sink
        l_ref[...] = jnp.where(sink > 0.5 * _NEG_INF, 1.0, 0.0)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        ring = jax.lax.fori_loop(0, field(6, item), block,
                                 tuple(ring_ref[i] for i in range(4)))
        for i in range(4):
            ring_ref[i] = ring[i]

        def store(t, _):   # a pair's rows of IB queries, as the batch holds them
            j, x = pair_and_queries(t)
            i0 = pl.multiple_of(x * IB, IB)
            o = []
            for h in (2 * j, 2 * j + 1):
                rows_c = pl.ds(pl.multiple_of(h * GQ + x * RC, RC), RC)
                o.append((acc_ref[rows_c, :] / jnp.maximum(l_ref[rows_c, :], 1e-30)
                          ).reshape(IB, group, w))
            o = jnp.concatenate(o, axis=1).astype(out_ref.dtype)
            queries = pl.ds(i0, IB)
            heads = pl.ds(pl.multiple_of(2 * j * group, 2 * group), 2 * group)
            i = i0 + jax.lax.broadcasted_iota(jnp.int32, o.shape, 0)
            out_ref[queries, heads, :] = jnp.where((i >= row_lo) & (i < row_hi), o,
                                                   out_ref[queries, heads, :])

        jax.lax.fori_loop(0, turns, store, None)


# jitted for the same reason as the decode kernel: one trace a layer kind
@functools.partial(jax.jit, static_argnames=(
    "n_kv", "sm_scale", "window", "queries_per_item", "pages_per_block", "blocks_in_ring",
    "product_rows"))
def gqa_ragged_pallas(
    q, pages, kv_lens, block_tables, cu_q_lens, num_seqs, sinks=None, *, n_kv: int,
    sm_scale: float, window: int | None = None,
    queries_per_item: int = _WAVE_QUERIES_PER_ITEM, pages_per_block: int | None = None,
    blocks_in_ring: int = _WAVE_BLOCKS_IN_RING, product_rows: int = _WAVE_PRODUCT_ROWS,
):
    """The ragged attention (prefill waves, chunks, mixed batches) as one
    Pallas TPU kernel over the wide-key pages (:func:`_ragged_kernel`):
    :func:`gqa_ragged_jnp`'s arithmetic with the scores left in VMEM. The
    kernel reads the queries and writes the output a tile of
    ``queries_per_item`` flat rows at a time in the batch's own layout;
    XLA lists the items before the call and zeroes the rows past the last
    sequence after it. Needs a geometry :func:`ragged_impl` accepts."""
    T, H, dk = q.shape
    w = pages.shape[-1]
    G = H // n_kv
    ps = _page_size(pages, n_kv)
    if cu_q_lens is None:
        cu_q_lens = jnp.arange(T + 1, dtype=jnp.int32)
    cu_q_lens, kv_lens = cu_q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32)
    # a tile of rows: whole sublane tiles, no wider than the batch needs
    QB = min(queries_per_item, max(16, 1 << (T - 1).bit_length()))
    n_tiles = -(-T // QB)
    rows = n_kv * G * QB
    RC = G * math.gcd(max(product_rows // G, 1), QB)
    # a KV block: whole vregs of keys (w lanes of scores)
    N = pages_per_block or (_WAVE_WINDOW_PAGES_PER_BLOCK if window else _WAVE_PAGES_PER_BLOCK)
    N = -(-N // (w // math.gcd(ps, w))) * (w // math.gcd(ps, w))
    # A table names pages of this array or the DMA engine faults; a block is
    # N whole columns of it from wherever an item's keys start.
    tables = jnp.pad(jnp.clip(block_tables.astype(jnp.int32), 0, pages.shape[0] - 1),
                     ((0, 0), (0, N)))
    n_live, items = _wave_items(
        cu_q_lens, kv_lens, num_seqs, rows=n_tiles * QB, QB=QB, ps=ps, N=N,
        table_pages=block_tables.shape[1], window=window)

    qp = jnp.pad(q, ((0, n_tiles * QB - T), (0, 0), (0, 0)))   # whole tiles of rows
    if sinks is None:
        sink = jnp.full((rows, w), _NEG_INF, jnp.float32)
    else:
        sink = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(n_kv, 1, G, 1),
                                (n_kv, QB, G, w)).reshape(rows, w)
    by_tile = lambda i, n_live, items, tables: (items[i], 0, 0)   # QB rows a block
    state = pltpu.VMEM((rows, w), jnp.float32)
    ring_bytes = blocks_in_ring * N * pages.shape[-2] * w * pages.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, sm_scale=sm_scale, width=tables.shape[1], n_kv=n_kv,
                          group=G, window=window, product_rows=RC),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(items.shape[0] // _ITEM_FIELDS,),
            in_specs=[
                pl.BlockSpec((QB, H, dk), by_tile),
                pl.BlockSpec((rows, w), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((QB, H, w), by_tile),
            scratch_shapes=[
                pltpu.VMEM((blocks_in_ring, N, *pages.shape[1:]), pages.dtype),
                pltpu.SemaphoreType.DMA((blocks_in_ring,)),
                pltpu.SMEM((4,), jnp.int32),
                state, state, state,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * QB, H, w), q.dtype),
        # The chain of copies runs from one grid step into the next, and a
        # tile's rows from one item into the next. The tables were clipped
        # above: no copy's bounds are checked. VMEM: the three float32
        # states, the sinks, the tile's queries and output twice (the
        # pipeline's), the ring, and room for a product's scores and weights.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True,
            vmem_limit_bytes=(rows * w * (5 * 4 + 6 * q.dtype.itemsize) + ring_bytes
                              + 8 * RC * N * ps * 4 + (16 << 20))),
        name="gqa_ragged_attention_kernel",
    )(n_live, items, tables.reshape(-1), qp, sink, pages)
    out = out[:T]
    t = jnp.arange(T, dtype=jnp.int32)
    seen = (t >= cu_q_lens[0]) & (t < cu_q_lens[jnp.minimum(num_seqs[0], kv_lens.shape[0])])
    return jnp.where(seen[:, None, None], out, jnp.zeros((), out.dtype))
