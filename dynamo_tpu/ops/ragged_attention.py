"""Ragged paged attention: one attention call for prefill, decode, and
mixed batches over the paged KV cache.

Semantics (vLLM-TPU style; the reference outsources this op to vLLM's CUDA
kernels — on TPU it is first-party, SURVEY.md §7 "hard parts"):

- ``q``: ``[T, n_q_heads, d]`` — every scheduled token this step,
  concatenated across sequences (ragged; no per-sequence padding).
- ``kv_pages``: ``[n_pages, page_size, 2 * n_kv_heads, d]`` — the paged KV
  cache for ONE layer, K/V interleaved on the combined-head axis (K at
  even indices, V at odd). The new tokens' K/V must already be written.
- ``kv_lens[s]``: tokens of sequence ``s`` IN CACHE (including this
  step's chunk).
- ``page_indices``: ``[S, pages_per_seq]`` block table per sequence.
- ``cu_q_lens``: ``[S + 1]`` cumulative query lengths; sequence ``s`` owns
  q rows ``cu[s]:cu[s+1]``. Entries past ``num_seqs`` repeat ``cu[num_seqs]``.
  ``None`` is the DECODE SHAPE, said statically by the caller: ``T == S``,
  row ``s`` is sequence ``s`` with one query token (``arange(S + 1)``).
- ``num_seqs``: ``i32[1]`` — valid sequences (dynamic).

Query token ``i`` of sequence ``s`` sits at absolute position
``kv_lens[s] - q_len_s + i`` and attends all cache positions ``<=`` its own
— exactly chunked-prefill causality; a decode step is the ``q_len_s == 1``
special case.

Three implementations, chosen from what a call can observe
(:func:`decode_impl`: no flag, no option, no model's name) and counted by
shape and implementation (:func:`traced_calls`,
``dynamo_engine_attention_calls_traced_total`` on /metrics; logged once at
trace time, :func:`_announce`: the reference on a TPU is a warning, never
silent):

- ``impl="library"``: on a TPU the library Pallas kernel
  (jax.experimental.pallas.ops.tpu.ragged_paged_attention) under this
  module's grids. A decode-shaped call runs it under a grid of its own: one
  sequence a query block, so that a sequence's pass computes on that
  sequence's rows alone (:func:`decode_shape_grid`).
- ``impl="pallas"`` (PR 51, PR 55): a DECODE-shaped call on a TPU over
  bfloat16 pages of 128-wide heads, no window and no int8 scales, takes a
  first-party kernel where its geometry fits (:func:`first_party_decode`):
  GROUP 1 (multi-head layers: as many query heads as KV heads) the kernel
  of ops/mha_attention.py, a GROUP of 2 or more query heads a KV head the
  kernel of ops/grouped_attention.py. The library kernel's decode pass
  converts each block to float32 and moves whole 16-page blocks; the
  first-party kernels keep K and V bfloat16 into the MXU and stream the
  pages in use, and no others, through the ring the other first-party
  decode kernels use (ops/page_ring.py). They read THE SAME PAGE: the
  layout above, its writer (model.write_kv), the waves' library call, the
  prefix cache and the transfer format are untouched (a head-major page
  would make the kernels trivial and would need a wave kernel, a writer and
  a transfer format of its own). Their device ops' names contain
  ``ragged_paged_attention`` like the library's, which is how a trace's
  readers find any of them.
- ``impl="reference"``: elsewhere (CPU test meshes; shapes neither kernel
  tiles) a vectorized jnp reference with identical semantics.

Heads HALF a lane row wide come in through :func:`paired_heads_attention`
(two KV heads a 128-wide row, the same cache bytes, the same kernel).
A layer that attends a sliding WINDOW says ``window=``: the caller hands it
the table of the window's pages alone and ``kv_lens`` shortened by the
tokens before them (:func:`ragged_paged_attention`, "A window").
Under tensor parallelism wrap with :func:`sharded_ragged_attention` —
attention is embarrassingly parallel over heads, so the shard_map has no
collectives.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

log = logging.getLogger("dynamo_tpu.ops.ragged_attention")

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# Explicit Pallas grid for the library kernel's RAGGED calls: 8-page DMA
# batches and 8-query blocks for calls of at most 64 rows (verify rows,
# small chunks and mixed batches), 128-query blocks for prefill waves
# (the kernel's own tuned table can pick whole-wave q blocks whose
# scratch exceeds scoped VMEM at T >= 2048). Constants, as the decode
# grid's are: a sweep passes its grid as an argument.
_SMALL_KV_PAGES_PER_BLOCK = 8
_SMALL_QUERIES_PER_BLOCK = 8
_PREFILL_QUERIES_PER_BLOCK = 128
# A wave's query block holds its queries x ALL the call's query heads (the
# kernel blocks 16 combined KV heads, or all there are), and its body is
# unrolled over the KV heads, each pass on queries x group rows. Up to 32
# heads, 128 queries fit Mosaic's default scoped VMEM (16 MB) and compile in
# seconds. 48 and 72 heads (Laguna's full and window layers, groups of 6 and
# 9 on 8 KV heads) at 128 queries are refused at compile time
# (RESOURCE_EXHAUSTED in vmem: 21 / 32 MB), and under a limit of their own
# they compile for 28 / 146 s a kernel (measured for the v5e, PR 39: a
# program's set-up went from seconds to minutes, a worker's past the
# benchmark's limit for one). At 32 queries they fit the default and compile
# in 2 / 7 s; what it costs is a re-read of the context's K/V for every
# block more: ~2.6 ms a full layer for a 2,048-token chunk at a context of
# 8,192, beside ~2.1 ms of attention FLOPs (PERF.md section 5).
_WIDE_HEADS_MIN = 33
# ... and a pass's rows, queries x GROUP, hold its scores and their exponentials
# in float32: 128 queries x a group of 16 (nemotron_h: 32 query heads on 2 KV
# heads) are 2,048 rows a pass and were refused by 0.7 MB of 16 (compiled for
# the v5e, PR 54); a wave's query block is cut so that a pass has at most this
# many rows (64 queries there; every group up to 8 keeps its 128).
_PREFILL_PASS_ROWS_MAX = 1024
_WIDE_HEADS_QUERIES_PER_BLOCK = 32
# Their KV block: 1,024 tokens, not the 8 pages of the other ragged calls.
# A pass of the kernel's body (one KV head of one KV block for one query
# block) costs about the same whether the block holds 256 or 1,024 keys, so
# a wave's call is its PASSES. The calls alone on the v5e, a 2,048-query
# chunk behind a context of 4,096 at page size 32 (PR 39, calls w1 / w2;
# pages a block 8 / 16 / 32, the queries in pieces where it says so): 48
# heads 9.48 / 5.39 / - ms whole, 8.32 / 4.93 / 3.82 in pieces of 256;
# 72 heads on a window of 512, 10.97 whole at 8, 3.37 / 2.37 / 1.42 in
# pieces of 128 (a piece's table is 21 pages: ONE block). 32 pages x 2
# buffers are 8 MB of Mosaic's 16; 64 were not tried in a served program.
_WIDE_HEADS_KV_TOKENS_PER_BLOCK = 1024

# The grid of a DECODE-SHAPED call: one query a block, 512 KV tokens a
# block (:func:`decode_shape_grid`). The kernel walks a query block's
# sequences one after another and computes each one's pass on the WHOLE
# block's rows (queries x group size), keeping that sequence's; at one
# query a block the rows are the sequence's own. Swept on the v5e with
# jax 0.9.0 by tools/attn_decode_bench.py at the three cells' decode
# shapes, all of page size 32 (PERF.md section 5, PR 28; share of the
# HBM roofline, (8, 8) -> (1, 16 pages)): 28/4 heads x 32 lanes 27.1 ->
# 71.4%, 12/2 heads x 8 / 16 / 32 lanes 30.8 / 32.0 / 31.7 -> 49.7 /
# 55.4 / 54.3%, 16/16 heads x 8 lanes 51.9 -> 52.3%. 16 pages are the
# best or within 2% of it at every shape but 12/2 heads x 8 lanes, where
# 24 pages read 56.1% (and cost the 16/16 shape a fifth: 43.4%). The KV
# block is kept in TOKENS, not pages, because its two VMEM buffers are
# what Mosaic's scoped limit holds it to: 512 tokens x the 16 combined
# heads the kernel blocks at most x 128 x 2 B = 2 MB a buffer, at any
# `--block-size` (16 pages of 128 tokens with 8 KV heads or more are
# refused, RESOURCE_EXHAUSTED in vmem), and in at most the 16 pages
# that were swept (one DMA a page: smaller pages were not measured).
# Constants of the shape, not knobs: the sweep tool passes its own.
_DECODE_QUERIES_PER_BLOCK = 1
_DECODE_KV_TOKENS_PER_BLOCK = 512
_DECODE_KV_PAGES_PER_BLOCK_MAX = 16


def decode_shape_grid(page_size: int, pages_per_seq: int) -> tuple[int, int]:
    """(queries per block, KV pages per block) of a decode-shaped call:
    (1, 16) at page size 32, and never more pages than a table holds
    (the kernel refuses that at trace time)."""
    pages = min(_DECODE_KV_TOKENS_PER_BLOCK // page_size,
                _DECODE_KV_PAGES_PER_BLOCK_MAX, pages_per_seq)
    return _DECODE_QUERIES_PER_BLOCK, max(1, pages)


@functools.cache
def _announce(level: int, message: str) -> None:
    """Log an implementation choice once per distinct message. Called at
    trace time only (the jitted program never runs this)."""
    log.log(level, message)


# Attention calls traced since the process started, by the shape the
# caller stated ("decode" / "ragged"; "window-decode" / "window-ragged" for
# a call with ``window=``) and the implementation chosen
# ("library" / "reference"). The choice is static per compiled program,
# so trace time is where it can be counted: a program of L layers adds L
# (a looped stack's body is traced once).
_TRACED: collections.Counter = collections.Counter()
_TRACED_IMPLS: dict[str, str] = {}   # shape -> "+"-joined impls, kept at trace time
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
    (``+``-joined if more than one; empty before any was traced). A
    dictionary read: the engine asks on every dispatch."""
    return _TRACED_IMPLS.get(shape, "")


def ragged_paged_attention_ref(
    q: jax.Array,             # [T, n_q, d]
    kv_pages: jax.Array,      # [n_pages, page_size, 2*n_kv, d]
    kv_lens: jax.Array,       # [S] i32
    page_indices: jax.Array,  # [S, pages_per_seq] i32
    cu_q_lens: jax.Array | None,  # [S+1] i32; None: the decode shape
    num_seqs: jax.Array,      # [1] i32
    *,
    sm_scale: float,
    kv_scales: jax.Array | None = None,  # [n_pages, page_size, 2*n_kv] f32
    window: int | None = None,  # a query sees its own position and window - 1 before
) -> jax.Array:               # [T, n_q, d]
    T, n_q, d = q.shape
    if cu_q_lens is None:
        cu_q_lens = jnp.arange(T + 1, dtype=jnp.int32)
    n_pages, page_size, n_comb, _ = kv_pages.shape
    n_kv = n_comb // 2
    group = n_q // n_kv
    S, pages_per_seq = page_indices.shape
    span = pages_per_seq * page_size

    t = jnp.arange(T, dtype=jnp.int32)
    # seq_id[t] = s such that cu[s] <= t < cu[s+1]
    seq_id = jnp.sum(t[:, None] >= cu_q_lens[None, 1:], axis=1).astype(jnp.int32)
    seq_id = jnp.minimum(seq_id, S - 1)
    valid_row = t < cu_q_lens[num_seqs[0]]

    q_len = cu_q_lens[seq_id + 1] - cu_q_lens[seq_id]          # [T]
    abs_pos = kv_lens[seq_id] - q_len + (t - cu_q_lens[seq_id])  # [T]

    tables_t = page_indices[seq_id]                      # [T, pages_per_seq]
    offs = jnp.arange(page_size, dtype=jnp.int32)
    slots = (tables_t[:, :, None] * page_size + offs[None, None, :]).reshape(T, span)
    flat = kv_pages.reshape(n_pages * page_size, n_comb, d)
    kv = flat[slots]                                     # [T, span, 2*n_kv, d]
    if kv_scales is not None:
        # Fused dequant-on-gather (int8 pages): the gather above moved
        # HALF the bytes a bf16 cache would; the dequant multiplies the
        # gathered values by their per-slot-per-head scales in registers.
        from dynamo_tpu.engine.kv_quant import dequantize_kv

        scf = kv_scales.reshape(n_pages * page_size, n_comb)[slots]
        kvf = dequantize_kv(kv, scf)
    else:
        kvf = kv.astype(jnp.float32)
    k = kvf[:, :, 0::2, :]                               # [T, span, n_kv, d]
    v = kvf[:, :, 1::2, :]

    qg = q.reshape(T, n_kv, group, d).astype(jnp.float32)
    s = jnp.einsum("thgd,tshd->thgs", qg, k) * sm_scale  # [T, n_kv, group, span]
    pos = jnp.arange(span, dtype=jnp.int32)
    mask = (pos[None, :] <= abs_pos[:, None]) & (pos[None, :] < kv_lens[seq_id][:, None])
    if window is not None:
        mask = mask & (pos[None, :] > abs_pos[:, None] - window)
    mask = mask & valid_row[:, None]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(valid_row[:, None, None, None], w, 0.0)
    out = jnp.einsum("thgs,tshd->thgd", w, v)
    return out.reshape(T, n_q, d).astype(q.dtype)


def pallas_ragged_attention(
    q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *,
    sm_scale: float, window: int | None = None,
) -> jax.Array:
    """The library Pallas kernel under this repo's explicit grid:
    ``cu_q_lens=None`` (the decode shape) takes
    :func:`decode_shape_grid`; ragged calls of at most 64 rows the small
    grid, prefill waves a capped query block (the module-level
    comments). Real-valued pages only. ``window`` is the kernel's
    ``sliding_window``: a MASK, it walks every page up to ``kv_lens``."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention as _kernel,
    )

    if cu_q_lens is None:
        cu_q_lens = jnp.arange(q.shape[0] + 1, dtype=jnp.int32)
        qb, pages = decode_shape_grid(kv_pages.shape[1], page_indices.shape[1])
    else:
        rows, heads = q.shape[:2]
        qb, pages = _SMALL_QUERIES_PER_BLOCK, _SMALL_KV_PAGES_PER_BLOCK
        if rows > 64 and heads < _WIDE_HEADS_MIN:
            group = max(1, heads // max(1, kv_pages.shape[2] // 2))
            qb = min(_PREFILL_QUERIES_PER_BLOCK, rows,
                     max(_SMALL_QUERIES_PER_BLOCK, _PREFILL_PASS_ROWS_MAX // group))
        elif rows > 64:
            qb = min(_WIDE_HEADS_QUERIES_PER_BLOCK, rows)
            pages = max(1, _WIDE_HEADS_KV_TOKENS_PER_BLOCK // kv_pages.shape[1])
        pages = min(pages, page_indices.shape[1])
    return _kernel(
        q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, sliding_window=window,
        num_kv_pages_per_block=pages, num_queries_per_block=qb,
    )


def split_query_chunks(
    rows: int, kv_lens, page_indices, cu_q_lens, num_seqs, *,
    chunk: int, page_size: int, window: int | None = None,
):
    """A ragged call's sequences cut into pieces of at most ``chunk``
    queries, each a sequence of its own to the kernel: ``(kv_lens,
    page_indices, cu_q_lens, num_seqs)`` of ``rows // chunk + S`` pieces,
    the live ones first and in the order of their queries, so ``q`` and the
    output are untouched.

    Why: the library kernel walks EVERY KV block of a sequence up to
    ``kv_lens`` for every block of its queries; causality and the window are
    masks, not bounds. A piece's ``kv_lens`` ends at its own last query, so
    the keys after it are not walked; and with ``window`` its table starts
    at the page of the oldest key its FIRST query sees, ``(window - 1 +
    chunk) / page_size + 2`` columns at most, so the keys before the
    window are not walked either. Positions are relative in kernel and
    reference alike (a query's is ``kv_lens - q_len + i``), so the masks
    are the same masks."""
    S, width = page_indices.shape
    pieces = -(-rows // chunk) + S
    live = jnp.arange(S, dtype=jnp.int32) < num_seqs[0]
    q_lens = jnp.where(live, cu_q_lens[1:] - cu_q_lens[:-1], 0)       # [S]
    parts = -(-q_lens // chunk)                                       # pieces of sequence s
    ends = jnp.cumsum(parts)                                          # ... up to and with s
    i = jnp.arange(pieces, dtype=jnp.int32)
    s = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), S - 1).astype(jnp.int32)
    lo = jnp.minimum((i - (ends[s] - parts[s])) * chunk, q_lens[s])
    hi = jnp.where(i < ends[-1], jnp.minimum(lo + chunk, q_lens[s]), lo)
    before = kv_lens[s] - q_lens[s]            # keys before the sequence's first query
    first_col = jnp.zeros_like(s)
    if window is not None:
        first_col = jnp.maximum(before + lo - (window - 1), 0) // page_size
        width = min(width, -(-(page_size - 1 + window - 1 + chunk) // page_size))
    cols = jnp.minimum(first_col[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :],
                       page_indices.shape[1] - 1)
    return (
        (before + hi - first_col * page_size).astype(jnp.int32),
        page_indices[s[:, None], cols],
        cu_q_lens[0] + jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(hi - lo)]),
        ends[-1:].astype(jnp.int32),
    )


def first_party_decode(backend: str, q, kv_pages, cu_q_lens, *, kv_scales=None,
                       window: int | None = None, num_kv_heads: int | None = None):
    """``(what the log calls it, the kernel's entry, its ``block_pages``)`` of
    the first-party kernel a call takes, or ``None``: a DECODE-shaped call
    (``cu_q_lens is None``) over real-valued pages with no window, from its
    geometry alone. GROUP 1 (as many query heads as the model has KV heads)
    has ops/mha_attention.py where its ``fits`` holds; a GROUP of 2 or more
    over every KV head the page keeps has ops/grouped_attention.py where its
    ``fits`` holds."""
    from dynamo_tpu.ops import grouped_attention, mha_attention

    if cu_q_lens is not None or kv_scales is not None or window is not None:
        return None
    page_heads = kv_pages.shape[2] // 2
    if q.shape[1] == (num_kv_heads or page_heads):
        if mha_attention.fits(backend, q, kv_pages):
            return "group 1", mha_attention.mha_decode_pallas, mha_attention.block_pages
    elif num_kv_heads in (None, page_heads) and grouped_attention.fits(backend, q, kv_pages):
        return (f"a group of {q.shape[1] // page_heads}", grouped_attention.grouped_decode_pallas,
                grouped_attention.block_pages)
    return None


def decode_impl(backend: str, q, kv_pages, cu_q_lens, *, kv_scales=None,
                window: int | None = None, num_kv_heads: int | None = None) -> str:
    """The label of a call's counter, from what the call can observe and
    nothing else (no flag, no model's name): ``"pallas"`` where a
    first-party decode kernel takes it (:func:`first_party_decode`); else
    ``"library"`` where the library kernel's shapes hold on a TPU, else
    ``"reference"``."""
    d, page_size = q.shape[-1], kv_pages.shape[1]
    if first_party_decode(backend, q, kv_pages, cu_q_lens, kv_scales=kv_scales, window=window,
                          num_kv_heads=num_kv_heads):
        return "pallas"
    return "library" if backend == "tpu" and d % 128 == 0 and page_size % 8 == 0 else "reference"


def ragged_paged_attention(
    q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *,
    sm_scale: float, kv_scales=None, window: int | None = None,
    query_chunk: int | None = None, num_kv_heads: int | None = None,
) -> jax.Array:
    """Backend dispatch: a Pallas kernel on TPU, jnp reference elsewhere.
    ``cu_q_lens=None`` states the decode shape (module docstring): the
    library kernel then runs its decode grid, and the reference reads it as
    ``arange(S + 1)``; a decode-shaped call of GROUP 1 takes the first-party
    kernel where its geometry fits (:func:`decode_impl`).

    ``num_kv_heads`` (a page that keeps MORE KV heads than the model has,
    ``ModelConfig.cache_kv_heads``: the spare ones' rows are zeros): ``q``
    holds the published heads. The first-party kernel computes on them
    alone; for the library kernel and the reference, which read the group
    off the page, the spare heads' queries go in as zeros and their output
    is dropped.

    **A window** (``window=w``): a query at position ``p`` sees the keys at
    ``p - w + 1 .. p``. The library kernel's ``sliding_window`` only masks
    (``row - w >= col``): it still reads every page from the table's first
    to ``kv_lens``. So the caller hands in the table of the pages a query
    of this call may see, the first of them the one that holds its
    sequence's OLDEST visible key, and ``kv_lens`` less the tokens before
    that page (model.dense_layer). Positions are relative in kernel and
    reference alike (a query's is ``kv_lens - q_len + i``), so the mask is
    the same mask and the call reads the window's pages and no others.
    Counted under ``window-decode`` / ``window-ragged``.

    ``query_chunk`` (a ragged call of more rows than that): the sequences
    go to kernel or reference in pieces of that many queries
    (:func:`split_query_chunks`), the same numbers for fewer keys walked.

    The kernel wants MXU/VPU-aligned shapes (head_dim % 128, page_size %
    8); models outside that (e.g. the byte-sized test presets) run the
    XLA reference path even on TPU — the kernel's trace-time asserts are
    not a serving error, but the choice is announced (a warning on TPU).

    ``kv_scales`` marks an int8 cache (``kv_pages`` int8 + per-slot-per-
    head f32 scales). The reference path fuses dequant into its gather
    (halved gather bytes). The TPU library kernel takes real-valued
    pages, so the int8 serving path dequantizes the REFERENCED pages
    before the call when that is smaller than the whole cache, else the
    whole cache — a capacity win, no traffic win (ROADMAP S10)."""
    d = q.shape[-1]
    page_size = kv_pages.shape[1]
    backend = jax.default_backend()
    geometry = (
        f"head_dim={d}, page_size={page_size}, q_heads={q.shape[1]}, "
        f"combined_kv_heads={kv_pages.shape[2]}, kv={kv_pages.dtype}"
    )
    shape = "decode" if cu_q_lens is None else "ragged"
    choice = dict(kv_scales=kv_scales, window=window, num_kv_heads=num_kv_heads)
    impl = decode_impl(backend, q, kv_pages, cu_q_lens, **choice)
    use_kernel = impl == "library"
    if cu_q_lens is not None and query_chunk and q.shape[0] > query_chunk:
        kv_lens, page_indices, cu_q_lens, num_seqs = split_query_chunks(
            q.shape[0], kv_lens, page_indices, cu_q_lens, num_seqs,
            chunk=query_chunk, page_size=page_size, window=window)
    if window is not None:
        shape, geometry = f"window-{shape}", f"{geometry}, window={window}"
    _count_traced(shape, impl)
    if impl == "pallas":
        said, kernel, block_pages = first_party_decode(backend, q, kv_pages, cu_q_lens, **choice)
        _announce(
            logging.INFO,
            f"ragged attention: first-party Pallas TPU kernel, {said} ({geometry}); decode "
            f"shape, {block_pages(kv_pages, page_indices.shape[1])} pages a KV block",
        )
        return kernel(q, kv_pages, kv_lens, page_indices, num_seqs, sm_scale=sm_scale)
    heads = q.shape[1]
    spare = kv_pages.shape[2] // 2 - (num_kv_heads or kv_pages.shape[2] // 2)
    if spare:   # a zero query head (a group of them) a spare KV head of the page
        q = jnp.pad(q, ((0, 0), (0, spare * (heads // num_kv_heads)), (0, 0)))
    if use_kernel:
        _announce(
            logging.INFO,
            f"ragged attention: Pallas TPU kernel ({geometry}); "
            + ("decode shape, grid "
               f"{decode_shape_grid(page_size, page_indices.shape[1])}"
               if cu_q_lens is None else "ragged shape"),
        )
    elif backend == "tpu":
        _announce(
            logging.WARNING,
            "ragged attention: jnp reference ON TPU — the Pallas kernel "
            f"needs head_dim % 128 == 0 and page_size % 8 == 0 ({geometry})",
        )
    else:
        _announce(
            logging.INFO,
            f"ragged attention: jnp reference (backend is {backend}; "
            f"{geometry})",
        )
    if use_kernel:
        if kv_scales is not None:
            from dynamo_tpu.engine.kv_quant import dequantize_kv

            n_pages = kv_pages.shape[0]
            S, pages_per_seq = page_indices.shape
            if S * pages_per_seq < n_pages:
                # Dequant-on-gather: materialize only the pages this
                # batch references, renumbering the tables to match.
                ids = page_indices.reshape(-1)
                kv_pages = dequantize_kv(kv_pages[ids], kv_scales[ids]).astype(
                    q.dtype
                )
                page_indices = jnp.arange(
                    S * pages_per_seq, dtype=jnp.int32
                ).reshape(S, pages_per_seq)
            else:
                # Whole-LAYER dequant (this function sees one layer's
                # pages): transient = n_pages bf16 rows for one layer,
                # ~1/num_layers of a full bf16 cache — bounded, but the
                # read traffic is a capacity-only fallback (see docstring).
                kv_pages = dequantize_kv(kv_pages, kv_scales).astype(q.dtype)
            kv_scales = None
        out = pallas_ragged_attention(
            q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, window=window,
        )
    else:
        out = ragged_paged_attention_ref(
            q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale, kv_scales=kv_scales, window=window,
        )
    return out[:, :heads] if spare else out


def block_attention(
    q, kv_pages, block_ends, page_indices, num_blocks, *,
    block_length: int, sm_scale: float, shape: str,
) -> jax.Array:
    """Attention of a block-diffusion model (``ModelConfig.block_length``):
    ``q [T, n_q, d]`` is ``T / B`` diffusion blocks of ``B`` consecutive
    rows, and every row of block ``b`` sees the keys ``0 .. block_ends[b] -
    1`` of the sequence whose pages ``page_indices[b]`` names: each earlier
    block and, BOTH ways, its own (whose K/V the caller has written).

    One decode-shaped call: a block's ``B`` rows are folded into the GQA
    group, ``[T / B, n_kv x (B x group), d]`` with ONE query a block at
    ``kv_lens = block_ends``, so that the causal mask the library kernel
    builds (a query's position is ``kv_lens - 1``) is the block's own and a
    block's K/V are read once a call. ``page_indices`` ``[T / B, pages]``
    has a row a BLOCK (a wave repeats a sequence's row for each of its
    blocks), ``num_blocks`` ``i32[1]`` the live ones. Counted under
    ``shape``: ``"block-decode"`` (a denoising or clean pass of a step) or
    ``"block-ragged"`` (a prefill wave's whole blocks)."""
    T, n_q, d = q.shape
    B, n_kv = block_length, kv_pages.shape[2] // 2
    group = n_q // n_kv
    folded = q.reshape(T // B, B, n_kv, group, d).transpose(0, 2, 1, 3, 4).reshape(
        T // B, n_kv * B * group, d)
    page_size = kv_pages.shape[1]
    use_kernel = jax.default_backend() == "tpu" and d % 128 == 0 and page_size % 8 == 0
    _count_traced(shape, "library" if use_kernel else "reference")
    _announce(
        logging.INFO if use_kernel or jax.default_backend() != "tpu" else logging.WARNING,
        f"block attention ({shape}): {'Pallas TPU kernel' if use_kernel else 'jnp reference'}"
        f", {B} rows a block folded into {folded.shape[1]} heads (head_dim={d}, "
        f"page_size={page_size})")
    call = pallas_ragged_attention if use_kernel else ragged_paged_attention_ref
    out = call(folded, kv_pages, block_ends, page_indices, None, num_blocks,
               sm_scale=sm_scale)
    return out.reshape(T // B, n_kv, B, group, d).transpose(0, 2, 1, 3, 4).reshape(T, n_q, d)


def paired_heads_attention(
    q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *, sm_scale: float,
) -> jax.Array:
    """Attention for heads HALF a lane row wide over pages that keep two
    KV heads a row, at the bytes the heads have: ``kv_pages [n_pages,
    page_size, n_kv, 2 d]`` with ``[k_2j | k_2j+1]`` at combined head ``2
    j`` and ``[v_2j | v_2j+1]`` at ``2 j + 1`` (K even, V odd, as ever: ``n_kv
    / 2`` KV heads of width ``2 d`` to the call below). A query head of
    KV head ``2 j`` goes in as ``[q | 0]`` and one of ``2 j + 1`` as ``[0 |
    q]``: its scores against the paired key are its own head's, the
    zeros meeting the other's; of the output ``[o_2j | o_2j+1]`` its own
    half is kept. ``sm_scale`` is the published head's (``d^-0.5``).
    Twice the attention FLOPs, the same cache bytes, and the library
    kernel on a TPU where a 64-wide head alone would get the ``jnp``
    reference (:func:`ragged_paged_attention` counts the call by shape
    and implementation). ``q [T, n_q, d]`` -> ``[T, n_q, d]``."""
    T, n_q, d = q.shape
    n_kv = kv_pages.shape[2]               # 2 x (n_kv / 2) combined rows
    second = ((jnp.arange(n_q) // (n_q // n_kv)) % 2 == 1)[None, :, None]
    zeros = jnp.zeros_like(q)
    q2 = jnp.where(second, jnp.concatenate([zeros, q], -1),
                   jnp.concatenate([q, zeros], -1))
    out = ragged_paged_attention(
        q2, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, sm_scale=sm_scale,
    )
    return jnp.where(second, out[..., d:], out[..., :d])


def sharded_ragged_attention(
    mesh: Mesh,
    q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *,
    sm_scale: float, kv_scales=None,
) -> jax.Array:
    """Ragged attention under tensor parallelism: heads split over the
    mesh's ``tp`` axis, zero collectives (each shard owns its q heads and
    the matching combined-KV block; dp replicates). int8 caches shard
    their scale pages on the same combined-head axis as the KV pages."""
    if kv_scales is not None:
        fn = functools.partial(ragged_paged_attention, sm_scale=sm_scale)

        def quant_fn(q, kv_pages, kv_scales, kv_lens, page_indices, cu, ns):
            return fn(
                q, kv_pages, kv_lens, page_indices, cu, ns,
                kv_scales=kv_scales,
            )

        return shard_map(
            quant_fn,
            mesh=mesh,
            in_specs=(
                P(None, "tp", None),          # q: heads sharded
                P(None, None, "tp", None),    # kv_pages: combined heads
                P(None, None, "tp"),          # kv_scales: combined heads
                P(), P(), P(), P(),
            ),
            out_specs=P(None, "tp", None),
            check_vma=False,
        )(q, kv_pages, kv_scales, kv_lens, page_indices, cu_q_lens, num_seqs)
    fn = functools.partial(
        ragged_paged_attention, sm_scale=sm_scale
    )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(None, "tp", None),          # q: heads sharded
            P(None, None, "tp", None),    # kv_pages: combined heads sharded
            P(), P(), P(), P(),
        ),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )(q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs)
