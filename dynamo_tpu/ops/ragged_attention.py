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

On TPU dispatches to the Pallas kernel
(jax.experimental.pallas.ops.tpu.ragged_paged_attention); elsewhere (CPU
test meshes) runs a vectorized jnp reference with identical semantics.
A decode-shaped call runs the same kernel under a grid of its own: one
sequence a query block, so that a sequence's pass computes on that
sequence's rows alone (:func:`decode_shape_grid`). Which implementation
a program got is logged once at trace time (:func:`_announce`) — the
reference on a TPU is a warning, never silent — and counted by shape
and implementation (:func:`traced_calls`,
``dynamo_engine_attention_calls_traced_total`` on /metrics).
Heads HALF a lane row wide come in through :func:`paired_heads_attention`
(two KV heads a 128-wide row, the same cache bytes, the same kernel).
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
# caller stated ("decode" / "ragged") and the implementation chosen
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
    mask = mask & valid_row[:, None]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(valid_row[:, None, None, None], w, 0.0)
    out = jnp.einsum("thgs,tshd->thgd", w, v)
    return out.reshape(T, n_q, d).astype(q.dtype)


def pallas_ragged_attention(
    q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *,
    sm_scale: float,
) -> jax.Array:
    """The library Pallas kernel under this repo's explicit grid:
    ``cu_q_lens=None`` (the decode shape) takes
    :func:`decode_shape_grid`; ragged calls of at most 64 rows the small
    grid, prefill waves a capped query block (the module-level
    comments). Real-valued pages only."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention as _kernel,
    )

    if cu_q_lens is None:
        cu_q_lens = jnp.arange(q.shape[0] + 1, dtype=jnp.int32)
        qb, pages = decode_shape_grid(kv_pages.shape[1], page_indices.shape[1])
    else:
        qb = (
            _SMALL_QUERIES_PER_BLOCK
            if q.shape[0] <= 64
            else min(_PREFILL_QUERIES_PER_BLOCK, q.shape[0])
        )
        pages = min(_SMALL_KV_PAGES_PER_BLOCK, page_indices.shape[1])
    return _kernel(
        q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale,
        num_kv_pages_per_block=pages, num_queries_per_block=qb,
    )


def ragged_paged_attention(
    q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs, *,
    sm_scale: float, kv_scales=None,
) -> jax.Array:
    """Backend dispatch: Pallas kernel on TPU, jnp reference elsewhere.
    ``cu_q_lens=None`` states the decode shape (module docstring): the
    kernel then runs its decode grid, and the reference reads it as
    ``arange(S + 1)``.

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
    use_kernel = backend == "tpu" and d % 128 == 0 and page_size % 8 == 0
    shape = "decode" if cu_q_lens is None else "ragged"
    _count_traced(shape, "library" if use_kernel else "reference")
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
        return pallas_ragged_attention(
            q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
            sm_scale=sm_scale,
        )
    return ragged_paged_attention_ref(
        q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, kv_scales=kv_scales,
    )


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
