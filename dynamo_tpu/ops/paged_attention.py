"""Paged attention for the decode path: one new query token per sequence
attends over that sequence's KV blocks scattered through the paged cache.

Two implementations with identical semantics:

- :func:`paged_attention_reference` — pure jnp gather + masked softmax.
  Runs anywhere (CPU test mesh included) and is the ground truth.
- :func:`paged_attention_pallas` — Pallas TPU kernel. Grid over the batch;
  per sequence it walks the block table, DMAs each KV page HBM→VMEM, and
  folds it into an online-softmax accumulator (flash-attention style), so
  the full [S] attention row never materializes and HBM traffic is exactly
  the live pages.

The reference framework outsources this op to vLLM's CUDA kernels; on TPU
we own it (SURVEY.md §7 "hard parts"). Cache layout is head-major flat
``[n_kv, total_slots, d]`` with ``slot = block * block_size + offset``:
per-head page DMAs then slice only the untiled leading axes (TPU tiling
constrains the last two dims), and tensor parallelism shards axis 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu import knobs

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# What Mosaic said when the int8-page variant first met a chip (v5e, jax
# 0.9.0 / libtpu 0.0.34, head_dim 128, block 32; chip_smoke.py's kernel
# check). The bf16-page kernel compiles and matches its reference.
INT8_PAGES_ON_TPU = (
    "the first-party int8-page kernel does not compile for TPU: Mosaic "
    "rejects the per-page scale DMA — 'Slice shape along dimension 2 must "
    "be aligned to tiling (128), but is 32' (the [n_kv, n_blocks, "
    "block_size] f32 scale rows are narrower than a 128-lane tile). It "
    "runs in interpret mode only; serving int8 KV goes through "
    "ops/ragged_attention.py. ROADMAP D6 decides between repair and removal."
)


def paged_attention_reference(
    q: jax.Array,            # [B, n_q, d]
    k_cache: jax.Array,      # [n_kv, total_slots, d]
    v_cache: jax.Array,      # [n_kv, total_slots, d]
    block_tables: jax.Array, # [B, max_blocks] int32 (padding -> garbage block)
    seq_lens: jax.Array,     # [B] int32, cached tokens (excl. self when given)
    *,
    block_size: int,
    scale: float | None = None,
    k_self: jax.Array | None = None,  # [B, n_kv, d]: the current token's K/V,
    v_self: jax.Array | None = None,  # attended without being in the cache yet
    k_scale: jax.Array | None = None,  # [n_kv, total_slots] f32: int8 caches'
    v_scale: jax.Array | None = None,  # per-slot-per-head dequant scales
) -> jax.Array:              # [B, n_q, d]
    B, n_q, d = q.shape
    n_kv = k_cache.shape[0]
    group = n_q // n_kv
    max_blocks = block_tables.shape[1]
    S = max_blocks * block_size
    scale = scale if scale is not None else d ** -0.5

    offsets = jnp.arange(block_size, dtype=jnp.int32)
    slots = (block_tables[:, :, None] * block_size + offsets[None, None, :]).reshape(B, S)
    k = k_cache[:, slots]  # [n_kv, B, S, d]
    v = v_cache[:, slots]
    if k_scale is not None:
        # int8 cache: dequant fused into the gather (the gather itself
        # moved half the bytes of the bf16 layout).
        from dynamo_tpu.engine.kv_quant import dequantize_kv

        k = dequantize_kv(k, k_scale[:, slots])
        v = dequantize_kv(v, v_scale[:, slots])

    qg = q.reshape(B, n_kv, group, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bhgd,hbsd->bhgs", qg, kf) * scale
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < seq_lens[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, _NEG_INF)
    vf = v.astype(jnp.float32)
    if k_self is not None:
        # The self position: one extra key/value, always valid. Keeps the
        # cache write out of the layer loop (deferred-scatter decode).
        s_self = jnp.einsum("bhgd,bhd->bhg", qg, k_self.astype(jnp.float32)) * scale
        logits = jnp.concatenate([logits, s_self[..., None]], axis=-1)
        vf = jnp.concatenate(
            [vf, v_self.astype(jnp.float32).transpose(1, 0, 2)[:, :, None, :]], axis=2
        )
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,hbsd->bhgd", weights, vf)
    return out.reshape(B, n_q, d).astype(q.dtype)


def _paged_attn_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_blocks] SMEM
    seq_lens_ref,      # [B] SMEM
    # inputs
    q_ref,             # [1, 1, group, d] VMEM (this sequence, this kv head)
    k_hbm,             # [n_kv, total_slots, d] ANY/HBM
    v_hbm,
    k_scale_hbm,       # [n_kv, n_blocks, block_size] ANY/HBM (int8 only;
    v_scale_hbm,       # dummy otherwise) — page-shaped so the DMA indexes
    #                    a whole page on an untiled axis and never slices
    #                    the minor (lane) dim at non-128 offsets
    k_self_ref,        # [1, 1, 1, d] VMEM — current token's K, this head
    v_self_ref,
    # output
    o_ref,             # [1, 1, group, d] VMEM
    # scratch
    k_page,            # [2, block_size, d] VMEM double buffer
    v_page,
    sem,               # DMA sems [2, 2]
    *quant_scratch,    # with_quant: k_sc, v_sc ([2, block_size] f32), sc_sem
    block_size: int,
    scale: float,
    with_self: bool,
    with_quant: bool,
):
    # One grid instance = one (sequence, kv head): all matmuls are plain 2D
    # (Mosaic's tpu.matmul does not support mismatched batch dims).
    b = pl.program_id(0)
    h = pl.program_id(1)
    seq_len = seq_lens_ref[b]
    num_blocks = jax.lax.div(seq_len + block_size - 1, block_size)
    group, d = q_ref.shape[2], q_ref.shape[3]
    if with_quant:
        k_sc, v_sc, sc_sem = quant_scratch

    q = q_ref[0, 0].astype(jnp.float32) * scale  # [group, d]

    def page_dma(slot, blk_idx):
        page = block_tables_ref[b, blk_idx]
        start = page * block_size
        copies = [
            pltpu.make_async_copy(
                k_hbm.at[h, pl.ds(start, block_size)], k_page.at[slot], sem.at[slot, 0]
            ),
            pltpu.make_async_copy(
                v_hbm.at[h, pl.ds(start, block_size)], v_page.at[slot], sem.at[slot, 1]
            ),
        ]
        if with_quant:
            # int8 pages halve the bulk DMA above; the scale tiles ride
            # alongside (block_size f32 each — noise next to the page).
            # Whole-page rows indexed on the untiled block axis, so no
            # dynamic minor-dim slicing (Mosaic lane alignment).
            copies.append(
                pltpu.make_async_copy(
                    k_scale_hbm.at[h, page], k_sc.at[slot], sc_sem.at[slot, 0]
                )
            )
            copies.append(
                pltpu.make_async_copy(
                    v_scale_hbm.at[h, page], v_sc.at[slot], sc_sem.at[slot, 1]
                )
            )
        return copies

    # Warm up the pipeline with the first page.
    @pl.when(num_blocks > 0)
    def _():
        for c in page_dma(0, 0):
            c.start()

    def body(i, carry):
        m, l, acc = carry  # [group, 1], [group, 1], [group, d]
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < num_blocks)
        def _():
            for c in page_dma(1 - slot, i + 1):
                c.start()

        for c in page_dma(slot, i):
            c.wait()

        k = k_page[slot].astype(jnp.float32)   # [bs, d]
        v = v_page[slot].astype(jnp.float32)
        if with_quant:
            # Dequant in-VMEM, after the halved page copy landed.
            k = k * k_sc[slot][:, None]
            v = v * v_sc[slot][:, None]
        # s[g, t] = q[g, :] . k[t, :]
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [group, bs]
        pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
        s = jnp.where(pos < seq_len, s, _NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                            # [group, bs]
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [group, d]
        acc_new = acc * alpha + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((group, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((group, 1), jnp.float32)
    acc0 = jnp.zeros((group, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_blocks, body, (m0, l0, acc0))
    if with_self:
        # Fold in the current token (not yet in the cache): one extra
        # always-valid position, so deferred-scatter decode stays exact.
        ks = k_self_ref[0, 0, 0].astype(jnp.float32)   # [d]
        vs = v_self_ref[0, 0, 0].astype(jnp.float32)
        s_self = jnp.sum(q * ks[None, :], axis=-1, keepdims=True)  # [group, 1]
        m_new = jnp.maximum(m, s_self)
        p = jnp.exp(s_self - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p
        acc = acc * alpha + p * vs[None, :]
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    *,
    block_size: int,
    scale: float | None = None,
    k_self: jax.Array | None = None,
    v_self: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, n_q, d = q.shape
    max_blocks = block_tables.shape[1]
    n_kv = k_cache.shape[0]
    scale = scale if scale is not None else d ** -0.5

    group = n_q // n_kv
    qg = q.reshape(B, n_kv, group, d)
    with_self = k_self is not None
    with_quant = k_scale is not None
    if with_quant and not interpret:
        raise NotImplementedError(INT8_PAGES_ON_TPU)
    self_dtype = jnp.float32 if with_quant else k_cache.dtype
    if not with_self:
        k_self = jnp.zeros((B, n_kv, d), self_dtype)
        v_self = jnp.zeros((B, n_kv, d), self_dtype)
    if with_quant:
        # Page-shaped scale layout for the kernel: the DMA then indexes
        # [head, page] and copies a whole block_size row — no dynamic
        # slicing of the minor (lane) dimension, which f32 tiling would
        # reject at non-128-aligned offsets.
        k_scale = k_scale.reshape(n_kv, -1, block_size)
        v_scale = v_scale.reshape(n_kv, -1, block_size)
    else:
        # Tiny dummies (never DMA'd — with_quant is static).
        k_scale = jnp.zeros((n_kv, 1, 1), jnp.float32)
        v_scale = jnp.zeros((n_kv, 1, 1), jnp.float32)
    # 4D so the tiled trailing dims are (1, d) == the array dims — the
    # head index stays on an untiled axis (Mosaic alignment rules).
    k_self4 = k_self.reshape(B, n_kv, 1, d)
    v_self4 = v_self.reshape(B, n_kv, 1, d)

    kernel = functools.partial(
        _paged_attn_kernel,
        block_size=block_size,
        scale=scale,
        with_self=with_self,
        with_quant=with_quant,
    )
    self_spec = pl.BlockSpec(
        (1, 1, 1, d), lambda b, h, *_: (b, h, 0, 0), memory_space=pltpu.VMEM
    )
    scratch = [
        pltpu.VMEM((2, block_size, d), k_cache.dtype),
        pltpu.VMEM((2, block_size, d), v_cache.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    if with_quant:
        scratch += [
            pltpu.VMEM((2, block_size), jnp.float32),
            pltpu.VMEM((2, block_size), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, d), lambda b, h, *_: (b, h, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            self_spec,
            self_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d), lambda b, h, *_: (b, h, 0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, group, d), q.dtype),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        qg, k_cache, v_cache, k_scale, v_scale, k_self4, v_self4,
    )
    return out.reshape(B, n_q, d)


def pallas_supported(head_dim: int, block_size: int, dtype) -> bool:
    """TPU tiling constraints on the page DMA: lane dim (head_dim) must be
    a multiple of 128 and the sublane slice (block_size) a multiple of the
    dtype's min tile (int8 pages tile at 32 sublanes)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = {1: 32, 2: 16}.get(itemsize, 8)
    return head_dim % 128 == 0 and block_size % sublane == 0


def paged_attention(
    q, k_cache, v_cache, block_tables, seq_lens, *, block_size, scale=None,
    k_self=None, v_self=None, k_scale=None, v_scale=None,
) -> jax.Array:
    """Dispatch: XLA gather path by default. ``DYNAMO_TPU_PAGED_ATTN=pallas``
    opts into the first-party kernel; asked for and unavailable (no TPU,
    or a geometry :func:`pallas_supported` rejects) is an error, never a
    quiet run of the reference under the kernel's name.

    ``k_scale``/``v_scale`` mark int8 caches: the XLA path fuses the
    dequant into its gather; the kernel's int8-page variant raises on a
    TPU (:data:`INT8_PAGES_ON_TPU`)."""
    if knobs.get_str("DYNAMO_TPU_PAGED_ATTN") == "pallas":
        if not pallas_supported(q.shape[-1], block_size, k_cache.dtype):
            raise ValueError(
                "DYNAMO_TPU_PAGED_ATTN=pallas: unsupported geometry "
                f"head_dim={q.shape[-1]}, block_size={block_size}, "
                f"cache dtype={k_cache.dtype} (needs head_dim % 128 == 0 "
                "and block_size a multiple of the dtype's sublane tile: "
                "16 for bf16, 32 for int8)"
            )
        backend = jax.default_backend()
        if backend != "tpu":
            raise RuntimeError(
                "DYNAMO_TPU_PAGED_ATTN=pallas needs a TPU backend, got "
                f"{backend!r} (tests drive paged_attention_pallas with "
                "interpret=True directly)"
            )
        return paged_attention_pallas(
            q, k_cache, v_cache, block_tables, seq_lens,
            block_size=block_size, scale=scale, k_self=k_self, v_self=v_self,
            k_scale=k_scale, v_scale=v_scale,
        )
    return paged_attention_reference(
        q, k_cache, v_cache, block_tables, seq_lens,
        block_size=block_size, scale=scale, k_self=k_self, v_self=v_self,
        k_scale=k_scale, v_scale=v_scale,
    )
