"""Functional llama-family transformer over a paged KV cache, built
around ONE ragged forward for prefill, decode, and mixed batches.

Pure functions over a params pytree — no flax Module state — so `jit`,
`shard_map`, and donation compose cleanly. Design choices (all measured on
v5e, round 2):

- **Unified ragged entry point** :func:`forward_tokens`: every scheduled
  token this step rides one program — prefill chunks of different lengths
  and single decode tokens together, no per-sequence padding. Attention is
  :mod:`dynamo_tpu.ops.ragged_attention` (Pallas kernel on TPU). The
  reference delegates this to vLLM (`components/backends/vllm`); here it
  is first-party (SURVEY.md §7 stage 6).
- **Combined paged cache** ``[L, n_pages, page_size, 2*n_kv, d]`` with K/V
  interleaved on the combined-head axis (K even, V odd): one page is one
  DMA covering K+V for all heads; the tensor-parallel shard axis is the
  combined-head axis.
- **Unrolled layers, in-place page writes**: carrying the cache through a
  `lax.scan` over layers streams the whole cache through HBM every step
  (measured +12 ms/step at 1B scale); a Python-level layer loop with
  donated buffers scatters just the new tokens' pages.
- **Fused projections, shard-blocked**: wq/wk/wv fuse into one ``wqkv``
  matmul and gate/up into ``wgu`` (measured −0.6 ms/step). Under tensor
  parallelism the fused columns are laid out shard-blocked —
  ``[q_s | k_s | v_s]`` per shard ``s`` — so a plain ``P(None, None, "tp")``
  sharding gives every shard its own (q, k, v) block and
  :func:`split_qkv` reassembles the natural head order.
- **Looped stacks roll the passes, not the layers**
  (``cfg.ut_steps > 1``, Ouro): the same unrolled layer body runs
  ``ut_steps`` times under ONE ``lax.fori_loop`` (:func:`_run_stack`), so
  a program holds ``num_layers`` bodies and not ``num_layers x
  ut_steps``. Each layer's page array holds ``ut_steps`` PLANES of
  ``num_kv_blocks + 1`` pages; pass ``u`` reads and writes plane ``u`` by
  adding ``u x pages-per-plane`` to the page ids, so the allocator, the
  block tables and the attention kernel see nothing new.
- **A page shape per layer kind** (``cfg.layer_types``, LFM2): a layer
  whose operator is a gated short convolution keeps no K/V; its page
  array, indexed by the same block ids, holds the newest rows of its
  rolling state written in each block (:func:`conv_layer`), so that the
  allocator, the prefix index and preemption see nothing new either.
  Heads 64 wide are cached two to a 128-wide row
  (``cfg.kv_head_pairs``) and attend through the same kernel.
- **A pool per layer kind** (``sliding_attention`` layers, Laguna): a
  layer whose queries see a window keeps its pages in a SECOND pool with
  block ids, and a block table, of its own (:func:`split_tables`); a
  sequence holds there the blocks a later query may still see and gives
  the others back. Query heads (``cfg.heads_of``), rope
  (:func:`kind_rope_tables`) and the table are the layer kind's; the
  layer body is the one :func:`dense_layer`.
- **A block of places a lane** (``cfg.block_length``, SDAR): a query sees
  every earlier block and, both ways, its own. The rows of a batch are
  whole blocks (:func:`block_rows`), a block's rows fold into the GQA
  group of ONE decode-shaped attention call (``ops/ragged_attention.py``,
  ``block_attention``), in a step's pass (:func:`block_hidden`, and
  :func:`block_logits` on the rows whose places can still be hidden) and in
  a prefill wave alike; pages, allocator and prefix index see nothing new.
  A generated block's K/V become FINAL when its revealed tokens run as
  clean rows, which is beside the first pass of the lane's next block
  (:func:`block_hidden`'s ``pending`` half; ``programs._megastep_blocks``) or
  in a wave that takes them as prompt; until then they are those of a
  pass with places still masked, they lie past the lane's cursor
  (``num_computed_tokens``), and nothing that reads or publishes K/V looks
  there.
- **A slab a lane** (``linear_attention`` layers): a layer whose mixer is a
  gated delta rule keeps a float32 state a SEQUENCE, too large to keep a
  block; its cache entry is a slab indexed by a lane slot
  (:func:`linear_layer`, ops/linear_attention.py), the slot one more column
  of the block table (:func:`split_slots`). A Mamba-2 ("mamba") layer keeps
  its state the same way (:func:`ssm_layer`, ops/ssm.py).
- **Blocks of one sub-layer** (``cfg.single_sublayer``): a block is a mixer OR
  a feed-forward, ``x + f(norm(x))`` under ONE norm (``attn_norm``):
  :func:`ssm_layer`, :func:`dense_layer` without its MLP, :func:`mlp_block`
  (whose cache entry is empty: it caches nothing).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map

from dynamo_tpu.engine.config import SLAB_KINDS, EngineConfig, ModelConfig
from dynamo_tpu.ops import expert_stream, grouped_matmul
from dynamo_tpu.ops.gqa_attention import (
    gqa_decode_attention,
    gqa_ragged_attention,
    write_gqa_rows,
)
from dynamo_tpu.ops import linear_attention, ssm
from dynamo_tpu.ops.latent_attention import (
    latent_decode_attention,
    latent_ragged_attention,
    write_latent_rows,
)
from dynamo_tpu.ops.ragged_attention import (
    block_attention,
    paired_heads_attention,
    ragged_paged_attention,
    sharded_ragged_attention,
)

Params = dict[str, Any]


# -- int8 weight-only quantization ------------------------------------------

def quantize_weight(w: jax.Array) -> dict[str, jax.Array]:
    """Per-output-channel symmetric int8: w ~= w_int8 * scale[out].
    Weight-only (activations stay bf16) — the capacity play that fits
    llama3-8b on one 16 GB v5e chip (bf16 params alone are 16.06 GB).
    The reference serves FP8 checkpoints through its engines; on TPU the
    analogue is int8 with the convert fused into the matmul by XLA."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"w": q, "scale": scale.astype(jnp.float32)}


def _dot(x: jax.Array, w) -> jax.Array:
    """Matmul against a plain or int8-quantized weight; returns f32."""
    if isinstance(w, dict):
        y = jnp.dot(
            x, w["w"].astype(x.dtype), preferred_element_type=jnp.float32
        )
        return y * w["scale"].reshape(1, -1)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def init_params_quantized(rng: jax.Array, cfg: ModelConfig, tp: int = 1) -> Params:
    """Random-init directly into the int8-quantized layout.

    Materializing the full bf16 pytree first (init_params +
    quantize_params) peaks at the bf16 footprint — for llama3-8b that is
    16.06 GB, which cannot exist on a 16 GB chip at all. Here every
    fused projection group is generated directly (random fused == fused
    random) and quantized per LAYER under ``lax.map``: one layer's
    bf16/f32 transients live at a time by construction, and the program
    holds one layer body per weight instead of ``num_layers`` unrolled
    copies (unrolled, this init took 135 s to compile for 28 layers on
    a v5e — longer than any serving program).
    """
    if cfg.is_moe or cfg.latent or cfg.layer_groups:
        raise NotImplementedError(
            f"int8 weights for {cfg.name!r}: experts, latent projections, conv "
            "operators and layers of more than one kind are served unquantised "
            "(no int8 init for them)"
        )
    h, i, v, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    dt = cfg.jax_dtype

    def build(rng):
        keys = jax.random.split(rng, 8)

        def dense(key, shape, fan_in):
            return (
                jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
            ).astype(dt)

        def qdense_stacked(key, shape2d, fan_in):
            return jax.lax.map(
                lambda l: quantize_weight(
                    dense(jax.random.fold_in(key, l), shape2d, fan_in)
                ),
                jnp.arange(L),
            )

        layers: dict[str, Any] = {
            "attn_norm": jnp.ones((L, h), dt),
            "mlp_norm": jnp.ones((L, h), dt),
            # Fused layouts generated directly at the fused shape.
            "wqkv": qdense_stacked(keys[1], (h, cfg.q_size + 2 * cfg.kv_size), h),
            "wo": qdense_stacked(keys[4], (cfg.q_size, h), cfg.q_size),
            "wgu": qdense_stacked(keys[5], (h, 2 * i), h),
            "w_down": qdense_stacked(keys[7], (i, h), i),
        }
        if cfg.attn_qkv_bias:
            layers["bqkv"] = dense(
                jax.random.fold_in(rng, 11),
                (L, cfg.q_size + 2 * cfg.kv_size), 1,
            )  # biases stay unquantized
        params: Params = {
            "embed": dense(keys[0], (v, h), h),
            "layers": layers,
            "final_norm": jnp.ones((h,), dt),
            "fuse_tp": jnp.asarray(tp, jnp.int32),
        }
        _init_loop_extras(rng, cfg, params)
        if not cfg.tie_embeddings:
            params["lm_head"] = quantize_weight(
                dense(jax.random.fold_in(rng, 99), (h, v), h)
            )
        return params

    return jax.jit(build)(rng)


def quantize_params(params: Params) -> Params:
    """int8-quantize the layer projection weights (wqkv/wo/wgu/w_down and
    lm_head); embeddings and norms stay in the model dtype. MoE expert
    weights stay unquantized (3-D; quantize later if wide-EP needs it)."""
    out = dict(params)
    layers = dict(params["layers"])
    for k in ("wqkv", "wo", "wgu", "w_down"):
        if k in layers and not isinstance(layers[k], dict):
            out_axis_scale = quantize_weight(layers[k])
            layers[k] = out_axis_scale
    out["layers"] = layers
    if "lm_head" in params and not isinstance(params["lm_head"], dict):
        out["lm_head"] = quantize_weight(params["lm_head"])
    return out


# -- fused-projection layout ------------------------------------------------

def fuse_qkv(wq: jax.Array, wk: jax.Array, wv: jax.Array, tp: int = 1) -> jax.Array:
    """Concatenate per-shard blocks ``[q_s | k_s | v_s]`` along the output
    axis. With tp=1 this is plain ``[q | k | v]``. Inputs ``[..., h, out]``."""
    qs = jnp.split(wq, tp, axis=-1)
    ks = jnp.split(wk, tp, axis=-1)
    vs = jnp.split(wv, tp, axis=-1)
    return jnp.concatenate(
        [blk for s in range(tp) for blk in (qs[s], ks[s], vs[s])], axis=-1
    )


def fuse_gu(wg: jax.Array, wu: jax.Array, tp: int = 1) -> jax.Array:
    gs = jnp.split(wg, tp, axis=-1)
    us = jnp.split(wu, tp, axis=-1)
    return jnp.concatenate(
        [blk for s in range(tp) for blk in (gs[s], us[s])], axis=-1
    )


def split_qkv(qkv: jax.Array, cfg: ModelConfig, tp: int = 1):
    """Inverse of :func:`fuse_qkv` on activations ``[T, q+2kv]``: returns
    (q [T, q_size], k [T, kv_size], v [T, kv_size]) in natural head order;
    ``q_size`` is what the columns leave for it (the layer's own heads:
    ``cfg.heads_of``)."""
    T = qkv.shape[0]
    q_size = qkv.shape[1] - 2 * cfg.kv_size
    qs, kvs = q_size // tp, cfg.kv_size // tp
    blocks = qkv.reshape(T, tp, qs + 2 * kvs)
    q = blocks[:, :, :qs].reshape(T, q_size)
    k = blocks[:, :, qs : qs + kvs].reshape(T, cfg.kv_size)
    v = blocks[:, :, qs + kvs :].reshape(T, cfg.kv_size)
    return q, k, v


def split_gu(gu: jax.Array, tp: int = 1):
    T = gu.shape[0]
    half = gu.shape[-1] // (2 * tp)
    blocks = gu.reshape(T, tp, 2 * half)
    return (
        blocks[:, :, :half].reshape(T, -1),
        blocks[:, :, half:].reshape(T, -1),
    )


# -- initialization --------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig, tp: int = 1) -> Params:
    """Random init (serving benchmarks + tests; real weights via loader).

    ``tp`` fixes the shard-blocked layout of the fused projections; it must
    match the serving mesh's tp axis (1 for single-chip).
    """
    h, i, v, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    dt = cfg.jax_dtype
    keys = jax.random.split(rng, 8)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    layers: dict[str, Any] = {
        "attn_norm": jnp.ones((L, h), dt),
        "mlp_norm": jnp.ones((L, h), dt),
    }
    if cfg.single_sublayer:   # ONE norm a block, varied so that its place shows
        layers = {"attn_norm": _varied_ones(jax.random.fold_in(rng, 85), (L, h), dt)}
    extra: dict[str, Any] = {}
    if cfg.latent:
        layers.update(_init_latent_attention(rng, cfg, dense))
    elif cfg.windowed:
        extra.update(_init_attention_by_kind(rng, cfg, dense, tp))
    else:
        # A model with conv layers keeps its attention leaves apart, one
        # entry an ATTENTION layer (``attn``), beside ``conv``.
        La = len(cfg.layers_of("attention"))
        attn = extra.setdefault("attn", {}) if cfg.hybrid or cfg.has_slab else layers
        wq = dense(keys[1], (La, h, cfg.q_size), h)
        wk = dense(keys[2], (La, h, cfg.kv_size), h)
        wv = dense(keys[3], (La, h, cfg.kv_size), h)
        attn["wqkv"] = fuse_qkv(wq, wk, wv, tp)
        attn["wo"] = dense(keys[4], (La, cfg.q_size, h), cfg.q_size)
        if cfg.qk_norm:
            whole = cfg.qk_norm_over == "projection"
            for n, (name, size) in enumerate((("q_layernorm", cfg.q_size),
                                              ("k_layernorm", cfg.kv_size))):
                attn[name] = _varied_ones(
                    jax.random.fold_in(rng, 80 + n),
                    (La, size if whole else cfg.head_dim), dt, _qk_norm_gain(cfg))
    if cfg.hybrid:
        extra["conv"] = _init_conv_operators(rng, cfg, dense)
    if cfg.linear:
        extra["linear"] = _init_linear_operators(rng, cfg, dense)
    if cfg.ssm:
        extra["ssm"] = _init_ssm_operators(rng, cfg, dense)
    if cfg.post_norm:   # the one norm a sub-layer: varied, so that its place shows
        for n, name in enumerate(("attn_norm", "mlp_norm")):
            layers[name] = _varied_ones(jax.random.fold_in(rng, 85 + n), (L, h), dt)
    if cfg.linear:      # a linear mixer's output norm: :data:`_LINEAR_OUT_NORM`
        scale = jnp.asarray([[_LINEAR_OUT_NORM if cfg.layer_kind(l) == "linear" else 1.0]
                             for l in range(L)], jnp.float32)
        layers["attn_norm"] = (scale * layers["attn_norm"].astype(jnp.float32)).astype(dt)
    if cfg.attn_qkv_bias:
        # Qwen2-family qkv bias, in the same shard-blocked fused column
        # order as wqkv (random fused == fused random for init; the
        # loader fuses real biases with _fuse_np).
        layers["bqkv"] = dense(
            jax.random.fold_in(rng, 11), (L, cfg.q_size + 2 * cfg.kv_size), 1
        )
    if cfg.shared_sparse:
        extra.update(_init_shared_sparse_mlp(rng, cfg, dense, tp))
    elif cfg.is_moe:
        E = cfg.num_experts
        layers["w_router"] = dense(jax.random.fold_in(rng, 7), (L, h, E), h)
        layers["w_gate"] = dense(keys[5], (L, E, h, i), h)
        layers["w_up"] = dense(keys[6], (L, E, h, i), h)
        layers["w_down"] = dense(keys[7], (L, E, i, h), i)
    else:
        layers["wgu"] = fuse_gu(
            dense(keys[5], (L, h, i), h), dense(keys[6], (L, h, i), h), tp
        )
        layers["w_down"] = dense(keys[7], (L, i, h), i)
    params: Params = {
        "embed": dense(keys[0], (v, h), h),
        "layers": layers,
        "final_norm": jnp.ones((h,), dt),
        # The fused wqkv/wgu column layout depends on tp; carried in the
        # pytree so serving can assert params match the mesh.
        "fuse_tp": jnp.asarray(tp, jnp.int32),
        **extra,
    }
    _init_loop_extras(rng, cfg, params)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(rng, 99), (h, v), h)
    return params


def _varied_ones(key, shape, dt, mean: float = 1.0):
    """A norm's weight drawn 10% around ``mean`` (1), so that a norm
    applied with the wrong weight, in the wrong place or not at all
    changes the logits (as :func:`_init_loop_extras` draws its own)."""
    return (mean * (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32))).astype(dt)


def _qk_norm_gain(cfg: ModelConfig) -> float:
    """What the head norms of ``q`` and ``k`` are drawn around: 1, but
    ``_QK_NORM_GAIN_BLOCKS`` for a model that generates by blocks
    (``block_length > 0``).

    With both at 1 a random model's scores are ~N(0, 1), a query's
    softmax over a context of 1,400 keys is all but flat (~500 keys
    weigh in), and every place reads the MEAN of its context. A block's
    hidden places all embed the mask token, so attention is the only
    thing that tells them, or two lanes, apart: reading a mean, the
    places of a block come out alike, a token that repeats adds up in
    that mean where distinct ones cancel, and greedy generation falls
    into one or two tokens a lane within a hundred. Three things then
    measure nothing a trained model shows: a pass's rows route alike
    (35-41 of 128 experts touched), the confidences of a block's places
    lie within the comparison's tolerance of each other, so that no
    unmasking order can be told from another, and the lower precision
    (fp8) reads under that tolerance. Drawn around g the scores are
    ~N(0, g^4), a query reads a few keys (as a trained head does), and
    its place and its lane show. The upper end is bfloat16 itself: the
    sharper the softmax, the further the cache's rounding of k moves it.

    The value is the one that leaves the comparison's tolerance (0.15)
    room on both sides, between the sound readings and fp8's (the v5e,
    PERF.md section 6, PR 42; max |diff| over the seeds of each sweep,
    sound / fp8, and the seeds on which leaving the order out was
    caught): 1: 0.011-0.022 / 0.10-0.165, 0 of 9; 1.25: 0.015-0.017 /
    0.11-0.20, 1 of 3; 1.5: 0.031-0.052 / 0.23-0.44, 10 of 11; **1.6:
    0.034-0.093 / 0.42-0.63, 10 of 10**; 1.75: 0.085-0.107 / 0.78-1.00,
    2 of 3 (the sound error itself is then inside the near-tie rule's
    reach)."""
    return _QK_NORM_GAIN_BLOCKS if cfg.block_length > 0 else 1.0


_QK_NORM_GAIN_BLOCKS = 1.6


# The parameter group of each cache kind's operators (``cfg.layer_groups``).
_GROUP_OF_KIND = {"attention": "attn", "window": "attn_window", "conv": "conv",
                  "linear": "linear", "ssm": "ssm"}
# A gate's logits are drawn this many times the fan-in scale: on a normed
# input they are then ~N(0, 1.4^2) and the gates sigmoid of them, spread
# over (0.2, 0.8) and not all near 0.5, so that a gate dropped, or taken
# from the wrong head, moves the logits (tests/test_laguna.py).
_GATE_LOGIT_SCALE = 1.4


def _init_attention_by_kind(rng: jax.Array, cfg: ModelConfig, dense, tp: int) -> dict:
    """The attention leaves of a model whose full and window layers differ
    in their heads: ``attn`` (one entry a full layer) and ``attn_window``
    (one a window layer), each ``wqkv [h, (n + 2 n_kv) d]``, ``wo [n d,
    h]`` and, with ``cfg.attn_gate``, ``wg [h, n]`` for ITS ``n`` query
    heads. With ``cfg.wide_key`` the kind's own KV heads and a value
    narrower than its key: ``wqkv [h, n dk + n_kv dk + n_kv dv]`` (``[q | k
    | v]``), ``wo [n dv, h]`` and, where the kind's softmax has one
    (``cfg.has_sink``), ``sink [n]`` float32 (:func:`_sink_logits`). Layer
    ``l``'s leaves are drawn from key ``l`` whatever its kind."""
    h, d = cfg.hidden_size, cfg.head_dim
    key = lambda l, n: jax.random.fold_in(jax.random.fold_in(rng, 70 + n), l)  # noqa: E731
    out: dict[str, dict] = {}
    for kind in ("attention", "window"):
        layers = cfg.layers_of(kind)
        if not layers:
            continue
        k_size = cfg.kv_heads_of(kind) * d
        v_size = cfg.kv_heads_of(kind) * cfg.value_dim
        group: dict[str, list] = {"wqkv": [], "wo": []}
        for l in layers:
            q = cfg.q_size_of(l)
            o = cfg.heads_of(l) * cfg.value_dim
            wq, wk, wv = (dense(key(l, 0), (h, q), h), dense(key(l, 1), (h, k_size), h),
                          dense(key(l, 2), (h, v_size), h))
            group["wqkv"].append(jnp.concatenate([wq, wk, wv], axis=-1) if cfg.wide_key
                                 else fuse_qkv(wq, wk, wv, tp))
            group["wo"].append(dense(key(l, 3), (o, h), o))
            if cfg.attn_gate:
                group.setdefault("wg", []).append(dense(
                    key(l, 4), (h, q // d), h / _GATE_LOGIT_SCALE ** 2))
            if cfg.wide_key and cfg.has_sink(kind):
                group.setdefault("sink", []).append(_sink_logits(key(l, 5), cfg, q // d))
        out[_GROUP_OF_KIND[kind]] = {k: jnp.stack(v) for k, v in group.items()}
    return out


def _sink_logits(key, cfg: ModelConfig, heads: int) -> jax.Array:
    """A window layer's sink logits ``[heads]`` float32, drawn around
    ``log(sliding_window) - 0.5``, half a unit either way: on random weights
    a row's scores are ~N(0, 1), so a full window's keys sum to
    ``sliding_window x e^0.5`` and such a sink takes a fifth to a third of
    the row's mass (more before the window is full): enough that a sink
    left out, or taken from the wrong head, moves the logits past the
    comparison's tolerance (the control's ``sink`` fault, PERF.md section
    6, PR 46), and not so much that the window's keys stop mattering."""
    return (math.log(cfg.sliding_window) - 0.5
            + 0.5 * jax.random.normal(key, (heads,), jnp.float32))


def _init_conv_operators(rng: jax.Array, cfg: ModelConfig, dense) -> dict:
    """The conv layers' leaves, one entry a CONV layer: ``in_proj [h, 3
    h]`` (columns ``[B | C | z]``), the depthwise taps ``conv_w [L, h]``
    (tap ``j`` multiplies ``u`` at ``L - 1 - j`` positions back: the
    published ``conv.weight[:, 0, j]``), ``out_proj [h, h]``. The taps are
    drawn at ``L^-0.5`` each, unequal, so that their order shows."""
    h, Lc, K = cfg.hidden_size, len(cfg.layers_of("conv")), cfg.conv_L_cache
    key = lambda n: jax.random.fold_in(rng, 90 + n)  # noqa: E731
    return {
        "in_proj": dense(key(0), (Lc, h, 3 * h), h),
        "conv_w": dense(key(1), (Lc, K, h), K),
        "out_proj": dense(key(2), (Lc, h, h), h),
    }


# What the two small maps of a linear layer (beta's logits and the decay's) are
# drawn UNDER the fan-in scale by, and what its output norm is drawn around
# (:func:`_init_linear_operators` says why, with the readings).
_SMALL_MAP_DIVISOR = 10.0
_LINEAR_OUT_NORM = 0.25


def _init_linear_operators(rng: jax.Array, cfg: ModelConfig, dense) -> dict:
    """The linear layers' leaves, one entry a LINEAR layer: ``w_qkv [h, 2 H
    dk + H dv]`` (columns ``[q | k | v]``, the published ``q_proj``,
    ``k_proj``, ``v_proj``), ``w_z [h, H dv]`` (the output gate's,
    ``g_proj``), ``w_ba [h, 2 H]`` (``b_proj`` then ``a_proj``: beta's logits
    and the decay's), the depthwise taps ``conv_w [K, channels]`` over ``[q |
    k | v]`` (tap ``j`` multiplies the input ``K - 1 - j`` positions back:
    the published ``conv1d.weight[:, 0, j]``), ``A_log [H]`` and ``dt_bias
    [H]`` float32, the output norm ``o_norm [dv]`` and ``w_out [H dv, h]``.

    The decay ``alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))`` is drawn
    to spread over about 0.9-0.999, head by head: ``dt = softplus(dt_bias)``
    log-uniform in [0.001, 0.1] (the family's initialisation) and
    ``exp(A_log)`` uniform in [0.8, 1.25] (a state that forgets in ten
    tokens hides the recurrence from any comparison). The taps are drawn at
    ``K^-0.5`` each, unequal, so that their order shows.

    **Two scales are drawn under the fan-in scale, both settled by controls
    on the v5e at the published widths** (PERF.md section 6, PR 50; the
    cell's own comparison, tolerance 0.15 untouched). (1) ``W_b`` and ``W_a``
    at a TENTH of it (:data:`_SMALL_MAP_DIVISOR`): the stream of a model
    normed on its sub-layers' OUTPUTS grows with depth (root-mean-square up
    to ~5 here), so at the fan-in scale beta's logit saturates and ``beta =
    2 sigmoid(.)`` is 0 or 2 for most tokens: the identity or a REFLECTION,
    which both keep every rounding error the state has ever taken, where
    ``beta`` near 1 overwrites it along ``k``; and the decay's logit beside
    ``dt_bias`` of -2 .. -7 puts ``alpha`` near 0 for half the tokens. Sound
    readings over three seeds, probes of 96 + 17 and 320 + 33 tokens: at the
    fan-in scale **0.126-0.157 and 0.191-0.233 (not correct)**, at a tenth
    0.085-0.109 and 0.091-0.107. (2) A linear mixer's output norm
    (``attn_norm`` of a linear layer) around :data:`_LINEAR_OUT_NORM` where
    the other output norms are drawn around 1: a recurrence carries what
    bfloat16 rounds off its inputs to every later token (the state ITSELF
    rounded to bfloat16 after each update reads 0.12-0.25 against the
    reference), so on random weights a linear layer gives the stream several
    times an attention layer's noise; drawn lower it is a smaller share of
    the stream and still far from unseen (``decay`` / ``neg_eigval`` /
    ``fp8`` read 10 x the tolerance and more at every scale tried). A trained
    checkpoint's decays, gates and norms are not known here."""
    h, Ll, K = cfg.hidden_size, len(cfg.layers_of("linear")), cfg.linear_conv_kernel_dim
    H, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key = lambda n: jax.random.fold_in(rng, 100 + n)  # noqa: E731
    dt = jnp.exp(jax.random.uniform(
        key(5), (Ll, H), jnp.float32, math.log(0.001), math.log(0.1)))
    return {
        "w_qkv": dense(key(0), (Ll, h, cfg.linear_channels), h),
        "w_z": dense(key(1), (Ll, h, H * dv), h),
        "w_ba": dense(key(2), (Ll, h, 2 * H), h * _SMALL_MAP_DIVISOR ** 2),
        "conv_w": dense(key(4), (Ll, K, cfg.linear_channels), K),
        "A_log": jnp.log(jax.random.uniform(key(6), (Ll, H), jnp.float32, 0.8, 1.25)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "o_norm": _varied_ones(key(7), (Ll, dv), cfg.jax_dtype),
        "w_out": dense(key(8), (Ll, H * dv, h), H * dv),
    }


def _init_ssm_operators(rng: jax.Array, cfg: ModelConfig, dense) -> dict:
    """The "mamba" layers' leaves, one entry a MAMBA layer. The published
    ``in_proj [h, 2 d_in + 2 G N + H]`` (columns ``[z | x | B | C | dt]``) is
    kept as TWO matrices, ``w_zx [h, d_in + channels]`` (``[z | x | B | C]``)
    and ``w_dt [h, H]``: 10,304 columns are 80.5 lane rows, and the 64 of
    ``dt`` feed float32 arithmetic of their own (as :func:`_init_linear_operators`
    splits ``w_qkv``, ``w_z``, ``w_ba``). The depthwise taps ``conv_w [K,
    channels]`` over ``[x | B | C]`` (tap ``j`` multiplies the input ``K - 1 -
    j`` positions back: the published ``conv1d.weight[:, 0, j]``) and their
    bias ``conv_b [channels]``; ``A_log``, ``D`` and ``dt_bias`` ``[H]``
    float32; the gated norm ``ssm_norm [d_in]`` and ``w_out [d_in, h]``.

    Drawn as the family initialises them: ``dt = softplus(dt_bias)``
    log-uniform in [0.001, 0.1], ``A = exp(A_log)`` uniform in [1, 16] (the
    decay ``exp(-A dt)`` then spreads from 0.2 to 0.999 head by head), the
    convolution's bias uniform in +-``K^-0.5`` (a ``Conv1d``'s default), ``D``
    around 1. The taps are drawn at ``K^-0.5`` each, unequal, so that their
    order shows; every matrix at the fan-in scale."""
    h, Ls, K = cfg.hidden_size, len(cfg.layers_of("ssm")), cfg.ssm_conv_kernel
    H, d_in, ch = cfg.ssm_num_heads, cfg.ssm_inner, cfg.ssm_channels
    key = lambda n: jax.random.fold_in(rng, 120 + n)  # noqa: E731
    dt = jnp.exp(jax.random.uniform(
        key(5), (Ls, H), jnp.float32, math.log(0.001), math.log(0.1)))
    out = {
        "w_zx": dense(key(0), (Ls, h, d_in + ch), h),
        "w_dt": dense(key(1), (Ls, h, H), h),
        "conv_w": dense(key(2), (Ls, K, ch), K),
        "A_log": jnp.log(jax.random.uniform(key(6), (Ls, H), jnp.float32, 1.0, 16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(key(7), (Ls, H), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
        "ssm_norm": _varied_ones(key(8), (Ls, d_in), cfg.jax_dtype),
        "w_out": dense(key(9), (Ls, d_in, h), d_in),
    }
    if cfg.ssm_conv_bias:
        out["conv_b"] = jax.random.uniform(
            key(3), (Ls, ch), jnp.float32, -K ** -0.5, K ** -0.5).astype(cfg.jax_dtype)
    return out


def _init_latent_attention(rng: jax.Array, cfg: ModelConfig, dense) -> dict:
    """The latent attention's leaves, ``[L, ...]`` each: ``wq_a [h, rq]``
    with ``q_norm [rq]``, ``wq_b [rq, H (dn + dr)]`` (per head nope then
    rope), ``wkv_a [h, rkv + dr]`` (compressed K/V then the shared rope
    key) with ``kv_norm [rkv]``, the K/V up-projection (the published
    ``kv_b_proj [rkv, H (dn + dv)]``) in its two parts and in the order
    the absorbed decode contracts them, ``wk_b [H, dn, rkv]`` (``q_nope
    -> q'``) and ``wv_b [H, rkv, dv]`` (``o' -> o``), so that no decode
    step slices or transposes a weight, and ``wo [H dv, h]``."""
    h, L, H = cfg.hidden_size, cfg.num_layers, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    key = lambda n: jax.random.fold_in(rng, 40 + n)  # noqa: E731
    return {
        "wq_a": dense(key(0), (L, h, rq), h),
        "q_norm": jnp.ones((L, rq), cfg.jax_dtype),
        "wq_b": dense(key(1), (L, rq, H * (dn + dr)), rq),
        "wkv_a": dense(key(2), (L, h, rkv + dr), h),
        "kv_norm": jnp.ones((L, rkv), cfg.jax_dtype),
        "wk_b": dense(key(3), (L, H, dn, rkv), rkv),
        "wv_b": dense(key(5), (L, H, rkv, dv), rkv),
        "wo": dense(key(4), (L, H * dv, h), H * dv),
    }


# What an UN-GATED expert's down-projection is drawn under a gated one's by:
# on unit-variance inputs ``relu(u)^2`` has a root-mean-square of sqrt(3/2) =
# 1.22 where ``silu(g) * u`` has 0.60, so at one scale of weights a relu^2
# expert's term is twice a SwiGLU's, and a term that enters or leaves the
# chosen k moves the stream by twice as much. From a control on the v5e
# (PERF.md section 6, PR 54: nemotron_h's cell, 64 of 128 experts held, 6 a
# token beside a shared expert, the cell's own comparison at 320 + 33 tokens,
# two seeds, tolerance 0.15 untouched; max |diff| sound / routed scaling
# factor left out / bias on the choice left out): at 1 / k 0.105-0.130 /
# 0.38-0.51 / 0.48-0.51 (too near the tolerance: half the experts are held,
# so every second flip of a token's 6th and 7th expert shows), at 1 / 2k
# **0.042-0.043 / 0.158-0.167 / 0.21-0.29**, at 1 / 3k 0.043 / 0.10-0.13
# (the scaling factor no longer caught), at 1 / 4k 0.037-0.044 / 0.087-0.100 /
# 0.12-0.13; with the routed terms zeroed 0.028-0.038.
_RELU2_DOWN_DIVISOR = 2


def _routed_down_divisor(cfg: ModelConfig) -> int:
    """What a routed expert's down-projection is drawn UNDER the fan-in
    scale by (:func:`_init_shared_sparse_mlp` says why it is drawn under
    it at all): ``num_experts_per_tok`` beside a shared expert, which
    carries the layer whichever routed terms flip; twice that where the
    routed terms ARE the layer (no shared expert). The second from a
    control on the v5e (PERF.md section 6, PR 35: 64 experts, 4 a token,
    no shared one, 10 layers of width 2048; the cell's own comparison
    sound, and with the routed terms zeroed on the reference's side):
    at ``1 / k`` twelve sound seeds read max |diff| 0.085-0.155 against a
    tolerance of 0.15, one of them over it; at ``1 / 2k`` fifteen sound
    probes read 0.067-0.121 and the zeroed terms 0.186-0.265, caught; at
    ``1 / 4k`` sound 0.051-0.101 and the zeroed terms 0.106-0.122, NOT
    caught: the largest scale that passes is the smallest that still
    sees the layer. Twice either for an un-gated ``relu^2`` expert
    (:data:`_RELU2_DOWN_DIVISOR`)."""
    return (cfg.num_experts_per_tok * (1 if cfg.num_shared_experts else 2)
            * (1 if cfg.gated_mlp else _RELU2_DOWN_DIVISOR))


def _init_shared_sparse_mlp(rng: jax.Array, cfg: ModelConfig, dense, tp: int) -> dict:
    """The MLPs of a model whose leading layers are dense and whose others
    are sigmoid-routed: ``dense_mlp`` (``[first_dense_layers, ...]``: wgu,
    w_down) and ``moe`` (``[num_layers - first_dense_layers, ...]``:
    ``w_router [h, E]`` at the router's full width, the shared experts as
    one SwiGLU of width ``ns x im``, ``shared_wgu [h, 2 ns im]`` and
    ``shared_down [ns im, h]``, and the HELD experts' ``w_gu [Eh, h, 2
    im]`` (gate then up) and ``w_down [Eh, im, h]``). The experts' two
    leaves are TUPLES of one array a sparse layer, not stacked: a
    ``[l]`` slice of a stacked array that feeds a loop over the experts
    is copied out before the loop, 1 GB a layer at the published widths
    (the v5e's compiler, PR 32), as :func:`init_cache` found for the
    pages. Expert ``e`` of the model is drawn from key ``e`` whatever
    share is held, so that the shares of one seed are shares of one
    model.

    A routed expert's down-projection is drawn at ``1 /
    num_experts_per_tok`` of the fan-in scale (``1 / 2 k`` without a
    shared expert: :func:`_routed_down_divisor`). The choice of the ``k``
    highest scores is not continuous: on random weights the ``k``-th and
    the next score of a token lie ~0.07 of their spread apart, what
    bfloat16 has rounded off the residual stream by then (~1%) moves
    them past each other in one token of six a layer, and a whole term
    ``w_e SwiGLU_e(x)`` then enters or leaves on one side of a
    comparison with float32 and not on the other. At the fan-in scale
    that term is 10-20% of the stream (a pre-norm branch on random
    weights is as large as the stream it adds to; in a trained model of
    this depth the stream is tens of times a branch), and engine and
    reference part by 0.3-0.5 in a log-probability whatever the code
    does; at ``1 / k`` a term that flips moves the stream by 1-2%
    (the ``k`` chosen terms together are then about a tenth of the
    shared expert's; PERF.md, PR 32: 0.29 on the v5e at the fan-in scale
    against a tolerance of 0.15). The router, the shared experts and every other
    leaf keep the fan-in scale."""
    h, i, im = cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size
    Ld = cfg.first_dense_layers
    Ls = len(cfg.sparse_layers)
    lo, hi = cfg.experts_held_range
    ns = cfg.num_shared_experts
    key = lambda n: jax.random.fold_in(rng, 60 + n)  # noqa: E731
    # An un-gated expert's two matrices are drawn at the published width and
    # STORED with zero columns / rows behind it (``cfg.expert_stored_width``).
    pad = cfg.expert_stored_width - im
    halves = 2 if cfg.gated_mlp else 1

    def experts(k, shape, fan_in, pad_axis):  # Ls x [Eh, ...], expert e from key e
        def layer(s):
            w = jnp.stack([
                dense(jax.random.fold_in(jax.random.fold_in(k, e), s), shape, fan_in)
                for e in range(lo, hi)
            ])
            return jnp.pad(w, [(0, pad * (a == pad_axis)) for a in range(3)]) if pad else w
        return tuple(layer(s) for s in range(Ls))

    out = {"moe": {
        "w_router": dense(key(0), (Ls, h, cfg.num_experts), h),
        "w_gu": experts(key(1), (h, halves * im), h, pad_axis=2),
        "w_down": experts(key(2), (im, h), im * _routed_down_divisor(cfg) ** 2, pad_axis=1),
    }}
    if cfg.router_bias:
        # Non-zero, so that it changes the choice for a measurable share
        # of tokens, and small against the spread of the scores it is
        # added to (sigmoid of a unit-variance logit: ~0.21).
        out["moe"]["expert_bias"] = 0.05 * jax.random.normal(
            key(8), (Ls, cfg.num_experts), jnp.float32)
    if ns:
        sw = cfg.shared_expert_width
        out["moe"]["shared_wgu"] = dense(key(3), (Ls, h, halves * sw), h)
        out["moe"]["shared_down"] = dense(key(4), (Ls, sw, h), sw)
    if Ld:
        out["dense_mlp"] = {
            "wgu": fuse_gu(dense(key(5), (Ld, h, i), h), dense(key(6), (Ld, h, i), h), tp),
            "w_down": dense(key(7), (Ld, i, h), i),
        }
    return out


def layer_params(params: Params, l: int, cfg: ModelConfig) -> dict:
    """Layer ``l``'s leaves: ``params["layers"]`` at ``l``; where the
    layers are of two kinds (``cfg.layer_groups``), its operator's from
    ``attn``, ``attn_window``, ``conv``, ``linear`` or ``ssm`` at its index among its kind; and,
    where the MLPs are kept apart (``cfg.shared_sparse``), the dense MLP of a
    leading layer or the sparse one of ``cfg.sparse_layers``."""
    lp = jax.tree.map(lambda a: a[l], params["layers"])
    kind = cfg.layer_kind(l)
    if cfg.layer_groups and kind in _GROUP_OF_KIND:   # ("none": a feed-forward block)
        at = cfg.layers_of(kind).index(l)
        lp.update({k: v[at] for k, v in params[_GROUP_OF_KIND[kind]].items()})
    if l in cfg.sparse_layers:
        group, at = "moe", cfg.sparse_layers.index(l)
    elif l < cfg.first_dense_layers:
        group, at = "dense_mlp", l
    else:   # a mixer block of a one-sub-layer stack, or a model of dense MLPs in ``layers``
        return lp
    # ``v[at]``: a stacked leaf's slice, or the experts' own array
    lp.update({k: v[at] for k, v in params[group].items()})
    return lp


def _init_loop_extras(rng: jax.Array, cfg: ModelConfig, params: Params) -> None:
    """The leaves only a looped / sandwich-norm model has, added in place:
    the two output norms per layer and the exit gate ``Linear(h, 1)``.

    An output norm's weight is the size of its sub-layer's whole
    contribution to the residual stream, so it is drawn around
    ``sqrt(2 / L)``: the ``2 L`` branches of a pass then move the state
    by about twice its own norm, whatever the depth (GPT-2-style inits
    scale a residual branch by ``1 / sqrt(2 L)`` for the same reason).
    Drawn around 1, a pass multiplies the state's norm by ``sqrt(2 L)``
    and, on random weights, what bfloat16 rounds off grows from pass to
    pass until it parts from float32 whatever the code does (PERF.md, PR
    27: 0.43 against a tolerance of 0.15 at 48 x 4, 0.05 with this
    scale), and no comparison with a reference means anything. Each
    weight varies by 10% around the scale, so that a norm applied with
    the wrong weight, or not at all, changes the logits."""
    h, L, dt = cfg.hidden_size, cfg.num_layers, cfg.jax_dtype
    if cfg.sandwich_norm:
        for i, name in enumerate(("attn_post_norm", "mlp_post_norm")):
            key = jax.random.fold_in(rng, 21 + i)
            params["layers"][name] = (
                (2.0 / L) ** 0.5
                * (1.0 + 0.1 * jax.random.normal(key, (L, h), jnp.float32))
            ).astype(dt)
    if cfg.ut_steps > 1:
        key = jax.random.fold_in(rng, 31)
        params["exit_gate"] = {
            "w": (jax.random.normal(key, (h,), jnp.float32) * h ** -0.5).astype(dt),
            "b": jnp.zeros((), dt),
        }


def params_fuse_tp(params: Params) -> int:
    """The tp the params' fused projections were laid out for (1 for
    pytrees predating the marker)."""
    v = params.get("fuse_tp")
    return 1 if v is None else int(v)


def init_cache(cfg: ModelConfig, engine: EngineConfig, dtype=None) -> tuple:
    """Combined KV cache: a TUPLE of per-layer page arrays
    ``[n_pages, page_size, 2*n_kv, d]`` (the last page is the garbage
    page absorbing padded-position writes).

    Per-layer arrays instead of one stacked ``[L, ...]`` tensor:
    feeding the Pallas attention custom call a ``cache[l]`` slice of
    the stacked donated buffer made XLA materialize a per-layer copy
    each step; separate buffers give the kernel aliased views for free.
    Pipeline parallelism keeps the
    stacked layout (:func:`init_cache_stacked`) — its stage sharding IS
    the layer axis.

    With ``engine.kv_dtype == "int8"`` each layer entry is instead a
    ``{"kv": int8 pages, "scale": f32 [n_pages, ps, 2*n_kv]}`` dict —
    symmetric per-slot-per-head quantized storage with the scale pages
    carried alongside (engine/kv_quant.py); the tuple structure and
    every index in it are unchanged.

    A looped model (``cfg.ut_steps > 1``) keeps ``ut_steps`` planes of
    ``num_kv_blocks + 1`` pages in each layer's array, plane ``u`` for
    pass ``u``: block ``b`` of pass ``u`` is page ``u * (num_kv_blocks +
    1) + b``, and every plane ends in a garbage page of its own."""
    return cache_for_blocks(cfg, engine, engine.num_kv_blocks, dtype,
                            window_blocks=engine.num_window_blocks)


def cache_for_blocks(cfg: ModelConfig, engine: EngineConfig, blocks: int, dtype=None,
                     window_blocks: int | None = None, slots: int | None = None) -> tuple:
    """:func:`init_cache` for ``blocks`` blocks and a garbage page: every
    layer's array ``[pages, *cfg.kv_page_tail(block_size, kind)]`` of ITS
    kind, indexed by the same block ids. A conv layer's pages hold its
    state (:func:`conv_layer`), an attention layer's its K/V. A WINDOW
    layer's array is the window pool's: ``window_blocks`` blocks (as many
    as ``blocks`` where not given) and a garbage page, under block ids of
    that pool's own. A LINEAR or MAMBA layer's entry is no page array but its
    slab, ``{"state", "conv"}`` of ``slots`` lane slots (``engine.state_slots``
    where not given; ``ModelConfig.slab_shapes``), the state float32; a
    feed-forward block's (``layer_kind`` "none") is ``{}``, no leaf at all."""
    dtype = dtype or cfg.jax_dtype
    window_blocks = blocks if window_blocks is None else window_blocks
    if cfg.has_slab:
        if engine.kv_quantized:
            _refuse_int8_latent(cfg)
        slab = cfg.slab_shapes(engine.state_slots if slots is None else slots)

        def entry(kind: str):
            if kind in SLAB_KINDS:
                return {"state": jnp.zeros(slab["state"], jnp.float32),
                        "conv": jnp.zeros(slab["conv"], dtype)}
            if kind == "none":   # a feed-forward block caches nothing: no leaf
                return {}
            return jnp.zeros((blocks + 1, *cfg.kv_page_tail(engine.block_size)), dtype)

        return tuple(entry(cfg.layer_kind(l)) for l in range(cfg.num_layers))
    shapes = []
    for l in range(cfg.num_layers):
        kind = cfg.layer_kind(l)
        pages = cfg.ut_steps * ((window_blocks if kind == "window" else blocks) + 1)
        shapes.append((pages, *cfg.kv_page_tail(engine.block_size, kind)))
    if engine.kv_quantized:
        _refuse_int8_latent(cfg)
        return tuple(
            {
                "kv": jnp.zeros(shape, jnp.int8),
                "scale": jnp.zeros(shape[:-1], jnp.float32),
            }
            for shape in shapes
        )
    return tuple(jnp.zeros(shape, dtype) for shape in shapes)


def init_cache_stacked(
    cfg: ModelConfig, engine: EngineConfig, dtype=None
):
    """Stacked ``[L, n_pages, page_size, 2*n_kv, d]`` cache — the
    pipeline-parallel layout (layer axis shards over the pp mesh).

    With ``engine.kv_dtype == "int8"`` the stacked cache is instead ONE
    ``{"kv": int8 [L, ...], "scale": f32 [L, n_pages, ps, 2*n_kv]}``
    dict — the same quantize-at-write storage as :func:`init_cache`'s
    per-layer dicts, with the layer axis stacked so both members shard
    over the pp mesh together (each stage holds only its own layers'
    kv AND scale pages)."""
    dtype = dtype or cfg.jax_dtype
    shape = (
        cfg.num_layers,
        engine.num_kv_blocks + 1,
        *cfg.kv_page_tail(engine.block_size),
    )
    if engine.kv_quantized:
        _refuse_int8_latent(cfg)
        return {
            "kv": jnp.zeros(shape, jnp.int8),
            "scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    return jnp.zeros(shape, dtype)


def _refuse_int8_latent(cfg: ModelConfig) -> None:
    if cfg.has_slab:
        raise NotImplementedError(
            "kv_dtype='int8' with linear_attention or mamba layers: the full layers' "
            "pages beside a float32 slab were not compared as int8")
    if cfg.windowed:
        raise NotImplementedError(
            "kv_dtype='int8' with sliding_attention layers: the window pool's "
            "pages were not compared as int8")
    if cfg.hybrid or cfg.kv_head_pairs:
        raise NotImplementedError(
            "kv_dtype='int8' with conv state pages or paired KV heads: the "
            "int8 pages keep a scale per slot and KV head"
        )
    if cfg.latent:
        raise NotImplementedError(
            "kv_dtype='int8' with attention='mla': the int8 pages keep a "
            "scale per slot and KV HEAD, and a latent page has no heads"
        )


# -- building blocks -------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def rope_tables(
    positions: jax.Array, d: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) ``[..., T, d/2]`` for :func:`rope_apply`. Positions are
    the same for every layer of a forward pass, so the tables are
    computed ONCE per program instead of twice per layer (the transcend-
    entals are VPU work that used to recur 2L times per wave)."""
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, scaling: dict) -> jax.Array:
    """YaRN's static frequencies ``[d/2]``: pair ``i``'s ``theta^(-2i/d)``
    blended with itself over ``factor`` by the linear ramp between the
    correction dims of ``beta_fast`` and ``beta_slow`` (the family's
    ``yarn_find_correction_range``): pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn less than ``beta_slow`` times are interpolated."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(scaling.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001
    extra = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def latent_rope_tables(positions: jax.Array, cfg: ModelConfig):
    """(cos, sin) ``[T, dr/2]`` of the latent attention's rope part:
    plain rope over ``qk_rope_head_dim``, or YaRN's frequencies with cos
    and sin scaled by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``."""
    d = cfg.qk_rope_head_dim
    scaling = dict(cfg.rope_scaling or ())
    if not scaling:
        return rope_tables(positions, d, cfg.rope_theta)
    freqs = yarn_inv_freq(d, cfg.rope_theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs
    m = (_yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
         / _yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def kind_rope_tables(positions: jax.Array, cfg: ModelConfig, kind: str):
    """(cos, sin) ``[T, r/2]`` of the layers of published ``kind``
    (``cfg.rope_of``), ``r = head_dim x partial_rotary_factor`` the values
    of a head that are rotated, from the front (:func:`rope_apply` passes
    the others through). rope_type "yarn": :func:`yarn_inv_freq` over those
    ``r`` values, cos and sin both multiplied by ``attention_factor``."""
    rp = cfg.rope_of(kind)
    r = int(cfg.head_dim * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    if rp.get("rope_type", "default") != "yarn":
        return rope_tables(positions, r, theta)
    with jax.named_scope("rope_yarn"):
        angles = positions[..., None].astype(jnp.float32) * yarn_inv_freq(r, theta, rp)
        m = float(rp.get("attention_factor", 1.0))
        return jnp.cos(angles) * m, jnp.sin(angles) * m


def latent_sm_scale(cfg: ModelConfig) -> float:
    """``(dn + dr)^-0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim)^2`` where the rope is scaled."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    scaling = dict(cfg.rope_scaling or ())
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= _yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rope_apply(
    x: jax.Array, cos: jax.Array, sin: jax.Array
) -> jax.Array:
    """Rotate ``x`` ``[..., T, n, d]`` by precomputed tables ``[..., T,
    r/2]``: the first ``r`` values of a head in half-split pairs, the
    other ``d - r`` passed through (``r = d`` for every model but one with
    a ``partial_rotary_factor``)."""
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([rope_apply(x[..., :r], cos, sin), x[..., r:]], axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [..., T, n, d], positions: [..., T]."""
    cos, sin = rope_tables(positions, x.shape[-1], theta)
    return rope_apply(x, cos, sin)


def _mlp(x, lp, cfg: ModelConfig, tp: int, mesh=None, row_valid=None,
         expert_stats: list | None = None):
    if "w_router" in lp and cfg.shared_sparse:
        return _shared_sparse_mlp(x, lp, cfg, row_valid, expert_stats)
    if cfg.is_moe and not cfg.shared_sparse:
        return _moe_mlp(x, lp, cfg, mesh)
    gu = _dot(x, lp["wgu"])
    g, u = split_gu(gu, tp)
    act = (jax.nn.silu(g) * u).astype(x.dtype)
    return _dot(act, lp["w_down"]).astype(x.dtype)


# -- the sigmoid-routed sparse MLP: a chip's share, dropless ------------------

# At or under this many rows every held expert runs on EVERY row and the
# rows not routed to it are weighted zero: a decode step's bytes and
# operations are then the same whatever the router favours (PERF.md, PR
# 32). A bf16 weight costs 2 B to read and 2 FLOP a row, so under ~240
# rows (the v5e's FLOP per byte) the product hides behind the weight
# stream it needs anyway. Above it the work follows the pairs held.
# Which path such a step takes is ``ops/expert_stream.py:impl``'s to say
# from the backend, the dtype and the shapes: on a TPU, bf16 or f32 at
# whole-lane widths, ONE Pallas kernel that streams each held expert's
# weights once (``"stream/pallas"``, PR 38); everywhere else (the CPU
# rehearsals, int8, odd widths) :func:`_experts_all_rows`, the loop of XLA
# products that is the definition of both (``"all_rows"``).
_EXPERTS_ALL_ROWS_MAX = 256
# Above it: the chosen pairs sorted by expert and the experts' SwiGLU over
# them (:func:`_experts_grouped`), whatever the count of held experts: on a
# TPU one kernel that streams each TOUCHED expert's weights once
# (``ops/expert_stream.py:grouped_impl`` says where: ``"grouped/stream"``, PR
# 47), else two grouped products (``ops/grouped_matmul.py:impl``).


def expert_call_shape(rows: int) -> str:
    """``"wave"`` for more rows than every expert on every row serves (a
    prefill wave), else ``"step"``: the ``shape`` under which a sparse
    layer's call is counted (``ops/grouped_matmul.py:count_traced``)."""
    return "wave" if rows > _EXPERTS_ALL_ROWS_MAX else "step"


def wave_impl(backend: str, dtype, rows_a_group: float, w_gu, w_down,
              gated: bool = True) -> str:
    """The implementation a wave's chosen pairs get (the ``impl`` of
    :func:`_experts_grouped`; ``grouped/<it>`` is the call's label):
    ``"stream"`` where ``ops/expert_stream.py:grouped_impl`` takes the call,
    else ``ops/grouped_matmul.py:impl``'s ``"pallas"`` or ``"ragged_dot"``."""
    return (expert_stream.grouped_impl(backend, dtype, rows_a_group, w_gu, w_down, gated)
            or grouped_matmul.impl(backend, dtype, w_gu, w_down))


def route_sigmoid(xf: jax.Array, w_router: jax.Array, cfg: ModelConfig,
                  bias: jax.Array | None = None):
    """(weights ``[N, E]`` float32, zero where not chosen; chosen ``[N,
    E]`` bool). ``sc = sigmoid(x Wg)`` in float32; the experts in
    ``n_group`` groups, a group's score the sum of its two highest
    ``sc``; the ``topk_group`` best groups kept; the ``k`` highest ``sc``
    among their experts chosen; weights ``sc_e / (sum(sc_chosen) +
    router_norm_eps) x routed_scaling_factor``. ``bias`` ``[E]``
    (``cfg.router_bias``) is added to the scores THE CHOICE is made by,
    groups and experts alike, and to nothing else: the weights are the
    chosen ``sc`` themselves."""
    N, E, G, k = xf.shape[0], cfg.num_experts, cfg.n_group, cfg.num_experts_per_tok
    logits = jnp.dot(
        xf.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    sc = jax.nn.sigmoid(logits)
    rows = jnp.arange(N)[:, None]
    pick = sc if bias is None else sc + bias.astype(jnp.float32)
    if G > 1:
        grp = pick.reshape(N, G, E // G)
        g_score = jnp.sum(jax.lax.top_k(grp, 2)[0], axis=-1)            # [N, G]
        _, g_idx = jax.lax.top_k(g_score, cfg.topk_group)
        kept = jnp.zeros((N, G), bool).at[rows, g_idx].set(True)
        # under every score: sc > 0, and a biased one is finite
        low = -1.0 if bias is None else -jnp.inf
        pick = jnp.where(kept[:, :, None], grp, low).reshape(N, E)
    _, idx = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(sc, idx, axis=1)
    w = (w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.router_norm_eps)
         * cfg.routed_scaling_factor)
    weights = jnp.zeros((N, E), jnp.float32).at[rows, idx].set(w)
    chosen = jnp.zeros((N, E), bool).at[rows, idx].set(True)
    return weights, chosen


def route_softmax(xf: jax.Array, w_router: jax.Array, cfg: ModelConfig,
                  bias: jax.Array | None = None):
    """(weights ``[N, E]`` float32, zero where not chosen; chosen ``[N,
    E]`` bool), as :func:`route_sigmoid` returns them. ``s = softmax(x
    Wr)`` in float32 over ALL ``E`` experts; the ``k`` highest chosen;
    weights ``s_e / sum(s_chosen)`` (``norm_topk_prob``). No groups, no
    bias on the choice (ModelConfig refuses them with this scoring)."""
    N, k = xf.shape[0], cfg.num_experts_per_tok
    logits = jnp.dot(
        xf.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    sc = jax.nn.softmax(logits, axis=-1)
    rows = jnp.arange(N)[:, None]
    w, idx = jax.lax.top_k(sc, k)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.router_norm_eps)
    weights = jnp.zeros(sc.shape, jnp.float32).at[rows, idx].set(w)
    chosen = jnp.zeros(sc.shape, bool).at[rows, idx].set(True)
    return weights, chosen


# The dropless layer's router, by ``ModelConfig.router_scoring``.
_ROUTERS = {"sigmoid": route_sigmoid, "softmax": route_softmax}


def _swiglu(x, w_gu, w_down, gated: bool = True):
    """``(silu(x Wg) * (x Wu)) Wd`` with ``w_gu = [Wg | Wu]``; float32 out.
    Not ``gated`` (``cfg.mlp_activation`` "relu2"): ``relu(x Wu)^2 Wd`` with
    ``w_gu = Wu`` alone."""
    gu = jnp.dot(x, w_gu, preferred_element_type=jnp.float32)
    if gated:
        g, u = jnp.split(gu, 2, axis=-1)
        act = (jax.nn.silu(g) * u).astype(x.dtype)
    else:
        act = jnp.square(jnp.maximum(gu, 0.0)).astype(x.dtype)
    return jnp.dot(act, w_down, preferred_element_type=jnp.float32)


# Experts an iteration of :func:`_experts_all_rows`'s loop, so that one
# expert's weights stream while the one before computes: the CPU's and the
# fallback path's knob since PR 38 (a TPU step at whole-lane widths runs
# ``ops/expert_stream.py``, which has no loop of products). One layer at 128
# rows on the v5e, ms a call (PERF.md section 6, PR 35): 64 experts of 2048
# x 1536 at unroll 1 / 2 / 4 / 8 / 16: 2.137 / 2.102 / 2.087 / 2.075 / 2.120,
# as 64 unrolled bodies 2.191 (and 18 s to compile where the loop takes
# 1.4); 12 experts of 7168 x 2048 looped 1.530, as 12 bodies 1.549.
_EXPERTS_LOOP_UNROLL = 4


def _experts_all_rows(xf, w_held, w_gu, w_down, gated: bool = True):
    """Every held expert on every row, the rows not routed to it weighted
    zero: ``[N, h]`` float32. The same bytes and operations whatever the
    routing. The DEFINITION of a step's expert layer, and its path on every
    backend but a TPU (and on a TPU for int8 or odd widths): there
    ``ops/expert_stream.py:expert_stream`` computes the same sums, in the
    same order of experts, from one stream of the weights, and is tested
    against this. One plain ``x @ W`` pair an expert, as a dense layer's: the
    batched form (``einsum("nh,ehi->eni")``) made the v5e's compiler
    re-lay the experts out, and keep the copy (672 MB a layer at the
    published widths) beside the weights for the whole megastep. ONE
    ``fori_loop`` over the experts whose body indexes their arrays (the
    slice fuses into the products: nothing is copied), however many are
    held. ``gated``: :func:`_swiglu`'s."""
    def body(e, out):
        y = _swiglu(
            xf,
            jax.lax.dynamic_index_in_dim(w_gu, e, keepdims=False),
            jax.lax.dynamic_index_in_dim(w_down, e, keepdims=False),
            gated,
        )
        return out + jax.lax.dynamic_slice_in_dim(w_held, e, 1, axis=1) * y

    out = jnp.zeros((xf.shape[0], w_down.shape[-1]), jnp.float32)
    return jax.lax.fori_loop(0, w_gu.shape[0], body, out,
                             unroll=math.gcd(_EXPERTS_LOOP_UNROLL, w_gu.shape[0]))


def _sorted_pairs(chosen_held, w_held, k: int, tile: int, align: int = 1):
    """The permutation that puts the chosen (row, held expert) pairs in
    expert order, without a sort. A pair's place is its expert's offset
    (the exclusive ``cumsum`` of the experts' counts, each rounded up to
    ``align`` places: the streamed kernel's groups start on whole sublane
    tiles, ``ops/expert_stream.py``) + its rank among that expert's rows
    (``cumsum`` down its column); a row's pairs, at most ``k``, come out in
    ascending expert order because its places ascend with the expert.
    Places that hold no pair belong to no group: a row with fewer than
    ``k`` held experts points its spare pairs past them all (place ``P``,
    weight 0).

    Returns (``rows`` ``[P]`` int32: the row each sorted place reads, 0
    where no pair lands; ``counts`` ``[Eh]`` int32; ``place`` ``[N,
    k]`` int32; ``weight`` ``[N, k]`` float32), ``P`` = ``N x k + (align - 1)
    x Eh`` rounded up to whole ``tile``s (the caller's slabs)."""
    N, Eh = chosen_held.shape
    P = -(-(N * k + (align - 1) * Eh) // tile) * tile
    counts = jnp.sum(chosen_held, axis=0, dtype=jnp.int32)
    padded = -(-counts // align) * align
    offset = jnp.cumsum(padded) - padded
    rank = jnp.cumsum(chosen_held, axis=0, dtype=jnp.int32) - 1
    dest = jnp.where(chosen_held, offset[None, :] + rank, P)             # [N, Eh]
    neg, expert = jax.lax.top_k(-dest, k)          # the k smallest places, ascending
    place = -neg
    weight = jnp.take_along_axis(w_held, expert, axis=1)
    rows = jnp.zeros((P,), jnp.int32).at[place.reshape(-1)].set(
        jnp.repeat(jnp.arange(N, dtype=jnp.int32), k), mode="drop", unique_indices=True)
    return rows, counts, place, weight


# Bytes of temporaries the grouped layer may hold at a time: the sorted
# rows, both products' results and the activation of one SLAB of the
# sorted places (the streamed kernel keeps the middle two in VMEM; its slabs
# are cut by the same count, so that a chip that holds few of the experts
# gathers no more empty places than it did). LFM2's widest wave (8,192
# places of 27 KB) is one slab; A.X-K1's (16,384 of 62 KB, a tenth of them
# live) would hold 1.0 GB at once and goes ~4,096 places at a time, as many
# slabs as hold a pair.
_GROUPED_SLAB_BYTES = 256 * 2 ** 20


def _slab_places(places: int, h: int, im: int, itemsize: int, tile: int) -> int:
    """Sorted places a slab: ``places`` in as few equal slabs, of whole
    ``tile``s, as keep a slab's temporaries under ``_GROUPED_SLAB_BYTES``."""
    a_place = h * itemsize + 2 * im * 4 + im * itemsize + h * 4
    slabs = -(-places * a_place // _GROUPED_SLAB_BYTES)
    return -(-places // (slabs * tile)) * tile


@functools.partial(jax.jit, static_argnames=("k", "impl", "all_held", "gated"))
def _experts_grouped(xf, w_held, chosen_held, w_gu, w_down, *, k: int, impl: str,
                     all_held: bool, gated: bool = True):
    """Each chosen (row, held expert) pair and nothing else, dropless at
    any skew: the pairs sorted by expert (:func:`_sorted_pairs`), then a
    slab of the sorted places at a time (:func:`_slab_places`; one slab
    where they fit, else as many as hold a pair, so a chip that holds few
    of the experts gathers and multiplies what it holds): the slab's rows
    gathered once, the experts' SwiGLU over them, and a weighted combine
    (``grouped_matmul.combine``: a row's terms added in ascending expert
    order, as :func:`_experts_all_rows` adds them, its other terms being
    exact zeros, so a token's sum has one order in a wave and in a decode
    step). ``impl`` ``"stream"``: ONE kernel (``ops/expert_stream.py:
    expert_stream_grouped``) that reads each touched expert's weights once
    and keeps the gate/up sums and the activation in VMEM; else two grouped
    products (``ops/grouped_matmul.py``) with ``silu(g) * u`` between them.
    Either way a held expert's weights are read once a slab whatever the
    width, only the tiles that hold a pair are multiplied, and the operands
    are bf16 and the sums float32 as :func:`_swiglu`'s. ``k``: pairs a row
    at most (the experts a token, or all that are held if fewer);
    ``all_held``: the chip holds every expert the router chooses among, so
    each row's ``k`` pairs are all here; ``gated``: :func:`_swiglu`'s (an
    un-gated expert's activation is ``relu(.)^2`` of ONE product). Jitted, so
    that the sparse layers of a program trace it once. ``[N, h]`` float32."""
    N, h = xf.shape
    Eh = w_gu.shape[0]
    stream = impl == "stream"
    align = expert_stream.GROUP_ALIGN if stream else 1
    # (an un-gated expert's temporaries are counted as a gated one's: more slabs, never fewer)
    S = _slab_places(N * k + (align - 1) * Eh, h, w_down.shape[1], xf.dtype.itemsize,
                     expert_stream.SLAB_ROWS if stream else grouped_matmul.tile_rows(impl))
    rows, counts, place, weight = _sorted_pairs(chosen_held, w_held, k, S, align)
    padded = -(-counts // align) * align
    start = jnp.cumsum(padded) - padded
    end, total = start + counts, jnp.sum(padded)

    def slab(s, out):
        lo = s * S
        x = xf[jax.lax.dynamic_slice_in_dim(rows, lo, S)]
        first = jnp.clip(start, lo, lo + S)
        sizes = jnp.clip(end, lo, lo + S) - first
        if stream:
            y = expert_stream.expert_stream_grouped(x, first - lo, sizes, w_gu, w_down,
                                                    gated=gated)
        else:
            gu = grouped_matmul.grouped_matmul(x, w_gu, sizes, impl=impl)
            if gated:
                g, u = jnp.split(gu, 2, axis=-1)
                act = (jax.nn.silu(g) * u).astype(xf.dtype)
            else:
                act = jnp.square(jnp.maximum(gu, 0.0)).astype(xf.dtype)
            y = grouped_matmul.grouped_matmul(act, w_down, sizes, impl=impl)
        # a place of another slab, or past the groups, is none here: never computed
        at = place - lo
        at = jnp.where((at >= 0) & (at < jnp.minimum(total - lo, S)), at, S)
        return grouped_matmul.combine(out, y, at, weight, full=all_held)

    out = jnp.zeros((N, h), jnp.float32)
    if rows.shape[0] == S:
        return slab(0, out)
    return jax.lax.fori_loop(0, -(-total // S), slab, out)


def _shared_sparse_mlp(x, lp, cfg: ModelConfig, row_valid=None,
                       expert_stats: list | None = None):
    """``sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)`` (``relu(x Wu)^2 Wd`` for
    each where ``cfg.mlp_activation`` is "relu2") over THIS chip's share
    of the routed experts (``cfg.experts_held``): the router runs at its
    full width, only the held experts' terms are added (what the absent
    ones would add is left out, and that partial result goes on), the
    shared experts are computed whole. Dropless: every (row, held expert)
    pair the router chose is computed. ``row_valid`` ``[N]`` marks the
    rows that are tokens (padding routes nowhere and is not counted).
    With ``expert_stats`` one int32 ``[5]`` is appended: held experts
    touched, 1 (this layer's step), pairs on held experts, pairs routed,
    rows the expert products ran on (every held expert on every row at or
    under ``_EXPERTS_ALL_ROWS_MAX`` rows; above it the rows of the tiles
    that hold a chosen pair)."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    N = xf.shape[0]
    lo, hi = cfg.experts_held_range
    gated = cfg.gated_mlp
    with jax.named_scope("router"):
        weights, chosen = _ROUTERS[cfg.router_scoring](
            xf, lp["w_router"], cfg, bias=lp.get("expert_bias"))
        if row_valid is None:
            row_valid = jnp.ones((N,), bool)
        chosen_held = chosen[:, lo:hi] & row_valid[:, None]
        w_held = jnp.where(chosen_held, weights[:, lo:hi], 0.0)
        Eh = hi - lo
        shape_name = expert_call_shape(N)
        backend = jax.default_backend()
        k = min(cfg.num_experts_per_tok, Eh)
        counts = jnp.sum(chosen_held, axis=0, dtype=jnp.int32)
        grouped = wave_impl(
            backend, xf.dtype, N * cfg.num_experts_per_tok / cfg.num_experts,
            lp["w_gu"], lp["w_down"], gated) if shape_name == "wave" else None
        path = f"grouped/{grouped}" if grouped else expert_stream.impl(
            backend, xf.dtype, N, lp["w_gu"], lp["w_down"], gated)
        grouped_matmul.count_traced(shape_name, path)
        if expert_stats is not None:
            if grouped == "stream":
                visited = expert_stream.grouped_rows_visited(counts)
            elif grouped:
                visited = grouped_matmul.rows_visited(counts, grouped_matmul.tile_rows(grouped))
            else:
                visited = jnp.int32(Eh * N)
            expert_stats.append(jnp.stack([
                jnp.sum(counts > 0), jnp.int32(1), jnp.sum(counts),
                jnp.sum(row_valid) * cfg.num_experts_per_tok, visited,
            ]).astype(jnp.int32))
    with jax.named_scope("experts"):
        if grouped:
            out = _experts_grouped(
                xf, w_held, chosen_held, lp["w_gu"], lp["w_down"],
                k=k, impl=grouped, all_held=Eh == cfg.num_experts, gated=gated)
        elif path == "stream/pallas":
            out = expert_stream.expert_stream(xf, w_held, lp["w_gu"], lp["w_down"],
                                              gated=gated)
        else:
            out = _experts_all_rows(xf, w_held, lp["w_gu"], lp["w_down"], gated)
    if "shared_wgu" in lp:
        with jax.named_scope("shared_expert"):
            out = out + _swiglu(xf, lp["shared_wgu"], lp["shared_down"], gated)
    return out.astype(x.dtype).reshape(shape)


# -- the mixtral path: softmax over the chosen, capacity-bounded ------------

def _moe_capacity(N: int, cfg: ModelConfig) -> int:
    """Per-expert token capacity for a dispatch of N tokens (static).
    The MIXTRAL path's only (``router_scoring="softmax"``): a token past
    an expert's capacity is dropped for that expert, which happens once
    ``num_experts_per_tok x moe_capacity_factor < num_experts``. The
    sigmoid-routed layer (:func:`_shared_sparse_mlp`) has no capacity and
    drops nothing."""
    k, E = cfg.num_experts_per_tok, cfg.num_experts
    return max(1, min(N, int(-(-N * k * cfg.moe_capacity_factor // E))))


def _moe_dispatch_local(xf, w_router, w_gate, w_up, w_down, cfg: ModelConfig,
                        e_offset, E_local: int):
    """Sparse top-k MoE over a contiguous slice of E_local experts.

    Capacity-bounded gather/scatter dispatch: each local expert computes a
    dense [C, h] batch of only its assigned tokens, so per-token MLP FLOPs
    scale with top_k (x capacity padding), not num_experts. Tokens past an
    expert's capacity are dropped for that expert (standard Switch/GShard
    semantics; `moe_capacity_factor` sizes the headroom). Runs per device
    under expert parallelism — ``e_offset`` selects the shard's experts
    and the caller psums the partial outputs (SURVEY.md §2.6 wide-EP row;
    the reference delegates this to SGLang's WideEP, dsr1-wideep-h100.md).
    """
    N, h = xf.shape
    k = cfg.num_experts_per_tok
    C = _moe_capacity(N, cfg)

    with jax.named_scope("router"):
        router = jnp.dot(xf, w_router, preferred_element_type=jnp.float32)  # [N, E]
        vals, idx = jax.lax.top_k(router, k)
        probs = jax.nn.softmax(vals, axis=-1)

        flat_e = idx.reshape(-1) - e_offset                 # [N*k] local expert ids
        flat_t = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
        flat_w = probs.reshape(-1)
        local = (flat_e >= 0) & (flat_e < E_local)

        # Slot of each entry within its expert's capacity batch, via one-hot
        # cumsum (O(N*k*E_local) int work — cheap next to the expert matmuls).
        onehot = (flat_e[:, None] == jnp.arange(E_local)[None, :]) & local[:, None]
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1  # [N*k]
        keep = local & (pos < C)
        # Overflow/non-local entries land in a garbage row/slot.
        e_c = jnp.where(keep, flat_e, E_local).astype(jnp.int32)
        p_c = jnp.where(keep, pos, C).astype(jnp.int32)

    with jax.named_scope("experts"):
        gathered = jnp.zeros((E_local + 1, C + 1, h), xf.dtype).at[e_c, p_c].set(xf[flat_t])
        g = gathered[:E_local, :C]                          # [E_local, C, h]
        gate = jnp.einsum("ech,ehi->eci", g, w_gate, preferred_element_type=jnp.float32)
        up = jnp.einsum("ech,ehi->eci", g, w_up, preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(xf.dtype)
        down = jnp.einsum("eci,eih->ech", act, w_down, preferred_element_type=jnp.float32)

        down_pad = jnp.pad(down, ((0, 1), (0, 1), (0, 0)))  # garbage row/slot -> 0
        entry_out = down_pad[e_c, p_c]                      # [N*k, h] f32
        w_masked = jnp.where(keep, flat_w, 0.0)
        out = jnp.zeros((N, h), jnp.float32).at[flat_t].add(w_masked[:, None] * entry_out)
        return out.astype(xf.dtype)


def _moe_dispatch_a2a(xl, w_router, w_gate, w_up, w_down, cfg: ModelConfig,
                      tp: int, E_local: int):
    """Token all-to-all EP dispatch over one shard's token slice ``xl``
    ([n, h]); runs inside shard_map over 'tp'.

    Wide-EP dataflow (SURVEY.md §2.6; the reference deploys it via
    SGLang's WideEP, dsr1-wideep-h100.md:8): each shard routes its OWN
    tokens, packs per-destination send buffers (capacity-bounded), and
    one ``all_to_all`` delivers every token to the shard holding its
    chosen expert; after the expert SwiGLUs a second ``all_to_all``
    returns the outputs for the weighted combine at the source. Per-chip
    activation traffic is O(N/tp * k) instead of the replicated path's
    O(N) broadcast compute — the winning trade once E and the host count
    grow past what weight-resident replication can carry.

    Drop semantics differ from the replicated path: capacity binds
    per (source, destination) pair here vs per expert there, so the two
    modes are bit-identical only while nothing overflows (generous
    ``moe_capacity_factor``); under saturation both drop, differently.
    """
    n, h = xl.shape
    k = cfg.num_experts_per_tok
    # Per-destination send capacity from this shard.
    Cs = max(1, min(n * k, int(-(-n * k * cfg.moe_capacity_factor // tp))))

    router = jnp.dot(xl, w_router, preferred_element_type=jnp.float32)  # [n, E]
    vals, idx = jax.lax.top_k(router, k)
    probs = jax.nn.softmax(vals, axis=-1)

    flat_e = idx.reshape(-1)                                # [n*k] global ids
    flat_t = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_w = probs.reshape(-1)
    dest = flat_e // E_local                                # [n*k] dest shard

    onehot = dest[:, None] == jnp.arange(tp)[None, :]
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    keep = pos < Cs
    d_c = jnp.where(keep, dest, tp).astype(jnp.int32)
    p_c = jnp.where(keep, pos, Cs).astype(jnp.int32)

    send_x = jnp.zeros((tp + 1, Cs + 1, h), xl.dtype).at[d_c, p_c].set(xl[flat_t])
    send_e = jnp.full((tp + 1, Cs + 1), -1, jnp.int32).at[d_c, p_c].set(
        (flat_e % E_local).astype(jnp.int32)
    )
    recv_x = jax.lax.all_to_all(send_x[:tp, :Cs], "tp", 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e[:tp, :Cs], "tp", 0, 0, tiled=True)

    # Local expert compute over everything received ([M, h], M = tp*Cs).
    # No second capacity bound: the buffers are already source-bounded.
    M = tp * Cs
    r_x = recv_x.reshape(M, h)
    r_e = recv_e.reshape(M)
    valid = r_e >= 0
    onehot2 = (r_e[:, None] == jnp.arange(E_local)[None, :]) & valid[:, None]
    pos2 = jnp.sum(jnp.cumsum(onehot2, axis=0) * onehot2, axis=1) - 1
    e_c2 = jnp.where(valid, r_e, E_local).astype(jnp.int32)
    p_c2 = jnp.where(valid, pos2, M).astype(jnp.int32)

    gathered = jnp.zeros((E_local + 1, M + 1, h), xl.dtype).at[e_c2, p_c2].set(r_x)
    g = gathered[:E_local, :M]
    gate = jnp.einsum("ech,ehi->eci", g, w_gate, preferred_element_type=jnp.float32)
    up = jnp.einsum("ech,ehi->eci", g, w_up, preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gate) * up).astype(xl.dtype)
    down = jnp.einsum("eci,eih->ech", act, w_down, preferred_element_type=jnp.float32)

    down_pad = jnp.pad(down, ((0, 1), (0, 1), (0, 0)))
    out_entries = down_pad[e_c2, p_c2].astype(xl.dtype)     # [M, h]
    back = jax.lax.all_to_all(
        out_entries.reshape(tp, Cs, h), "tp", 0, 0, tiled=True
    )
    back_pad = jnp.pad(back, ((0, 1), (0, 1), (0, 0)))
    entry_vals = back_pad[d_c, p_c]                         # [n*k, h]
    w_masked = jnp.where(keep, flat_w, 0.0)
    out = jnp.zeros((n, h), jnp.float32).at[flat_t].add(
        w_masked[:, None] * entry_vals.astype(jnp.float32)
    )
    return out.astype(xl.dtype)


def _moe_mlp(x, lp, cfg: ModelConfig, mesh=None):
    """Mixtral-style sparse MoE: softmax over top-k router logits, weighted
    sum of expert SwiGLUs, sparse capacity-bounded dispatch.

    Under expert parallelism (mesh given, experts sharded over the model
    axis — parallel/sharding.py), two dispatch modes
    (``cfg.moe_dispatch``):

    - ``"replicated"`` (default): every device sees all tokens, computes
      its LOCAL experts' contributions, psums over 'tp'. Activations ride
      replicated while expert weights stay resident per shard — the right
      trade on ICI at serving batch sizes (weights dominate traffic).
    - ``"alltoall"``: tokens shard over 'tp' and travel to their experts
      (``_moe_dispatch_a2a``) — the wide-EP mode for expert fleets too
      large to make every shard compute every token.
    """
    shape = x.shape
    xf = x.reshape(-1, shape[-1])  # [N, h]
    E = cfg.num_experts

    if mesh is None:
        out = _moe_dispatch_local(
            xf, lp["w_router"], lp["w_gate"], lp["w_up"], lp["w_down"],
            cfg, jnp.int32(0), E,
        )
        return out.reshape(shape)

    from jax.sharding import PartitionSpec as P

    tp = int(mesh.shape["tp"])
    E_local = E // tp

    if cfg.moe_dispatch == "alltoall":
        N = xf.shape[0]
        pad = (-N) % tp  # token axis must split evenly over 'tp'
        xp = jnp.pad(xf, ((0, pad), (0, 0)))

        def a2a_fn(xr, w_router, w_gate, w_up, w_down):
            return _moe_dispatch_a2a(
                xr, w_router, w_gate, w_up, w_down, cfg, tp, E_local
            )

        out = shard_map(
            a2a_fn,
            mesh=mesh,
            in_specs=(P("tp"), P(), P("tp"), P("tp"), P("tp")),
            out_specs=P("tp"),
            check_vma=False,
        )(xp, lp["w_router"], lp["w_gate"], lp["w_up"], lp["w_down"])
        return out[:N].reshape(shape)

    def local_fn(xr, w_router, w_gate, w_up, w_down):
        off = jax.lax.axis_index("tp") * E_local
        out = _moe_dispatch_local(xr, w_router, w_gate, w_up, w_down, cfg, off, E_local)
        return jax.lax.psum(out, "tp")

    out = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P(), P("tp"), P("tp"), P("tp")),
        out_specs=P(),
        check_vma=False,
    )(xf, lp["w_router"], lp["w_gate"], lp["w_up"], lp["w_down"])
    return out.reshape(shape)


def _logits(x: jax.Array, params: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        # Contract over h with embed kept [V, h]: dot_general reads the
        # embedding matrix in its stored layout. `embed.T` materialized a
        # 2x-param-size transposed copy EVERY decode step.
        return jax.lax.dot_general(
            x, params["embed"],
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return _dot(x, params["lm_head"])


def write_kv(cache_l, write_pages: jax.Array, write_offs: jax.Array, kvn: jax.Array):
    """Scatter this step's interleaved K/V rows ``[T, 2*n_kv, d]`` into
    one layer's pages. Plain caches write the rows as-is; quantized
    caches ({"kv", "scale"} — engine/kv_quant.py) quantize HERE, at
    block-write time, the one and only quantization a row ever sees
    (every later move — offload, onboard, transfer — copies the int8
    bytes and scales verbatim)."""
    if isinstance(cache_l, dict):
        from dynamo_tpu.engine.kv_quant import quantize_kv

        q8, sc = quantize_kv(kvn)
        return {
            "kv": cache_l["kv"].at[write_pages, write_offs].set(q8),
            "scale": cache_l["scale"].at[write_pages, write_offs].set(sc),
        }
    return cache_l.at[write_pages, write_offs].set(kvn)


def _interleave_kv(k: jax.Array, v: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[T, kv_size] x2 -> [T, 2*n_kv, d] with K at even, V at odd heads;
    (``cfg.cache_kv_heads`` of them, the spare ones zero);
    with ``cfg.kv_head_pairs`` two heads a row, ``[T, n_kv, 2 d]``: rows
    ``[k_2j | k_2j+1]`` even, ``[v_2j | v_2j+1]`` odd (a reshape: adjacent
    heads are adjacent columns)."""
    T = k.shape[0]
    n, d = cfg.num_kv_heads, cfg.head_dim
    if cfg.kv_head_pairs:
        n, d = n // 2, 2 * d
    kv = jnp.stack([k.reshape(T, n, d), v.reshape(T, n, d)], axis=2)
    spare = cfg.cache_kv_heads - cfg.num_kv_heads   # zero heads the page's tiling asks for
    if spare:
        kv = jnp.pad(kv, ((0, 0), (0, spare), (0, 0), (0, 0)))
    return kv.reshape(T, 2 * (n + spare), d)


def dense_layer(
    x: jax.Array,            # [T, h]
    lp: dict,                # ONE layer's params (leaves already indexed)
    cache_l: jax.Array,      # ONE layer's pages [n_pages, page_size, 2*n_kv, d]
    positions: jax.Array,
    write_pages: jax.Array,
    write_offs: jax.Array,
    kv_lens: jax.Array,
    block_tables: jax.Array,
    cu_q_lens: jax.Array | None,  # None: the decode shape (one row a sequence)
    num_seqs: jax.Array,
    cfg: ModelConfig,
    tp: int = 1,
    mesh=None,
    rope_cs: tuple[jax.Array, jax.Array] | None = None,
    row_valid: jax.Array | None = None,
    expert_stats: list | None = None,
    window: int | None = None,
    blocks: tuple | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One transformer block over a ragged token batch: attn-norm → fused
    qkv (→ per-head RMSNorm of q and k where the layer has
    ``q_layernorm``; over the whole projection with ``cfg.qk_norm_over``
    "projection") → rope (none where ``cfg.rope_theta`` is None) → in-place page scatter → ragged paged
    attention (KV heads in pairs where ``cfg.kv_head_pairs``) → wo →
    mlp. Shared by :func:`forward_hidden` (per-layer tuple cache) and the
    pipeline-parallel stage body (parallel/pipeline.py — stage-stacked
    cache, sliced per layer), so the layer math cannot drift. Operating
    on ONE layer's page array is also the perf contract: the Pallas
    attention call must see its own buffer, not a slice of a stacked
    tensor (see :func:`init_cache`). ``rope_cs`` carries the per-pass
    precomputed rotary tables (:func:`rope_tables`; the layer kind's own
    where kinds differ, :func:`kind_rope_tables`). The layer's query heads
    are what its ``wqkv`` holds beside the KV heads.

    ``window`` (a ``sliding_attention`` layer): ``cache_l`` is the window
    pool's array, and ``write_pages``, ``kv_lens`` and ``block_tables`` are
    the WINDOW's (:func:`split_tables`): the table's first column the page
    that holds the oldest key a query of this call may see, ``kv_lens``
    counted from that page's first token. The call then reads the window's
    pages alone (ops/ragged_attention.py, "A window"). With ``wg`` in the
    layer's leaves each head's output is gated by ``sigmoid(y wg)`` of the
    SAME normed input, inside ``o_proj`` (scope ``attn_gate``).

    ``cfg.wide_key`` (a key wider than its value, MiMo): ``wqkv`` is ``[q |
    k | v]`` at the layer kind's OWN KV heads (``cfg.kv_heads_of``) and the
    two widths, the values scaled by ``cfg.attn_value_scale`` as they are
    projected; K and V go to the page of ops/gqa_attention.py
    (:func:`write_gqa_rows`) and attention is that module's in both shapes
    and both kinds, with the layer's ``sink`` leaf where its kind has one.

    ``blocks`` (a block-diffusion model, :func:`block_rows`): the rows are
    whole diffusion blocks and each sees its block both ways beside the
    causal past, one decode-shaped call with a block's rows folded into
    the GQA group (ops/ragged_attention.py, :func:`block_attention`; scope
    ``attn/block``).

    The ``jax.named_scope`` sections (``qkv`` holding ``qk_norm``, ``kv_write``, ``attn``
    (holding ``full`` or ``window`` where a model has both),
    ``o_proj``, ``mlp``; ``embed`` and ``lm_head`` around the stack) put
    the model's own names on the device ops of a profile. They change op
    metadata only: the lowered program and its compile-cache key stay
    what they were."""
    T = x.shape[0]
    sm_scale = cfg.head_dim ** -0.5
    if rope_cs is None and cfg.rope_theta is not None:
        rope_cs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    # The dtype of what the matmuls read; ``x`` itself is wider in a
    # looped stack (:func:`_run_stack`), and the same everywhere else.
    dt = lp["attn_norm"].dtype
    with jax.named_scope("qkv"):
        # cfg.post_norm: the stream as it is; the norm is on the output
        y = x.astype(dt) if cfg.post_norm else rms_norm(
            x, lp["attn_norm"], cfg.rms_norm_eps).astype(dt)
        qkv = _dot(y, lp["wqkv"])
        if "bqkv" in lp:  # Qwen2-family qkv bias (fused column order)
            qkv = qkv + lp["bqkv"]
        if cfg.wide_key:   # [q | k | v], the kind's KV heads, v narrower and scaled
            n_kv = cfg.kv_heads_of("window" if window else "attention")
            k0 = qkv.shape[1] - n_kv * (cfg.head_dim + cfg.value_dim)
            v0 = qkv.shape[1] - n_kv * cfg.value_dim
            q, k = qkv[:, :k0].astype(dt), qkv[:, k0:v0].astype(dt)
            v = (cfg.attn_value_scale * qkv[:, v0:]).astype(dt).reshape(T, n_kv, -1)
        else:
            n_kv = cfg.num_kv_heads
            q, k, v = split_qkv(qkv.astype(dt), cfg, tp)
        whole = cfg.qk_norm_over == "projection"
        if "q_layernorm" in lp and whole:  # over the whole projection, before the heads
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, lp["q_layernorm"], cfg.rms_norm_eps)
                k = rms_norm(k, lp["k_layernorm"], cfg.rms_norm_eps)
        q = q.reshape(T, -1, cfg.head_dim)
        k = k.reshape(T, n_kv, cfg.head_dim)
        if "q_layernorm" in lp and not whole:  # per head, BEFORE rope
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, lp["q_layernorm"], cfg.rms_norm_eps)
                k = rms_norm(k, lp["k_layernorm"], cfg.rms_norm_eps)
        if rope_cs is not None:   # None: cfg.rope_theta None, no rotary embedding
            q = rope_apply(q, *rope_cs)
            k = rope_apply(k, *rope_cs)
    with jax.named_scope("kv_write"):
        if cfg.wide_key:
            cache_l = write_gqa_rows(cache_l, write_pages, write_offs, k, v)
        else:
            kvn = _interleave_kv(k.reshape(T, cfg.kv_size), v, cfg)
            cache_l = write_kv(cache_l, write_pages, write_offs, kvn)
    if isinstance(cache_l, dict):
        kv_pages, kv_scales = cache_l["kv"], cache_l["scale"]
    else:
        kv_pages, kv_scales = cache_l, None
    with jax.named_scope("attn"):
        if cfg.wide_key:
            # ops/gqa_attention.py: a kernel of its own in each shape on a TPU
            # (the chunked walk on the CPU); the sink a leaf of the kinds that have one
            with jax.named_scope("window" if window else "full"):
                kw = dict(n_kv=n_kv, sm_scale=sm_scale, window=window, sinks=lp.get("sink"))
                if cu_q_lens is None:
                    attn = gqa_decode_attention(q, kv_pages, kv_lens, block_tables, **kw)
                else:
                    attn = gqa_ragged_attention(
                        q, kv_pages, kv_lens, block_tables, cu_q_lens, num_seqs, **kw)
        elif blocks is not None:
            block_ends, block_pages, num_blocks, shape = blocks
            with jax.named_scope("block"):
                attn = block_attention(
                    q, kv_pages, block_ends, block_pages, num_blocks,
                    block_length=cfg.block_length, sm_scale=sm_scale, shape=shape,
                )
        elif cfg.windowed:
            with jax.named_scope("window" if window else "full"):
                attn = ragged_paged_attention(
                    q, kv_pages, kv_lens, block_tables, cu_q_lens, num_seqs,
                    sm_scale=sm_scale, window=window,
                    query_chunk=cfg.wave_query_chunk,
                )
        elif mesh is not None:
            attn = sharded_ragged_attention(
                mesh, q, kv_pages, kv_lens, block_tables, cu_q_lens,
                num_seqs, sm_scale=sm_scale, kv_scales=kv_scales,
            )
        elif cfg.kv_head_pairs:
            attn = paired_heads_attention(
                q, kv_pages, kv_lens, block_tables, cu_q_lens, num_seqs,
                sm_scale=sm_scale,
            )
        else:
            # The page may keep spare KV heads (cfg.cache_kv_heads: zeros the
            # library kernel's tiling asks for): the entry is told the
            # model's own count. Its first-party decode kernel reads the
            # published heads alone; the library kernel gets zero queries
            # for the spare ones there (ops/ragged_attention.py).
            attn = ragged_paged_attention(
                q, kv_pages, kv_lens, block_tables, cu_q_lens, num_seqs,
                sm_scale=sm_scale, kv_scales=kv_scales, num_kv_heads=cfg.num_kv_heads,
            )
    if "wg" in lp:
        with jax.named_scope("o_proj"), jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(_dot(y, lp["wg"]))             # [T, heads] f32
            attn = (attn.astype(jnp.float32) * gate[:, :, None]).astype(dt)
    x = _attn_out_and_mlp(
        x, attn.reshape(T, -1), lp, cfg, tp, mesh,
        row_valid=row_valid, expert_stats=expert_stats,
    )
    return x, cache_l


def split_tables(block_tables: jax.Array, cfg: ModelConfig, engine: EngineConfig):
    """A window model's block table ``[S, P + 1 + W]`` (``P =
    engine.max_blocks_per_seq``) in its three parts: the FULL pool's table
    ``[S, P]``, block ``j`` of the sequence in column ``j`` as ever; one
    column ``first`` ``[S]``, the index among the sequence's blocks of the
    one in the window table's column 0; and the WINDOW pool's table ``[S,
    W]``, block ``first + j`` of the sequence in column ``j`` (the host
    assembles the three and sends them as one array: ``EngineCore.
    _table_row``). Every other model's table is the full one: ``(tables,
    None, None)``."""
    if not cfg.windowed:
        return block_tables, None, None
    P = engine.max_blocks_per_seq
    return block_tables[:, :P], block_tables[:, P], block_tables[:, P + 1:]


def latent_layer(
    x: jax.Array,            # [T, h]
    lp: dict,                # ONE layer's params (:func:`layer_params`)
    cache_l: jax.Array,      # ONE layer's latent pages [n_pages, rows, lanes]
    write_pages: jax.Array,
    write_offs: jax.Array,
    kv_lens: jax.Array,
    block_tables: jax.Array,
    cu_q_lens: jax.Array | None,  # None: the decode shape (one row a sequence)
    num_seqs: jax.Array,
    cfg: ModelConfig,
    rope_cs: tuple[jax.Array, jax.Array],
    row_valid: jax.Array | None = None,
    expert_stats: list | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One block with latent (MLA) attention over a ragged token batch,
    :func:`dense_layer`'s counterpart: low-rank q with its norm, one
    compressed K/V vector a token with its norm beside a rope key all
    heads share, the page scatter of ``[ckv | kr]``, attention (absorbed
    in the decode shape, expanded heads for a ragged batch, both over
    the pages: ops/latent_attention.py), ``wo``, and the layer's MLP (dense, or the
    sigmoid-routed share). Scopes: ``qkv/q_proj`` (with the absorbed
    query's ``Wkvb_k``), ``qkv/kv_down``, ``kv_write``, ``attn``, ``o_proj`` (with
    ``Wkvb_v`` in the decode shape), then the MLP's."""
    T = x.shape[0]
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    sm_scale = latent_sm_scale(cfg)
    dt = lp["attn_norm"].dtype
    wk, wv = lp["wk_b"], lp["wv_b"]          # [H, dn, r], [H, r, dv]
    decode = cu_q_lens is None
    # "qkv": the section a trace's reader knows the projections before
    # attention by (the dense layer's fused one); the two parts inside it.
    with jax.named_scope("qkv"), jax.named_scope("q_proj"):
        y = rms_norm(x, lp["attn_norm"], eps).astype(dt)
        cq = rms_norm(_dot(y, lp["wq_a"]).astype(dt), lp["q_norm"], eps)
        q = _dot(cq, lp["wq_b"]).astype(dt).reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], rope_apply(q[..., dn:], *rope_cs)
        if decode:
            q_lat = jnp.einsum(
                "bhd,hdr->bhr", q_nope, wk, preferred_element_type=jnp.float32
            ).astype(dt)
    with jax.named_scope("qkv"), jax.named_scope("kv_down"):
        kva = _dot(y, lp["wkv_a"]).astype(dt)
        ckv = rms_norm(kva[:, :r], lp["kv_norm"], eps)
        kr = rope_apply(kva[:, None, r:], *rope_cs)[:, 0]
    with jax.named_scope("kv_write"):
        cache_l = write_latent_rows(cache_l, write_pages, write_offs, ckv, kr)
    if decode:
        with jax.named_scope("attn"):
            o_lat = latent_decode_attention(
                q_lat, q_rope, cache_l, kv_lens, block_tables, sm_scale=sm_scale
            )
        with jax.named_scope("o_proj"):
            attn = jnp.einsum(
                "bhr,hrd->bhd", o_lat, wv, preferred_element_type=jnp.float32
            ).astype(dt)
    else:
        with jax.named_scope("attn"):
            attn = latent_ragged_attention(
                q_nope, q_rope, wk, wv, cache_l, kv_lens, block_tables,
                cu_q_lens, num_seqs, sm_scale=sm_scale,
            )
    x = _attn_out_and_mlp(
        x, attn.reshape(T, H * dv), lp, cfg, 1, None,
        row_valid=row_valid, expert_stats=expert_stats,
    )
    return x, cache_l


def _operator_scope(operator: str, stage: str, part: str) -> contextlib.ExitStack:
    """``stage/operator/part`` as nested ``jax.named_scope``s. ``stage``: the
    section a trace's reader knows this stage of a block by
    (chipbench/trace/phases.py lists the dense layer's); ``<operator>/<part>``
    inside it: the operator's own names (``conv``, ``linear``)."""
    stack = contextlib.ExitStack()
    for name in (stage, operator, part):
        stack.enter_context(jax.named_scope(name))
    return stack


def conv_state_rows(state_l, block_tables, pos, slots: int, block_size: int):
    """The cached ``u`` at positions ``pos`` ``[S, m]`` of sequences whose
    blocks are ``block_tables`` ``[S, pages]``: ``[S, m, h]``, zero before
    position 0. Position ``q`` lies in slot ``q % slots`` of the page of
    block ``q // block_size``."""
    q = jnp.maximum(pos, 0)
    page = jnp.take_along_axis(block_tables, q // block_size, axis=1)
    rows = state_l[page, q % slots]                       # [S, m, h/128, 128]
    rows = rows.reshape(*pos.shape, -1)
    return jnp.where((pos >= 0)[..., None], rows, jnp.zeros((), rows.dtype))


def conv_layer(
    x: jax.Array,            # [T, h]
    lp: dict,                # ONE conv layer's params (:func:`layer_params`)
    state_l: jax.Array,      # ONE layer's state pages [n_pages, L-1, h/128, 128]
    positions: jax.Array,
    write_pages: jax.Array,
    block_tables: jax.Array,
    cu_q_lens: jax.Array | None,  # None: the decode shape (one row a sequence)
    cfg: ModelConfig,
    engine: EngineConfig,
    row_valid: jax.Array | None = None,
    expert_stats: list | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One block whose operator is a gated short convolution, over a
    ragged token batch: ``[B, C, z] = norm(x) W_in``; ``u = B * z``; ``c_t
    = sum_j w_j u_{t - (L-1-j)}`` per channel (depthwise, causal, ``u``
    zero before position 0); ``x + (C * c) W_out``; then the layer's MLP.
    ``L = cfg.conv_L_cache`` taps.

    **The state.** What a token leaves behind is ``u`` at its position;
    what the next needs is ``u`` at the ``L - 1`` positions before it,
    whatever the context. It lives in PAGES indexed by the same block
    ids as an attention layer's K/V (:func:`cache_for_blocks`): position
    ``p`` is written to slot ``p % (L-1)`` of the page of ITS block, so a
    page holds the ``L - 1`` newest rows written in that block, and a
    token at the first offsets of a block reads the previous block's
    page through the block table. Within a chunk, rows come from the
    chunk itself (``u`` shifted along ``T``), and each sequence's first
    ``L - 1`` rows from the pages; the decode shape is one gather, ``L``
    multiply-adds and one scatter a lane. ``u`` is rounded to the model
    dtype before it is used OR cached, so a row's arithmetic does not
    depend on where a chunk was cut. Only the last ``L - 1`` rows of
    each (chunk, block) are written (the others write the garbage page):
    ONE scatter a layer a step with no (page, slot) written twice.

    A block that is full under a sequence's cursor holds, for ever, the
    state at its end: a pure function of the tokens up to there, as its
    K/V is. So a prefix hit (always whole blocks), a resume after
    preemption and a prompt's next chunk find their state where the
    allocator and the prefix index already point, with no snapshot.

    **The invariant** (pinned by tests/test_lfm2.py). K/V written past a
    sequence's cursor is never attended; state written past it IS read
    if the sequence goes on from the cursor, because slot ``p % (L-1)``
    of a partial block then no longer holds ``u_{p - (L-1)}``. So: **a
    sequence that continues must never have written a position past the
    cursor it continues from**. Every such write the engine makes is by
    a sequence that then ENDS (a megastep's iterations after the host
    finds a stop the device could not see; the one-step-ahead dispatch
    of a lane that ended in the step before), or goes to the garbage
    page (padding rows, a megastep's iterations of a lane the device saw
    stop: ``active`` false), or is discarded WITH its blocks (a lane
    preempted while its step was in flight restarts from full, hashed
    blocks, which no later position writes). Speculative decoding
    rejects rows it has written and goes on: it is refused for a model
    with conv layers (options._refuse_uncarried_options).

    Scopes: ``conv/in_proj``, ``conv/state`` (the gather and the scatter
    of state rows), ``conv/mix`` (the gates and the taps),
    ``conv/out_proj``, each inside the section of the dense layer's stage
    it stands for (``qkv``, ``kv_write``, ``attn``, ``o_proj``), then the
    MLP's."""
    T, h = x.shape
    K = cfg.conv_L_cache
    n, bs = K - 1, engine.block_size
    dt = lp["attn_norm"].dtype
    decode = cu_q_lens is None

    scope = functools.partial(_operator_scope, "conv")

    with scope("qkv", "in_proj"):
        y = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(dt)
        bcz = _dot(y, lp["in_proj"]).astype(dt)
    with scope("attn", "mix"):
        gate_b, gate_c, z = jnp.split(bcz, 3, axis=-1)
        u = gate_b * z                                           # [T, h], dt
    with scope("kv_write", "state"):
        back = jnp.arange(1, n + 1, dtype=jnp.int32)[None, :]    # 1 .. L-1 back
        if decode:
            st = conv_state_rows(state_l, block_tables, positions[:, None] - back, n, bs)
            write = jnp.ones((T,), bool)
        else:
            starts, ends = cu_q_lens[:-1], cu_q_lens[1:]
            q_len = ends - starts
            start_pos = positions[jnp.minimum(starts, T - 1)]
            st = conv_state_rows(state_l, block_tables, start_pos[:, None] - back, n, bs)
            # rows that end a chunk or a block: the newest of their page
            tail = jnp.where(back - 1 < q_len[:, None], ends[:, None] - back, T)
            write = jnp.zeros((T,), bool).at[tail.reshape(-1)].set(True, mode="drop")
            write = write | (positions % bs >= bs - n)
    with scope("attn", "mix"):
        w = lp["conv_w"].astype(jnp.float32)                     # [L, h]
        c = w[K - 1] * u.astype(jnp.float32)
        for j in range(1, K):                                    # u, j rows back
            if decode:
                prev = st[:, j - 1]
            else:
                prev = jnp.roll(u, j, axis=0)
                for i in range(j):   # row i of a chunk: from the pages
                    at = jnp.where(i < q_len, starts + i, T)
                    prev = prev.at[at].set(st[:, j - i - 1], mode="drop")
            c = c + w[K - 1 - j] * prev.astype(jnp.float32)
        g = (gate_c.astype(jnp.float32) * c).astype(dt)
    with scope("kv_write", "state"):
        page = jnp.where(write, write_pages, engine.garbage_block)
        state_l = state_l.at[page, positions % n].set(u.reshape(T, *state_l.shape[2:]))
    with scope("o_proj", "out_proj"):
        x = x + _dot(g, lp["out_proj"]).astype(x.dtype)
    x = _residual_mlp(x, lp, cfg, 1, None, row_valid, expert_stats)
    return x, state_l


def split_slots(block_tables: jax.Array, cfg: ModelConfig, engine: EngineConfig):
    """A model with slab layers (linear or mamba) sends each sequence's lane
    SLOT as one more column of its block table, ``[S, P + 1]`` (``P =
    engine.max_blocks_per_seq``; ``EngineCore._table_row``): ``(tables [S,
    P], slots [S])``. Every other model: ``(tables, None)``."""
    if not cfg.has_slab:
        return block_tables, None
    P = engine.max_blocks_per_seq
    return block_tables[:, :P], block_tables[:, P]


def _slab_conv(pre, rows, slots, positions, write_pages, cu_q_lens, w, bias,
               engine: EngineConfig):
    """The depthwise causal convolution of a slab layer (linear or mamba) over
    ``pre [T, channels]`` (the model dtype), then SiLU: ``silu(bias + sum_j w_j
    pre_{t-K+1+j})``, zeros before position 0. ``rows [slots, K - 1, channels
    / 128, 128]``: the slab's newest input rows a lane slot, oldest first;
    ``w [K, channels]``, ``bias [channels]`` or None. A sequence's first
    row (position 0) and every row of the garbage slot read zeros by a
    select; a dead decode lane (its K/V row goes to the garbage page) is sent
    to the garbage slot. Returns ``(c [T, channels] float32, rows, slots,
    fresh [S])`` with ``slots`` and ``fresh`` as the state's update must use
    them."""
    T, K = pre.shape[0], w.shape[0]
    decode = cu_q_lens is None
    garbage = rows.shape[0] - 1
    if decode:   # a dead lane (active false: its K/V row goes to the garbage page)
        slots = jnp.where(write_pages == engine.garbage_block, garbage, slots)
        start_pos, q_len = positions, None
    else:
        starts, ends = cu_q_lens[:-1], cu_q_lens[1:]
        q_len = ends - starts
        start_pos = positions[jnp.minimum(starts, T - 1)]
    fresh = (start_pos == 0) | (slots == garbage)
    old = linear_attention.zero_where_fresh(rows[slots], fresh)
    old = old.reshape(old.shape[0], K - 1, -1)                # [S, K-1, channels]
    w = w.astype(jnp.float32)                                 # [K, channels]
    c = w[K - 1] * pre.astype(jnp.float32)
    for j in range(1, K):                                     # the input, j rows back
        if decode:
            prev = old[:, K - 1 - j]
        else:
            prev = jnp.roll(pre, j, axis=0)
            for i in range(j):   # row i of a sequence's chunk: from the slab
                at = jnp.where(i < q_len, starts + i, T)
                prev = prev.at[at].set(old[:, K - 1 + i - j], mode="drop")
        c = c + w[K - 1 - j] * prev.astype(jnp.float32)
    if bias is not None:
        c = c + bias.astype(jnp.float32)
    c = jax.nn.silu(c)
    if decode:
        new = jnp.concatenate([old[:, 1:], pre[:, None]], axis=1)
    else:   # the K - 1 newest rows at the chunk's end: the chunk's, else the slab's
        m = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        r = q_len[:, None] - (K - 1) + m                      # chunk row of new row m
        mine = pre[jnp.clip(starts[:, None] + r, 0, T - 1)]
        kept = jnp.take_along_axis(
            old, jnp.clip(m + q_len[:, None], 0, K - 2)[..., None], axis=1)
        new = jnp.where((r >= 0)[..., None], mine, kept)
    rows = rows.at[slots].set(new.reshape(new.shape[0], *rows.shape[1:]))
    return c, rows, slots, fresh


def linear_layer(
    x: jax.Array,            # [T, h]
    lp: dict,                # ONE linear layer's params (:func:`layer_params`)
    slab: dict,              # ONE layer's slab {"state", "conv"} (cache_for_blocks)
    positions: jax.Array,
    write_pages: jax.Array,
    slots: jax.Array,        # [S] i32: each sequence's lane slot
    cu_q_lens: jax.Array | None,  # None: the decode shape (one row a sequence)
    cfg: ModelConfig,
    engine: EngineConfig,
) -> tuple[jax.Array, dict]:
    """One block whose mixer is a gated delta rule, over a ragged token
    batch (``H`` heads, keys ``dk`` and values ``dv`` wide, ``K`` taps):
    ``[q~ | k~ | v~] = x W_qkv``; a depthwise causal convolution of ``K`` taps
    over those channels (zero before position 0), then SiLU; ``q = q / |q|
    dk^-1/2``, ``k = k / |k|`` per head; ``beta = 2 sigmoid(x W_b)`` (the
    factor 2 with ``cfg.linear_allow_neg_eigval``), ``alpha = exp(-exp(A_log)
    softplus(x W_a + dt_bias))`` per head in float32; the state's recurrence
    and its read-out ``o`` (ops/linear_attention.py); ``y = norm_dv(o) *
    silu(x W_z)`` per head; ``x + norm(y W_out)``, then the layer's MLP. The
    mixer reads the stream AS IT IS and its output is normed
    (``cfg.post_norm``).

    **The state** lives in the layer's SLAB, indexed by a lane slot a
    sequence holds from admission to its end (``slots``; the last slot is
    the garbage slot): ``state [slots, H / p, dk, p dv]`` float32 (``p``
    heads side by side in a tile: ops/linear_attention.py, "The slab") and
    ``conv [slots, K - 1, channels / 128, 128]``, the convolution's newest input
    rows, oldest first, rounded to the model dtype before they are used OR
    kept, so that a row's arithmetic does not depend on where a chunk was
    cut. A sequence's first row (position 0) reads zeros whatever its slot
    held, by a select; so does every row of the garbage slot (padding, a
    dead lane). No block holds a state: a prefix hit cannot find one
    (``prefix_caching`` is refused for such a model) and a preempted
    sequence replays from position 0 into a fresh slot.

    **The invariant is :func:`conv_layer`'s**: the state is updated IN
    PLACE, so a sequence that goes on from a cursor must never have written
    a position past it. Every such write the engine makes is by a sequence
    that then ENDS (a megastep's iterations after the host finds a stop the
    device could not see; the one-step-ahead dispatch of a lane that ended
    in the step before: its slot is given to no one before that dispatch
    was enqueued, and whoever takes it next starts at position 0 and reads
    zeros), or goes to the garbage slot (padding rows, a megastep's
    iterations of a lane the device saw stop: ``active`` false), or is
    discarded WITH the slot (a lane preempted while its step was in flight
    restarts from position 0). Speculative decoding rejects rows it has
    written and goes on: refused (options._refuse_uncarried_options).

    Scopes, each inside the dense layer's stage it stands for: ``qkv`` >
    ``linear/in_proj``, ``kv_write`` > ``linear/conv``, ``attn`` >
    ``linear/state_step`` (decode) or ``linear/state_scan`` (ragged),
    ``attn`` > ``linear/gate_norm``, ``o_proj`` > ``linear/out_proj``, then
    the MLP's."""
    T = x.shape[0]
    H, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    dt = lp["attn_norm"].dtype
    decode = cu_q_lens is None
    state, rows = slab["state"], slab["conv"]

    scope = functools.partial(_operator_scope, "linear")

    with scope("qkv", "in_proj"):
        y = x.astype(dt)
        pre = _dot(y, lp["w_qkv"]).astype(dt)                     # [T, channels]
        z = _dot(y, lp["w_z"])                                    # [T, H dv] f32
        ba = _dot(y, lp["w_ba"])                                  # [T, 2 H] f32
        beta = jax.nn.sigmoid(ba[:, :H]) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
        g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, H:] + lp["dt_bias"])   # log alpha
    with scope("kv_write", "conv"):
        c, rows, slots, fresh = _slab_conv(
            pre, rows, slots, positions, write_pages, cu_q_lens, lp["conv_w"], None, engine)
        q = linear_attention.l2_normalize(c[:, :H * dk].reshape(T, H, dk), 1e-6) * dk ** -0.5
        k = linear_attention.l2_normalize(c[:, H * dk:2 * H * dk].reshape(T, H, dk), 1e-6)
        v = c[:, 2 * H * dk:].reshape(T, H, dv)
    if decode:
        with scope("attn", "state_step"):
            o, state = linear_attention.gdn_step(
                state, slots, q, k, v, jnp.exp(g), beta, fresh)
    else:
        with scope("attn", "state_scan"):
            o, state = linear_attention.gdn_scan(
                state, slots, fresh, q, k, v, g, beta, cu_q_lens)
    with scope("attn", "gate_norm"):
        o = rms_norm(o, lp["o_norm"].astype(jnp.float32), cfg.rms_norm_eps)
        # (the gate stays flat as its product gives it: gated per head in [T, H, dv]
        # the v5e's compiler re-lays W_z out, all layers of it, at every dispatch)
        gated = (o.reshape(T, H * dv) * jax.nn.silu(z)).astype(dt)
    with scope("o_proj", "out_proj"):
        a = rms_norm(_dot(gated, lp["w_out"]).astype(x.dtype), lp["attn_norm"], cfg.rms_norm_eps)
        x = x + a
    x = _residual_mlp(x, lp, cfg, 1, None)
    return x, {"state": state, "conv": rows}


def ssm_layer(
    x: jax.Array,            # [T, h]
    lp: dict,                # ONE mamba layer's params (:func:`layer_params`)
    slab: dict,              # ONE layer's slab {"state", "conv"} (cache_for_blocks)
    positions: jax.Array,
    write_pages: jax.Array,
    slots: jax.Array,        # [S] i32: each sequence's lane slot
    cu_q_lens: jax.Array | None,  # None: the decode shape (one row a sequence)
    cfg: ModelConfig,
    engine: EngineConfig,
) -> tuple[jax.Array, dict]:
    """One block that is a Mamba-2 mixer ALONE, ``x + mixer(norm(x))``, over a
    ragged token batch (``H`` heads of ``P`` channels, ``d_in = H P``, a state
    ``N`` wide, ``G`` groups, ``K`` taps): ``[z | xBC] = u W_zx`` and ``dt~ = u
    W_dt`` (the published ``in_proj``, split: :func:`_init_ssm_operators`); a
    depthwise causal convolution of ``K`` taps with a bias over ``xBC``, then
    SiLU (:func:`_slab_conv`); ``xBC`` split into ``x [H, P]``, ``B [G, N]``,
    ``C [G, N]``; ``dt = softplus(dt~ + dt_bias)`` per head in float32, NOT
    clamped, ``log a = -exp(A_log) dt``; the state's recurrence and its
    read-out ``y = S C + D x`` (ops/ssm.py); ``y * silu(z)``, THEN an RMSNorm
    over each group of ``d_in / G`` channels under one weight ``[d_in]`` (the
    gate before the norm); ``W_out``. No MLP: the feed-forward is a block of
    its own (:func:`mlp_block`).

    **The state** lives in the layer's SLAB exactly as :func:`linear_layer`'s
    does (``state [slots, H, P, N]`` float32, ``conv [slots, K - 1, channels /
    128, 128]``), under the same invariant: nothing written past a cursor is
    read by a sequence that goes on; a fresh or garbage slot reads zeros by a
    select.

    Scopes, each inside the dense layer's stage it stands for: ``qkv`` >
    ``ssm/in_proj``, ``kv_write`` > ``ssm/conv``, ``attn`` > ``ssm/ssd_step``
    (decode) or ``ssm/ssd_scan`` (ragged), ``attn`` > ``ssm/gate_norm``,
    ``o_proj`` > ``ssm/out_proj``."""
    T = x.shape[0]
    H, P, N, G = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_n_groups
    d_in = H * P
    dt = lp["attn_norm"].dtype
    state, rows = slab["state"], slab["conv"]

    scope = functools.partial(_operator_scope, "ssm")

    with scope("qkv", "in_proj"):
        u = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(dt)
        zx = _dot(u, lp["w_zx"])                                  # [T, d_in + channels] f32
        z, pre = zx[:, :d_in], zx[:, d_in:].astype(dt)
        step = jax.nn.softplus(_dot(u, lp["w_dt"]) + lp["dt_bias"])   # [T, H] f32
        la = -jnp.exp(lp["A_log"]) * step                         # log a
    with scope("kv_write", "conv"):
        c, rows, slots, fresh = _slab_conv(
            pre, rows, slots, positions, write_pages, cu_q_lens, lp["conv_w"],
            lp.get("conv_b"), engine)
        xs = c[:, :d_in].reshape(T, H, P)
        Bm = c[:, d_in:d_in + G * N].reshape(T, G, N)
        Cm = c[:, d_in + G * N:].reshape(T, G, N)
    if cu_q_lens is None:
        with scope("attn", "ssd_step"):
            y, state = ssm.ssd_step(state, slots, xs, step, jnp.exp(la), Bm, Cm, lp["D"], fresh)
    else:
        with scope("attn", "ssd_scan"):
            y, state = ssm.ssd_scan(state, slots, fresh, xs, step, la, Bm, Cm, lp["D"],
                                    cu_q_lens, chunk=cfg.ssm_chunk_size)
    with scope("attn", "gate_norm"):
        gated = (y.reshape(T, d_in) * jax.nn.silu(z)).reshape(T, G, d_in // G)
        gated = rms_norm(gated, lp["ssm_norm"].astype(jnp.float32).reshape(G, d_in // G),
                         cfg.rms_norm_eps).reshape(T, d_in).astype(dt)
    with scope("o_proj", "out_proj"):
        x = x + _dot(gated, lp["w_out"]).astype(x.dtype)
    return x, {"state": state, "conv": rows}


def _attn_out_and_mlp(x, attn, lp, cfg: ModelConfig, tp: int, mesh,
                      row_valid=None, expert_stats: list | None = None):
    """The block after attention: ``x + attn Wo``, then ``x + mlp(norm x)``.
    With ``cfg.sandwich_norm`` each sub-layer's OUTPUT is normed once more
    before its residual add (``attn_post_norm`` / ``mlp_post_norm``),
    inside that sub-layer's scope. ``row_valid`` and ``expert_stats`` are
    the sigmoid-routed MLP's (:func:`_shared_sparse_mlp`)."""
    with jax.named_scope("o_proj"):
        a = _dot(attn, lp["wo"]).astype(x.dtype)
        if cfg.sandwich_norm:
            a = rms_norm(a, lp["attn_post_norm"], cfg.rms_norm_eps)
        if cfg.post_norm:
            a = rms_norm(a, lp["attn_norm"], cfg.rms_norm_eps)
        x = x + a
    if cfg.single_sublayer:   # the block IS its mixer: the feed-forward is a block of its own
        return x
    return _residual_mlp(x, lp, cfg, tp, mesh, row_valid, expert_stats)


def _residual_mlp(x, lp, cfg: ModelConfig, tp: int, mesh,
                  row_valid=None, expert_stats: list | None = None):
    """``x + mlp(norm x)``, the second half of every block (scope ``mlp``);
    with ``cfg.post_norm`` ``x + norm(mlp(x))``, the one norm on the output."""
    with jax.named_scope("mlp"):
        dt = lp["mlp_norm"].dtype
        y = x.astype(dt) if cfg.post_norm else rms_norm(
            x, lp["mlp_norm"], cfg.rms_norm_eps).astype(dt)
        m = _mlp(y, lp, cfg, tp, mesh, row_valid, expert_stats)
        if cfg.sandwich_norm:
            m = rms_norm(m, lp["mlp_post_norm"], cfg.rms_norm_eps)
        if cfg.post_norm:
            m = rms_norm(m.astype(x.dtype), lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + m
    return x


def mlp_block(x, lp, cfg: ModelConfig, row_valid=None, expert_stats: list | None = None):
    """A block that is a feed-forward ALONE (``cfg.single_sublayer``, layer
    kind "moe"): ``x + mlp(norm x)`` under the block's one norm
    (``attn_norm``), scope ``mlp`` as the second half of a two-sub-layer block
    has it. It reads no position and caches nothing."""
    with jax.named_scope("mlp"):
        dt = lp["attn_norm"].dtype
        y = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(dt)
        return x + _shared_sparse_mlp(y, lp, cfg, row_valid, expert_stats)


# -- the unified forward ----------------------------------------------------

def forward_tokens(
    params: Params,
    cache: tuple,            # L x [n_pages, page_size, 2*n_kv, d] (donated)
    tokens: jax.Array,       # [T] i32 — all scheduled tokens, ragged-concat
    positions: jax.Array,    # [T] i32 — absolute position of each token
    write_pages: jax.Array,  # [T] i32 — destination page (garbage for pads)
    write_offs: jax.Array,   # [T] i32 — destination offset within page
    kv_lens: jax.Array,      # [S] i32 — cache tokens per seq incl. this chunk
    block_tables: jax.Array, # [S, pages_per_seq] i32
    cu_q_lens: jax.Array | None,  # [S+1] i32; None: the decode shape
    num_seqs: jax.Array,     # [1] i32
    last_rows: jax.Array,    # [S] i32 — row of each seq's last token (0 pad)
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh=None,
    mm_embeds=None,          # [T, h] — multimodal rows (override where mask)
    mm_mask=None,            # [T] bool
    expert_stats: list | None = None,
    block_shape: str = "block-ragged",
) -> tuple[jax.Array, jax.Array]:
    """One step over every scheduled token. Returns (last-token logits
    [S, vocab] f32, cache). Prefill chunks, decode tokens, and mixed
    batches are all this function — a decode step is S sequences of
    q_len 1 (reference chunked-prefill semantics, vLLM scheduler shape).
    ``cu_q_lens=None`` says that shape statically (T == S, row ``s`` is
    sequence ``s``): attention then runs its decode path
    (ops/ragged_attention.py), the same result from less work.
    """
    x, cache = forward_hidden(
        params, cache, tokens, positions, write_pages, write_offs,
        kv_lens, block_tables, cu_q_lens, num_seqs, cfg, engine, mesh,
        mm_embeds=mm_embeds, mm_mask=mm_mask, expert_stats=expert_stats,
        block_shape=block_shape,
    )
    with jax.named_scope("lm_head"):
        last = x[last_rows]  # [S, h]
        return _logits(last, params, cfg), cache


def forward_hidden(
    params: Params,
    cache: jax.Array,
    tokens: jax.Array,
    positions: jax.Array,
    write_pages: jax.Array,
    write_offs: jax.Array,
    kv_lens: jax.Array,
    block_tables: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh=None,
    mm_embeds=None,
    mm_mask=None,
    want_gates: bool = False,
    expert_stats: list | None = None,
    block_shape: str = "block-ragged",
) -> tuple[jax.Array, jax.Array]:
    """The transformer stack up to the final norm: returns (hidden states
    [T, h], cache), and with ``want_gates`` (looped models) the exit
    gate's probability after every pass, ``[ut_steps, T]``, as a third.
    Shared by the logits path (:func:`forward_tokens`)
    and the embeddings path (reference serves /v1/embeddings through its
    engines, http/service/service_v2.rs:277-336).

    ``mm_embeds``/``mm_mask`` (a separately-compiled prefill variant)
    override the token-embedding rows at multimodal placeholder
    positions with encoder output (llm/multimodal.py).

    ``expert_stats`` (a list, the sigmoid-routed MLP's): every sparse
    layer appends its int32 ``[4]`` count (:func:`_shared_sparse_mlp`) at
    trace time, for the caller to sum inside the same trace."""
    tp = int(mesh.shape["tp"]) if mesh is not None else 1
    with jax.named_scope("embed"):
        x = params["embed"][tokens]  # [T, h]
        if mm_embeds is not None:
            x = jnp.where(mm_mask[:, None], mm_embeds.astype(x.dtype), x)
        if cfg.latent:
            rope_cs = latent_rope_tables(positions, cfg)
        elif cfg.windowed or cfg.rope_theta is None:
            rope_cs = None   # a pair of tables a layer kind, below; or no rope at all
        else:
            rope_cs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        # Padding rows (and a megastep's dead lanes) write the garbage
        # page: they route to no expert and are not counted.
        row_valid = write_pages != engine.garbage_block if cfg.shared_sparse else None
    blocks = None
    if cfg.block_length:
        with jax.named_scope("attn"), jax.named_scope("block"):
            blocks = (*block_rows(positions, block_tables, cu_q_lens, num_seqs,
                                  cfg.block_length), block_shape)
    block_tables, win_first, win_tables = split_tables(block_tables, cfg, engine)
    block_tables, slots = split_slots(block_tables, cfg, engine)
    if win_tables is not None:
        with jax.named_scope("qkv"):
            rope_cs = {kind: kind_rope_tables(positions, cfg, kind)
                       for kind in dict.fromkeys(cfg.layer_types)}
        with jax.named_scope("kv_write"):
            # Where each row's K/V lands in the WINDOW pool: the page of its
            # position's block, counted from the window table's first; a
            # row that writes the full pool's garbage page writes this
            # pool's (its last page).
            T, bs = tokens.shape[0], engine.block_size
            if cu_q_lens is None:
                seq_of = jnp.arange(T, dtype=jnp.int32)
            else:
                seq_of = jnp.minimum(
                    jnp.sum(jnp.arange(T, dtype=jnp.int32)[:, None] >= cu_q_lens[None, 1:],
                            axis=1), win_tables.shape[0] - 1).astype(jnp.int32)
            column = jnp.clip(positions // bs - win_first[seq_of], 0, win_tables.shape[1] - 1)
            win_write = jnp.where(write_pages == engine.garbage_block,
                                  engine.num_window_blocks, win_tables[seq_of, column])
            win_kv_lens = kv_lens - bs * win_first

    def layer(x, lp, cache_l, write_pages, block_tables, l):
        if cfg.windowed:  # full and window attention layers, a pool each
            window = cfg.sliding_window if cfg.layer_kind(l) == "window" else None
            return dense_layer(
                x, lp, cache_l, positions,
                win_write if window else write_pages, write_offs,
                win_kv_lens if window else kv_lens,
                win_tables if window else block_tables, cu_q_lens, num_seqs, cfg,
                tp=tp, mesh=mesh, rope_cs=rope_cs[cfg.layer_types[l]],
                row_valid=row_valid, expert_stats=expert_stats, window=window,
            )
        if "w_zx" in lp:   # a mamba layer's leaves (cfg.layer_types)
            return ssm_layer(
                x, lp, cache_l, positions, write_pages, slots, cu_q_lens, cfg, engine)
        if cfg.single_sublayer and "w_router" in lp:   # a feed-forward block: no cache entry
            return mlp_block(x, lp, cfg, row_valid, expert_stats), cache_l
        if "A_log" in lp:  # a linear layer's leaves (cfg.layer_types)
            return linear_layer(
                x, lp, cache_l, positions, write_pages, slots, cu_q_lens, cfg, engine)
        if "in_proj" in lp:  # a conv layer's leaves (cfg.layer_types)
            return conv_layer(
                x, lp, cache_l, positions, write_pages, block_tables,
                cu_q_lens, cfg, engine,
                row_valid=row_valid, expert_stats=expert_stats,
            )
        if cfg.latent:
            return latent_layer(
                x, lp, cache_l, write_pages, write_offs, kv_lens,
                block_tables, cu_q_lens, num_seqs, cfg, rope_cs,
                row_valid=row_valid, expert_stats=expert_stats,
            )
        return dense_layer(
            x, lp, cache_l, positions, write_pages, write_offs,
            kv_lens, block_tables, cu_q_lens, num_seqs, cfg,
            tp=tp, mesh=mesh, rope_cs=rope_cs,
            row_valid=row_valid, expert_stats=expert_stats, blocks=blocks,
        )

    return _run_stack(
        params, cache, x, write_pages, block_tables, layer, cfg,
        want_gates=want_gates,
    )


def _run_stack(
    params, cache, x, write_pages, block_tables, layer, cfg: ModelConfig,
    want_gates: bool = False,
):
    """The layer stack and the final norm over ``x`` ``[T, h]``: returns
    (normed hidden states, cache). ``layer(x, lp, cache_l, write_pages,
    block_tables, l) -> (x, cache_l)`` is block ``l`` on its layer's pages.

    A single-pass model runs the unrolled layers once, then the final
    norm (scope ``lm_head``, which it alone feeds). A looped model
    (``cfg.ut_steps > 1``) runs the SAME unrolled body ``ut_steps`` times
    under one ``lax.fori_loop`` whose carry is ``(x, caches)`` — the
    megastep's scan carries the caches the same way — each pass ending in
    the final norm (scope ``loop_norm``): its output feeds the next pass,
    and the last pass's the ``lm_head``. Pass ``u`` works on plane ``u``
    of every layer's pages: the page ids it writes and the block tables
    it reads are offset by ``u x pages-per-plane``, a garbage page id
    landing on that plane's own garbage page.

    The body holds no test on the loop's index. Norming at the START of
    every pass but the first (``lax.cond(u > 0, ...)`` or ``jnp.where``)
    is the same mathematics and gave other logits on the v5e, not on the
    CPU (PERF.md, PR 27: the predicate on the induction variable is
    compiled as if always true); norming at the end needs none.

    ``want_gates`` (looped models; tests and offline use, no serving
    program) also returns the exit gate's probability after every pass,
    ``[ut_steps, T]``."""
    def one_pass(x, caches, write_pages, block_tables):
        caches = list(caches)
        for l in range(cfg.num_layers):
            x, caches[l] = layer(
                x, layer_params(params, l, cfg), caches[l], write_pages, block_tables, l
            )
        return x, tuple(caches)

    def final_norm(x):
        return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    if cfg.ut_steps == 1:
        x, cache = one_pass(x, cache, write_pages, block_tables)
        with jax.named_scope("lm_head"):
            return final_norm(x), cache

    plane = cache_pages(cache[0]) // cfg.ut_steps

    def body(u, carry):
        x, caches, *gates = carry
        off = u * plane
        x, caches = one_pass(
            x, caches, write_pages + off,
            None if block_tables is None else block_tables + off,
        )
        with jax.named_scope("loop_norm"):
            x = final_norm(x)
        if want_gates:
            gates = [gates[0].at[u].set(exit_gate_probs(params, x))]
        return (x, caches, *gates)

    # The residual stream and the norm between passes stay in float32:
    # what one pass rounds off, the next ones work on again.
    dt = x.dtype
    gates = (jnp.zeros((cfg.ut_steps, x.shape[0]), jnp.float32),) if want_gates else ()
    x, cache, *gates = jax.lax.fori_loop(
        0, cfg.ut_steps, body, (x.astype(jnp.float32), tuple(cache), *gates)
    )
    return (x.astype(dt), cache, *gates)


def cache_pages(cache_l) -> int:
    """Pages in one layer's array (plain, or int8 {"kv", "scale"})."""
    return (cache_l["kv"] if isinstance(cache_l, dict) else cache_l).shape[0]


def exit_gate_probs(params: Params, hidden: jax.Array) -> jax.Array:
    """``sigmoid(w . h + b)`` of the looped model's exit gate on normed
    hidden states ``[..., h]``, float32. The serving programs never
    evaluate it: at ``early_exit_threshold`` 1 it decides nothing."""
    g = params["exit_gate"]
    z = jnp.einsum(
        "...h,h->...", hidden.astype(jnp.float32), g["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return jax.nn.sigmoid(z + g["b"].astype(jnp.float32))


def forward_ring_prefill(
    params: Params,
    cache: tuple,            # per-layer paged cache (donated)
    tokens: jax.Array,       # [T] i32, ONE prompt, bucket-padded
    write_pages: jax.Array,  # [T] i32 (garbage page for pad rows)
    write_offs: jax.Array,   # [T] i32
    last_row: jax.Array,     # [] i32 — index of the prompt's last token
    cfg: ModelConfig,
    engine: EngineConfig,
    sp_mesh,
    axis_name: str = "sp",
) -> tuple[jax.Array, jax.Array]:
    """Sequence-parallel long-context prefill: ONE long prompt, hidden
    states computed densely with ring attention over the ``sp`` mesh axis
    (K/V chunks rotate over ICI via ppermute — ops/ring_attention.py)
    while each token's K/V is also written into the paged cache, so
    decode continues on the normal paged path. Returns (last-token logits
    [1, vocab] f32, cache).

    The reference has no sequence parallelism at all (SURVEY.md §2.6
    "ABSENT"); this is the TPU-native long-context prefill the project
    brief calls first-class. Causal masking makes bucket padding safe:
    pad rows sit AFTER the last real token, so no real row attends them.
    """
    from dynamo_tpu.ops.ring_attention import ring_attention

    T = tokens.shape[0]
    with jax.named_scope("embed"):
        positions = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"][tokens]  # [T, h]
        rope_cs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)

    def layer(x, lp, cache_l, write_pages, _tables, _l):
        with jax.named_scope("qkv"):
            dt = lp["attn_norm"].dtype
            y = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(dt)
            qkv = _dot(y, lp["wqkv"])
            if "bqkv" in lp:
                qkv = qkv + lp["bqkv"]
            qkv = qkv.astype(dt)
            q, k, v = split_qkv(qkv, cfg)
            q = rope_apply(q.reshape(T, cfg.num_heads, cfg.head_dim), *rope_cs)
            k = rope_apply(k.reshape(T, cfg.num_kv_heads, cfg.head_dim), *rope_cs)
            v3 = v.reshape(T, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope("kv_write"):
            kvn = _interleave_kv(k.reshape(T, cfg.kv_size), v, cfg)
            cache_l = write_kv(cache_l, write_pages, write_offs, kvn)
        with jax.named_scope("attn"):
            attn = ring_attention(q, k, v3, mesh=sp_mesh, axis_name=axis_name)
            attn = attn.reshape(T, cfg.q_size)
        return _attn_out_and_mlp(x, attn, lp, cfg, 1, None), cache_l

    x, cache = _run_stack(params, cache, x, write_pages, None, layer, cfg)
    with jax.named_scope("lm_head"):
        last = jax.lax.dynamic_slice_in_dim(x, last_row, 1, axis=0)  # [1, h]
        return _logits(last, params, cfg), cache


def embed_forward(
    params: Params,
    scratch: jax.Array,      # dedicated scratch paged cache (donated)
    tokens: jax.Array,       # [T] i32, one sequence
    valid: jax.Array,        # [T] bool (bucket padding mask)
    write_pages: jax.Array,  # [T] i32 into the scratch cache
    block_tables: jax.Array, # [1, scratch_pages] i32
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Causal LLM-as-embedder: one full forward over the prompt, masked
    mean pooling of the final-norm hidden states. Returns
    ([h] f32 embedding, scratch).

    Bucket-padded rows write to the garbage page (caller's
    ``write_pages``) and causal masking keeps valid rows from attending
    them; pooling masks them out of the mean."""
    T = tokens.shape[0]
    positions = jnp.arange(T, dtype=jnp.int32)
    write_offs = positions % engine.block_size
    kv_lens = jnp.asarray([T], jnp.int32)
    cu = jnp.asarray([0, T], jnp.int32)
    num_seqs = jnp.asarray([1], jnp.int32)
    x, scratch = forward_hidden(
        params, scratch, tokens, positions, write_pages, write_offs,
        kv_lens, block_tables, cu, num_seqs, cfg, engine, mesh,
    )
    w = valid.astype(jnp.float32)[:, None]
    pooled = jnp.sum(x.astype(jnp.float32) * w, axis=0) / jnp.maximum(
        jnp.sum(w), 1.0
    )
    return pooled, scratch


def block_rows(positions, block_tables, cu_q_lens, num_seqs, B: int):
    """What a block-diffusion model's attention call needs of a ragged batch
    whose every sequence brings WHOLE diffusion blocks (``B`` consecutive
    rows from a position that ``B`` divides; the host cuts its waves so:
    ``EngineCore._plan_prefill_wave``): ``(block_ends [T / B], the block
    table's row of each block's sequence [T / B, pages], live blocks
    i32[1])``. A block ends ``B`` past its first row's position whatever
    the sequence's ``kv_lens`` say: that is the mask."""
    T = positions.shape[0]
    first = jnp.arange(0, T, B, dtype=jnp.int32)
    seq_of = jnp.minimum(
        jnp.sum(first[:, None] >= cu_q_lens[None, 1:], axis=1),
        block_tables.shape[0] - 1).astype(jnp.int32)
    return (positions[first] + B, block_tables[seq_of],
            (cu_q_lens[num_seqs[0]] // B).reshape(1).astype(jnp.int32))


def block_hidden(
    params: Params,
    cache: jax.Array,
    tokens: jax.Array,        # [S, B] i32 — a block a lane, mask tokens where hidden
    block_tables: jax.Array,  # [S, pages_per_seq] i32
    positions: jax.Array,     # [S] i32 — position of each block's first place
    active: jax.Array,        # [S] bool
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh=None,
    expert_stats: list | None = None,
    pending: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One pass of a block-diffusion step up to the final norm: every lane's
    block of ``B = cfg.block_length`` places through the stack, each row
    seeing its block both ways beside the lane's causal past. Returns
    (``[S x B, h]`` normed hidden states, a row a place; cache) and NO
    logits. The block's K/V are WRITTEN, at its own positions, every pass: a
    denoising pass's are overwritten by the next, and the last one's are
    those of a block with places still masked, which nothing but the pass
    that wrote them ever reads.

    **Where a block's K/V become final.** ``pending`` ``[S, B]``: the
    REVEALED tokens of each lane's block before this one, -1 for a lane
    whose rows do not ride this pass (only a block's first has any: the
    caller hands -1 in the others). The batch is then ``[S current blocks | S
    pending blocks]``, block-major, one static shape whatever rides: a
    pending block lies ``B`` before its lane's current one, writes its K/V
    before the layer's attention like every row, and sees the past and
    itself both ways (its block ends where the current one starts), while
    the current block sees the past, the pending block as just written, and
    itself. Those are a block's final K/V: its revealed tokens seen both
    ways over a causal past, the ones a prefill wave would write for the
    same tokens. A pending half no lane rides is dead: garbage page, no
    expert group (``row_valid``), and the attention call is told ``S`` live
    blocks and never visits it. Nothing of the pending rows is returned.

    Dead lanes write the garbage page. Thin assembly over
    :func:`forward_hidden`, as :func:`verify_tokens` is over
    :func:`forward_tokens`."""
    S, B = tokens.shape
    bs = engine.block_size
    with jax.named_scope("kv_write"):
        live = S
        if pending is not None:
            rides = (pending[:, 0] >= 0) & active
            tokens = jnp.concatenate([tokens, jnp.where(rides[:, None], pending, 0)])
            positions = jnp.concatenate([positions, positions - B])
            active = jnp.concatenate([active, rides])
            block_tables = jnp.concatenate([block_tables, block_tables])
            live = jnp.where(jnp.any(rides), 2 * S, S)
        n = tokens.shape[0]
        positions = jnp.where(active, positions, 0)
        pos = positions[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]   # [n, B]
        page = jnp.take_along_axis(block_tables, pos // bs, axis=1)
        write_pages = jnp.where(active[:, None], page, engine.garbage_block).reshape(-1)
        write_offs = (pos % bs).reshape(-1)
        kv_lens = (positions + B).astype(jnp.int32)
        cu = B * jnp.arange(n + 1, dtype=jnp.int32)
        num_seqs = jnp.asarray(live, jnp.int32).reshape(1)
    x, cache = forward_hidden(
        params, cache, tokens.reshape(-1), pos.reshape(-1), write_pages,
        write_offs, kv_lens, block_tables, cu, num_seqs, cfg, engine, mesh,
        expert_stats=expert_stats, block_shape="block-decode",
    )
    return x[: S * B], cache


def block_logits(params: Params, hidden: jax.Array, rows: jax.Array | None,
                 cfg: ModelConfig) -> jax.Array:
    """The head of a block pass on the rows asked for: ``[len(rows), vocab]``
    float32 logits of ``hidden[rows]`` (:func:`block_hidden`'s ``[S x B,
    h]``), or of every row, a row a place, with ``rows`` None. A pass asks
    for the places that can still be hidden; a block's clean rows (the
    pending half of :func:`block_hidden`) never come here:
    ``programs._megastep_blocks``."""
    with jax.named_scope("lm_head"):
        return _logits(hidden if rows is None else hidden[rows], params, cfg)


def decode_tokens(
    params: Params,
    cache: jax.Array,
    tokens: jax.Array,        # [B] i32 — one new token per sequence
    block_tables: jax.Array,  # [B, pages_per_seq] i32
    positions: jax.Array,     # [B] i32 — position of `tokens`
    active: jax.Array,        # [B] bool
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh=None,
    expert_stats: list | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pure-decode step: B sequences, one token each. Thin assembly over
    :func:`forward_tokens` — in-jit slot computation so decode chains can
    advance positions on-device. It states the decode shape
    (``cu_q_lens=None``) instead of an ``arange(B + 1)`` whose meaning
    the attention call could not read back from a traced array."""
    B = tokens.shape[0]
    bs = engine.block_size
    with jax.named_scope("kv_write"):  # where each lane's K/V row lands
        page = jnp.take_along_axis(block_tables, (positions // bs)[:, None], axis=1)[:, 0]
        write_pages = jnp.where(active, page, engine.garbage_block)
        write_offs = positions % bs
        kv_lens = jnp.where(active, positions + 1, 1).astype(jnp.int32)
        num_seqs = jnp.array([B], jnp.int32)
        rows = jnp.arange(B, dtype=jnp.int32)
    return forward_tokens(
        params, cache, tokens, positions, write_pages, write_offs,
        kv_lens, block_tables, None, num_seqs, rows, cfg, engine, mesh,
        expert_stats=expert_stats,
    )


def verify_tokens(
    params: Params,
    cache: jax.Array,
    tokens: jax.Array,        # [S, R] i32 — pending + draft per lane, junk-padded
    block_tables: jax.Array,  # [S, pages_per_seq] i32
    positions: jax.Array,     # [S] i32 — position of slot 0
    draft_len: jax.Array,     # [S] i32 — live draft slots (0 = plain decode row)
    active: jax.Array,        # [S] bool
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Verify-shaped step: every lane is a fixed-width R = spec_k + 1
    ragged row (pending token + up to R-1 drafted tokens). The scanned
    device-draft body calls this between inner iterations — the width is
    static so the whole draft→verify→accept loop compiles once per
    (S, R) shape. Returns ([S*R, vocab] logits, cache); slot logits for
    lane s live at rows s*R .. s*R+R-1.

    Slot j writes K/V at position ``positions + j`` only while live
    (``active`` and ``j <= draft_len``); dead slots write the garbage
    page, so a rejected draft's K/V simply never lands past the live
    prefix and the lane's cursor algebra (num_computed_tokens rollback)
    needs no device-side undo. The rows are width-R even when the draft
    is shorter, so kv_lens is ``positions + R`` — the ragged attention
    places query i of a q_len-R row at ``kv_lens - R + i``, which puts
    every slot (live or dead) at its true position ``positions + j``.
    A dead slot attends positions only dead slots wrote (garbage /
    stale), producing junk logits that ``resolve_verify`` can never
    select (``accepted <= draft_len``); live slots attend exactly the
    one-token-at-a-time decode history."""
    S, R = tokens.shape
    bs = engine.block_size
    with jax.named_scope("kv_write"):
        j = jnp.arange(R, dtype=jnp.int32)[None, :]
        pos = positions[:, None] + j                              # [S, R]
        live = active[:, None] & (j <= draft_len[:, None])
        page = jnp.take_along_axis(block_tables, pos // bs, axis=1)
        write_pages = jnp.where(live, page, engine.garbage_block).reshape(-1)
        write_offs = (pos % bs).reshape(-1)
        kv_lens = jnp.where(active, positions + R, R).astype(jnp.int32)
        cu = R * jnp.arange(S + 1, dtype=jnp.int32)
        num_seqs = jnp.array([S], jnp.int32)
        rows = jnp.arange(S * R, dtype=jnp.int32)
    return forward_tokens(
        params, cache, tokens.reshape(-1), pos.reshape(-1), write_pages,
        write_offs, kv_lens, block_tables, cu, num_seqs, rows, cfg,
        engine, mesh,
    )
