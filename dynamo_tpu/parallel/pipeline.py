"""Pipeline parallelism: layer-staged GPipe over a ``("pp",)`` device mesh.

The reference never implements pipeline parallelism itself — it plumbs
``pipeline-parallel-size`` flags down to its engines
(`/root/reference/components/backends/sglang/docs/multinode-examples.md:10`,
SURVEY.md §2.6 "engine-delegated"). On TPU the partitioning is
first-party, and it is NOT a port of a GPU schedule: the whole fill/drain
pipeline is ONE jitted ``shard_map`` program in which every stage runs the
same code on its own layer slice and activations rotate between stages via
``lax.ppermute`` over ICI.

Design:

- **Layer-axis sharding.** The params pytree keeps its stacked ``[L, ...]``
  layer arrays; PP shards axis 0 over ``pp`` (``pp_param_specs``), so stage
  ``s`` physically holds layers ``[s*L/pp, (s+1)*L/pp)`` — and the paged KV
  cache ``[L, pages, page_size, 2kv, d]`` shards the same way: each stage
  scatters and reads only its own layers' pages. No resharding, no copies:
  placement IS the stage assignment.
- **Microbatched rounds.** The ragged token batch (same layout as
  :func:`dynamo_tpu.engine.model.forward_tokens` — prefill chunks, decode
  tokens, mixed) splits into ``M`` equal row chunks. Round ``r`` has stage
  ``s`` working microbatch ``r - s``; after each round activations
  ``ppermute`` one stage forward. ``M + pp - 1`` rounds drain the pipe;
  steady-state efficiency is ``M / (M + pp - 1)``.
- **Chunked-prefill causality for free.** Microbatch ``m``'s attention
  reads pages written by microbatches ``< m`` in earlier rounds plus its
  own scatter this round — exactly the chunked-prefill semantics the
  ragged kernel already implements (per-chunk ``kv_lens`` computed by the
  host-side :func:`plan_microbatches`), so sequences may straddle chunk
  boundaries.
- **Replicated exit.** Only the last stage's final-norm rows are real; a
  ``psum`` over ``pp`` replicates each sequence's last-token hidden state
  so the logits matmul (and fused sampling above it) run identically on
  every device — multi-host leaders can fetch outputs from any process
  (same rule as `_replicate_out`, engine/core.py).

Composition: v1 is a pure-``pp`` mesh (tp=1 inside each stage); ``pp×tp``
composes by nesting :func:`sharded_ragged_attention`'s head split inside
each stage and is left until a >8-device single-host target exists.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.model import (
    Params,
    _dot,
    _logits,
    dense_layer,
    rms_norm,
    rope_tables,
)


def make_pp_mesh(pp: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if pp > len(devices):
        raise ValueError(f"pp={pp} needs {pp} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:pp]), ("pp",))


def pp_microbatches(pp: int) -> int:
    """Microbatches a step runs as over ``pp`` stages: the wavefront
    schedule needs M >= pp for the ring-fed token feedback; M = pp also
    makes per-step lm-head traffic match the unpipelined engine (V/pp per
    stage). The buckets are held to it (engine/options.py)."""
    return pp


def pp_param_specs(cfg: ModelConfig, pp: int) -> dict[str, Any]:
    """PartitionSpecs for `model.init_params` pytrees under PP: stacked
    layer arrays shard axis 0 over ``pp``; embeddings/norms replicate
    (stage 0 embeds, the last stage projects — via the psum exit every
    stage holds both, which is what lets the logits matmul run
    replicated)."""
    if cfg.num_layers % pp:
        raise ValueError(f"pp={pp} must divide num_layers={cfg.num_layers}")
    if cfg.ut_steps > 1:
        raise ValueError(
            f"pp={pp} with ut_steps={cfg.ut_steps}: a looped stack would "
            "go round the stages once per pass; not built"
        )
    layers = {
        "attn_norm": P("pp"),
        "mlp_norm": P("pp"),
        "wqkv": P("pp"),
        "wo": P("pp"),
    }
    if cfg.attn_qkv_bias:
        layers["bqkv"] = P("pp")
    if cfg.is_moe:
        layers["w_router"] = P("pp")
        layers["w_gate"] = P("pp")
        layers["w_up"] = P("pp")
        layers["w_down"] = P("pp")
    else:
        layers["wgu"] = P("pp")
        layers["w_down"] = P("pp")
    specs = {
        "embed": P(None, None),
        "layers": layers,
        "final_norm": P(None),
        "fuse_tp": P(),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, None)
    return specs


def _is_quant_leaf(x) -> bool:
    """An int8 ``{"w", "scale"}`` projection (model.quantize_weight)."""
    return isinstance(x, dict) and set(x) == {"w", "scale"}


def shard_params_pp(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """Place a params pytree per :func:`pp_param_specs`. int8 params keep
    their ``{"w", "scale"}`` dict leaves: a stacked layer projection is
    ``w [L, ...]`` + ``scale [L, 1, out]`` — BOTH carry the layer axis
    first, so one ``P("pp")`` spec shards the pair onto its stage."""
    specs = pp_param_specs(cfg, int(mesh.shape["pp"]))
    if "fuse_tp" not in params:
        specs.pop("fuse_tp")

    def place(x, spec):
        put = lambda a: jax.device_put(a, NamedSharding(mesh, spec))
        if _is_quant_leaf(x):
            return {k: put(v) for k, v in x.items()}
        return put(x)

    return jax.tree.map(place, params, specs, is_leaf=_is_quant_leaf)


def cache_sharding_pp(mesh: Mesh, quantized: bool = False):
    """[L, pages, page_size, 2kv, d] — layer axis on pp (each stage holds
    only its own layers' KV). Quantized caches are a ``{"kv", "scale"}``
    dict of stacked arrays; the scale pages shard their layer axis the
    same way, so every stage owns matching (kv, scale) page pairs."""
    if quantized:
        return {
            "kv": NamedSharding(mesh, P("pp", None, None, None, None)),
            "scale": NamedSharding(mesh, P("pp", None, None, None)),
        }
    return NamedSharding(mesh, P("pp", None, None, None, None))


@dataclass
class PPPlan:
    """Host-planned microbatch schedule (static shapes: one compile per
    (T, S, n_micro) bucket combo, same rule as the engine's buckets)."""

    n_micro: int
    tokens: np.ndarray       # [M, Tm] i32
    positions: np.ndarray    # [M, Tm] i32
    write_pages: np.ndarray  # [M, Tm] i32 (garbage page on pad rows)
    write_offs: np.ndarray   # [M, Tm] i32
    kv_lens: np.ndarray      # [M, S] i32 — per seq, through this chunk
    cu_q_lens: np.ndarray    # [M, S+1] i32 — chunk-local ragged offsets
    last_local: np.ndarray   # [M, S] i32 — chunk-local row of seq's last token
    last_mask: np.ndarray    # [M, S] bool — last token lands in this chunk


def plan_microbatches(
    tokens: np.ndarray,       # [T] i32 ragged batch (model.forward_tokens layout)
    positions: np.ndarray,    # [T] i32
    write_pages: np.ndarray,  # [T] i32
    write_offs: np.ndarray,   # [T] i32
    kv_lens: np.ndarray,      # [S] i32 — per seq, through the whole batch
    cu_q_lens: np.ndarray,    # [S+1] i32
    num_seqs: int,
    last_rows: np.ndarray,    # [S] i32 global row of each seq's last token
    n_micro: int,
    garbage_block: int,
) -> PPPlan:
    """Split a ragged token batch into ``n_micro`` equal row chunks.
    Sequences may straddle chunks: per-chunk ``kv_lens`` count each
    sequence's tokens only through that chunk, which is exactly the
    chunked-prefill contract of :mod:`dynamo_tpu.ops.ragged_attention`."""
    T = len(tokens)
    S = len(kv_lens)
    M = max(1, int(n_micro))
    Tm = -(-T // M)
    pad = M * Tm - T

    def padded(arr, fill):
        return np.concatenate(
            [np.asarray(arr, np.int32), np.full(pad, fill, np.int32)]
        ).reshape(M, Tm)

    plan = PPPlan(
        n_micro=M,
        tokens=padded(tokens, 0),
        positions=padded(positions, 0),
        write_pages=padded(write_pages, garbage_block),
        write_offs=padded(write_offs, 0),
        kv_lens=np.ones((M, S), np.int32),
        cu_q_lens=np.zeros((M, S + 1), np.int32),
        last_local=np.zeros((M, S), np.int32),
        last_mask=np.zeros((M, S), bool),
    )
    cu = np.asarray(cu_q_lens, np.int64)  # dynalint: sync-ok — host plan arrays, not device arrays
    kv = np.asarray(kv_lens, np.int64)  # dynalint: sync-ok — host plan arrays, not device arrays
    for m in range(M):
        lo_c, hi_c = m * Tm, (m + 1) * Tm
        q_in_chunk = np.maximum(
            0,
            np.minimum(cu[1:], hi_c) - np.maximum(cu[:-1], lo_c),
        )  # [S]
        q_in_chunk[num_seqs:] = 0
        # kv through this chunk = total kv minus this seq's rows in LATER
        # chunks (rows are the seq's trailing tokens, kernel contract).
        after = np.maximum(0, cu[1:] - hi_c)
        # A sequence with no query rows in this chunk would otherwise get
        # a meaningless kv_len (e.g. prior_kv - offset for one that starts
        # in a later chunk). The ragged kernel skips zero-length queries,
        # but pin the value to the benign 1 so it can never be consumed.
        kv_through = np.where(q_in_chunk > 0, np.maximum(1, kv - after), 1)
        plan.kv_lens[m] = kv_through.astype(np.int32)
        plan.cu_q_lens[m, 1:] = np.cumsum(q_in_chunk).astype(np.int32)
        in_chunk = (last_rows >= lo_c) & (last_rows < hi_c)
        in_chunk[num_seqs:] = False
        plan.last_mask[m] = in_chunk
        plan.last_local[m] = np.where(in_chunk, last_rows - lo_c, 0).astype(
            np.int32
        )
    return plan


def _stage_layers(
    x, layers_local, cache_local, positions, write_pages, write_offs,
    kv_lens, block_tables, cu_q_lens, num_seqs, cfg: ModelConfig,
):
    """One stage's layer slice over one microbatch: the SAME
    :func:`model.dense_layer` block as forward_hidden, sliced out of the
    stage-local stacked ``[Lp, ...]`` cache (pp keeps the stacked layout
    — the layer axis IS the stage sharding — and pays the slice
    roundtrip the engine's tuple cache avoids; pp is a capacity mode,
    not the single-chip fast path). A quantized cache is a
    ``{"kv", "scale"}`` dict of stacked arrays: the per-layer slice
    hands dense_layer exactly the per-layer dict it already handles, and
    the write-back updates both members in place."""
    rope_cs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    quant = isinstance(cache_local, dict)
    Lp = (cache_local["kv"] if quant else cache_local).shape[0]
    for j in range(Lp):
        lp = jax.tree.map(lambda a: a[j], layers_local)
        cache_j = (
            {k: v[j] for k, v in cache_local.items()} if quant
            else cache_local[j]
        )
        x, cache_j = dense_layer(
            x, lp, cache_j, positions, write_pages, write_offs,
            kv_lens, block_tables, cu_q_lens, num_seqs, cfg,
            rope_cs=rope_cs,
        )
        if quant:
            cache_local = {
                k: cache_local[k].at[j].set(cache_j[k]) for k in cache_local
            }
        else:
            cache_local = cache_local.at[j].set(cache_j)
    return x, cache_local


def _pp_program(
    params, cache, mb_tokens, mb_positions, mb_pages, mb_offs,
    mb_kv_lens, block_tables, mb_cu, num_seqs, mb_last_local, mb_last_mask,
    *, cfg: ModelConfig, engine: EngineConfig, pp: int, n_micro: int,
):
    """The per-device GPipe body (runs under shard_map over ``pp``)."""
    M = n_micro
    S = mb_kv_lens.shape[1]
    Tm = mb_tokens.shape[1]
    s = jax.lax.axis_index("pp")
    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

    buf = jnp.zeros((Tm, cfg.hidden_size), cfg.jax_dtype)
    hid = jnp.zeros((S, cfg.hidden_size), jnp.float32)
    for r in range(M + pp - 1):
        mb = r - s
        valid = (mb >= 0) & (mb < M)
        mbc = jnp.clip(mb, 0, M - 1)
        toks = mb_tokens[mbc]
        # Stage 0 injects the embedding; later stages take the rotated
        # activation (the gather is a few KB — cheaper than branching).
        x = jnp.where(s == 0, params["embed"][toks], buf)
        pos = mb_positions[mbc]
        pages = jnp.where(valid, mb_pages[mbc], engine.garbage_block)
        x, cache = _stage_layers(
            x, params["layers"], cache, pos, pages, mb_offs[mbc],
            mb_kv_lens[mbc], block_tables, mb_cu[mbc], num_seqs, cfg,
        )
        # Last stage banks each sequence's last-token hidden state the
        # round its microbatch drains.
        normed = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        take = normed[mb_last_local[mbc]]  # [S, h]
        emit = valid & (s == pp - 1) & mb_last_mask[mbc]
        hid = hid + jnp.where(emit[:, None], take.astype(jnp.float32), 0.0)
        if r < M + pp - 2:
            buf = jax.lax.ppermute(x, "pp", fwd_perm)
    # Replicate the exit: only stage pp-1 contributed.
    hid = jax.lax.psum(hid, "pp")
    return hid, cache


def _param_specs_tree(params: Params):
    specs = jax.tree.map(lambda _: P(), params)
    specs["layers"] = jax.tree.map(lambda _: P("pp"), params["layers"])
    return specs


def pp_forward_impl(
    params: Params,
    cache: jax.Array,
    mb_tokens, mb_positions, mb_pages, mb_offs,
    mb_kv_lens, block_tables, mb_cu, num_seqs,
    mb_last_local, mb_last_mask,
    *,
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh: Mesh,
    n_micro: int,
):
    """Traceable body of :func:`pp_forward_tokens` (EngineCore jits it
    inside its own fused prefill+sample program)."""
    if cfg.is_moe:
        raise NotImplementedError(
            "pipeline parallelism for MoE presets: compose pp with the EP "
            "dispatch inside each stage (parallel/sharding.py) — not yet built"
        )
    pp = int(mesh.shape["pp"])
    hid, cache = shard_map(
        partial(_pp_program, cfg=cfg, engine=engine, pp=pp, n_micro=n_micro),
        mesh=mesh,
        in_specs=(
            _param_specs_tree(params),
            P("pp"),  # cache
            P(), P(), P(), P(),  # mb token arrays
            P(), P(), P(), P(),  # kv_lens, tables, cu, num_seqs
            P(), P(),            # last_local, last_mask
        ),
        out_specs=(P(), P("pp")),
        check_vma=False,
    )(
        params, cache, mb_tokens, mb_positions, mb_pages, mb_offs,
        mb_kv_lens, block_tables, mb_cu, num_seqs, mb_last_local, mb_last_mask,
    )
    return _logits(hid.astype(cfg.jax_dtype), params, cfg), cache


@partial(
    jax.jit,
    static_argnames=("cfg", "engine", "mesh", "n_micro"),
    donate_argnums=(1,),
)
def pp_forward_tokens(
    params: Params,
    cache: jax.Array,
    mb_tokens, mb_positions, mb_pages, mb_offs,
    mb_kv_lens, block_tables, mb_cu, num_seqs,
    mb_last_local, mb_last_mask,
    *,
    cfg: ModelConfig,
    engine: EngineConfig,
    mesh: Mesh,
    n_micro: int,
):
    """PP analogue of :func:`model.forward_tokens`: same ragged batch (via
    a :class:`PPPlan`), same result — last-token logits ``[S, vocab]`` f32
    plus the updated (layer-sharded) cache."""
    return pp_forward_impl(
        params, cache, mb_tokens, mb_positions, mb_pages, mb_offs,
        mb_kv_lens, block_tables, mb_cu, num_seqs, mb_last_local,
        mb_last_mask, cfg=cfg, engine=engine, mesh=mesh, n_micro=n_micro,
    )


def _pp_decode_round_body(
    params, cache, buf, r, store, tables_g, pos0_g, act_g,
    *, cfg: ModelConfig, engine: EngineConfig, pp: int, n_micro: int,
    n_steps: int,
):
    """One wavefront round (per device, under shard_map): stage ``s``
    advances work item ``idx = r - s`` — decode step ``idx // M`` of lane
    group ``idx % M`` — one stage down the pipe. The lm head is computed
    vocab-sharded over ``pp`` (each stage reads only its ``V/pp`` slice of
    the embedding per round, so per-step embedding traffic matches the
    unpipelined engine when ``M == pp``)."""
    M = n_micro
    s = jax.lax.axis_index("pp")
    bs = engine.block_size
    buf = buf[0]  # [Bm, h] (leading pp axis is the shard axis)
    Bm = buf.shape[0]

    idx = r - s
    valid = (idx >= 0) & (idx < n_steps * M)
    idxc = jnp.maximum(idx, 0)
    g = idxc % M
    t = idxc // M

    toks = store[g]                       # [Bm] this group's current token
    x = jnp.where(s == 0, params["embed"][toks], buf)
    pos = pos0_g[g] + t                   # [Bm]
    act = act_g[g]
    table = tables_g[g]                   # [Bm, pages]
    page = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    write_pages = jnp.where(act & valid, page, engine.garbage_block)
    write_offs = pos % bs
    kv_lens = jnp.where(act, pos + 1, 1).astype(jnp.int32)
    num_seqs = jnp.asarray([Bm], jnp.int32)

    # cu_q_lens=None: the decode shape, as engine/model.py:decode_tokens
    # states it (one row a lane), so attention runs its decode grid.
    x, cache = _stage_layers(
        x, params["layers"], cache, pos, write_pages, write_offs,
        kv_lens, table, None, num_seqs, cfg,
    )
    # Exit: the last stage's final-norm rows, replicated; then this
    # stage's V/pp slice of the logits.
    normed = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    exit_h = jax.lax.psum(
        jnp.where((s == pp - 1) & valid, normed.astype(jnp.float32), 0.0),
        "pp",
    ).astype(cfg.jax_dtype)
    V = cfg.vocab_size
    Vp = V // pp
    if cfg.tie_embeddings:
        w = jax.lax.dynamic_slice_in_dim(params["embed"], s * Vp, Vp, axis=0)
        logits = jax.lax.dot_general(
            exit_h, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        lm = params["lm_head"]
        if isinstance(lm, dict):
            wq = jax.lax.dynamic_slice_in_dim(lm["w"], s * Vp, Vp, axis=1)
            sc = jax.lax.dynamic_slice_in_dim(lm["scale"], s * Vp, Vp, axis=1)
            logits = _dot(exit_h, {"w": wq, "scale": sc})
        else:
            w = jax.lax.dynamic_slice_in_dim(lm, s * Vp, Vp, axis=1)
            logits = _dot(exit_h, w)
    buf_next = jax.lax.ppermute(x, "pp", [(i, (i + 1) % pp) for i in range(pp)])
    return buf_next[None], cache, logits


def pp_decode_round(
    params, cache, buf, r, store, tables_g, pos0_g, act_g,
    *, cfg: ModelConfig, engine: EngineConfig, mesh: Mesh, n_micro: int,
    n_steps: int,
):
    """One wavefront decode round over the pp mesh. ``buf`` is the
    rotating activation buffer ``[pp, Bm, h]`` (stage-sharded); returns
    (buf', cache', logits ``[Bm, V]`` vocab-sharded over pp)."""
    pp = int(mesh.shape["pp"])
    return shard_map(
        partial(
            _pp_decode_round_body, cfg=cfg, engine=engine, pp=pp,
            n_micro=n_micro, n_steps=n_steps,
        ),
        mesh=mesh,
        in_specs=(
            _param_specs_tree(params),
            P("pp"),   # cache (layer axis)
            P("pp"),   # buf (stage axis)
            P(), P(), P(), P(), P(),  # r, store, tables, pos0, act
        ),
        out_specs=(P("pp"), P("pp"), P(None, "pp")),
        check_vma=False,
    )(params, cache, buf, r, store, tables_g, pos0_g, act_g)
