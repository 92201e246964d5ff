"""What an engine may be built with.

:func:`resolve` is called first in ``EngineCore.__init__``: it fills in what
the model decides (the window pool, a block-diffusion model's megastep, the
unpaired page where pairs are not carried), refuses by name what the model
does not carry (:class:`UnsupportedModelOption`), and then makes every check
that reads only the two configurations and the meshes, in the order an
engine has always raised them. What needs the weights is checked where they
are placed (``parallel/placement.py:_check_fuse_tp``).

The rest of the matrix lives where its input does, and is listed here so
that one place names all of it (ROADMAP D16):

- ``ModelConfig.__post_init__`` (engine/config.py): what a model's own
  fields may say together (``ut_steps``, the attention and its widths, the
  router's scoring, the kinds of layer), before any engine exists.
- ``model._refuse_int8_latent`` (engine/model.py): int8 pages for a cache
  with window layers, conv state pages, paired KV heads or latent rows,
  where the cache is built: it guards ``init_cache`` for callers that build
  no engine.
- ``EngineCore.add_request`` calls :func:`refuse_mm_embeds` for a request
  that brings multimodal embedding rows to a model of the wide-key page.
- ``backends/jax/main.py``: ``--quant`` for a model with ``layer_groups``
  (before the weights are initialised), and ``--role prefill|decode`` for
  such a model (after the engine is built: its blocks do not leave the
  device, ``KvTransfer.kv_page_shape``).
"""

from __future__ import annotations

import dataclasses
import logging

import jax

from dynamo_tpu.engine.config import UnsupportedModelOption

log = logging.getLogger("dynamo_tpu.engine")


_TWO_SHAPES = "one block holds pages of two shapes; [planes, *page] carries one"
_LANE_STATE = ("the linear or mamba layers' state lies in a slab indexed by lane slot; no block "
               "holds it, so a block that leaves the device, or is found again by its "
               "hash, carries the full layers' pages and not the state at its end")
_TWO_POOLS = ("the window layers' pages lie in a pool of their own, whose blocks "
              "are given back as they slide out; a block that leaves the device, "
              "or is found again by its hash, carries the full layers' pages only")


def _resolve_window_pool(model_cfg, engine_cfg):
    """``engine_cfg`` with what a model's cache decides filled in.
    ``enable_prefix_caching`` None becomes True; False for a model with
    linear-attention layers (no block holds their state); and False for a
    model with window layers: a window block is not content-addressed, so a prefix
    hit would find the full layers' pages and not the window layers' newest
    ``sliding_window`` tokens (asked for by name, it is refused:
    :func:`_refuse_uncarried_options`). ``num_window_blocks`` 0 becomes
    what every lane decoding and one widest wave hold
    (``EngineConfig.window_blocks_auto``); one dispatch's span for one
    sequence has to fit with room to spare."""
    windowed = model_cfg.windowed
    prefix = engine_cfg.enable_prefix_caching
    if model_cfg.has_slab and prefix is None:
        log.info("model %s has linear_attention or mamba layers: prefix caching is off "
                 "(no block holds their state)", model_cfg.name)
        engine_cfg = dataclasses.replace(engine_cfg, enable_prefix_caching=False)
        prefix = False
    if not windowed:
        if engine_cfg.num_window_blocks:
            raise ValueError(
                f"num_window_blocks={engine_cfg.num_window_blocks} for model "
                f"{model_cfg.name!r}: only a model with sliding_attention layers "
                "has a window pool")
        if prefix is None:
            return dataclasses.replace(engine_cfg, enable_prefix_caching=True)
        return engine_cfg
    w = model_cfg.sliding_window
    blocks = engine_cfg.num_window_blocks or engine_cfg.window_blocks_auto(w)
    least = engine_cfg.window_table_blocks(w) + engine_cfg.window_span_blocks(
        w, engine_cfg.megastep_k)
    if blocks < least:
        raise ValueError(
            f"num_window_blocks={blocks} cannot hold one sequence's widest "
            f"dispatch beside one decoding lane ({least} blocks of "
            f"{engine_cfg.block_size} tokens for window {w})")
    if prefix is None:
        log.info("model %s has sliding_attention layers: prefix caching is off "
                 "(window blocks are not content-addressed)", model_cfg.name)
    return dataclasses.replace(
        engine_cfg, num_window_blocks=blocks, enable_prefix_caching=False)


def _unpaired_where_not_carried(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh):
    """``model_cfg``, with ``kv_pairing`` cleared where a DENSE model of
    64-wide KV heads is served with an option the paired page does not
    carry: a mesh of any kind (tp shards KV heads one by one; a stage's
    and a ring's programs were never compared with pairs) or int8 pages
    (a scale per slot and KV head). Such a model keeps those options on
    the page it had before there were pairs, ``(block_size, 2 n_kv, 64)``,
    and the attention a 64-wide head gets there. A hybrid model has no
    unpaired path that was ever compared: it refuses them by name
    (:func:`_refuse_uncarried_options`)."""
    engaged = (mesh is not None or sp_mesh is not None or pp_mesh is not None
               or engine_cfg.ring_prefill_threshold > 0 or engine_cfg.kv_quantized)
    if model_cfg.kv_head_pairs and not model_cfg.hybrid and engaged:
        return dataclasses.replace(model_cfg, kv_pairing=False)
    return model_cfg


def _resolve_block_megastep(model_cfg, engine_cfg):
    """The engine's configuration with a block-diffusion model's
    ``megastep_k`` resolved to the forwards a dispatch fuses: whole blocks
    of ``model_cfg.denoising_steps + 1`` passes (as many as ``megastep_k``
    holds, at least one). A page holds whole blocks, so that a block never
    straddles two and a page's K/V is a function of the tokens up to its
    end."""
    B = model_cfg.block_length
    if not B:
        return engine_cfg
    misfit = [n for n in (engine_cfg.block_size, *engine_cfg.prefill_buckets) if n % B]
    if misfit:
        raise ValueError(
            f"block_size and prefill_buckets must hold whole blocks of {B}; {misfit} do not")
    passes = model_cfg.denoising_steps + 1
    return dataclasses.replace(
        engine_cfg, megastep_k=max(1, engine_cfg.megastep_k // passes) * passes)


_BLOCK_STEP = ("a step of this model is a block of places a lane, denoised over "
               "several forwards; ")


def _refuse_uncarried_options(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh) -> None:
    """A model with latent attention or the sigmoid-routed sparse MLP
    runs on ONE chip's programs with a plain latent page; one with conv
    layers (``model_cfg.hybrid``) keeps pages of two shapes, its 64-wide
    KV heads in pairs, and a rolling state that does not forgive a write
    past the cursor (model.conv_layer, "The invariant"). Every option
    that page, or that layer, does not carry is refused here (for a model
    with ``linear_attention`` layers, whose state lies in a slab a lane that no
    block holds, also the prefix cache asked for by name, as a windowed
    model's), at start-up and by name (:class:`UnsupportedModelOption`), not at the
    first request that meets it. Carried by all: the prefix cache,
    preemption and recompute, embeddings, both schedulers, the megastep;
    by the latent page also the host and disk tiers, the disagg payload
    and peer pulls, which a hybrid cache refuses (a block that leaves the
    device is ``[planes, *page]`` of ONE shape:
    ``EngineCore.kv_page_shape``)."""
    # (``linear``: a slab a lane slot, a gated delta rule's or a Mamba-2 mixer's)
    hybrid, windowed, linear = model_cfg.hybrid, model_cfg.windowed, model_cfg.has_slab
    blocks = model_cfg.block_length > 0
    # one chip's programs: the layers no mesh rule, stage body or verify row knows
    if not (model_cfg.latent or model_cfg.shared_sparse or hybrid or windowed or blocks
            or linear):
        return
    stays = _TWO_SHAPES if hybrid else _LANE_STATE if linear else _TWO_POOLS
    refused = {
        "scheduling": blocks and engine_cfg.scheduling == "chunked" and (
            _BLOCK_STEP + "a mixed step's decode rows are one token a lane, and its "
            "chunks are not held to whole blocks"),
        "prefix_caching": (windowed or linear) and engine_cfg.enable_prefix_caching is True
        and stays,
        "kv_dtype": engine_cfg.kv_quantized and (
            model_cfg.latent or hybrid or windowed or blocks or linear) and (
            "int8 pages keep a scale per slot and KV head; "
            + ("a latent page has no heads" if model_cfg.latent else
               "the full layers' pages beside a float32 slab were not compared as int8"
               if linear else
               "a block in flight is quantised anew every pass, which was not compared"
               if blocks else
               "the window pool's pages were not compared as int8" if windowed else
               "conv state pages and paired heads have no such scale")),
        "host_kv_blocks": (hybrid or windowed or linear) and engine_cfg.host_kv_blocks > 0
        and stays,
        "disk_kv_dir": (hybrid or windowed or linear) and bool(engine_cfg.disk_kv_dir) and stays,
        "tp": mesh is not None
        and "no sharding rule for the latent projections, the held experts (a "
            "share is stated with experts_held, not with a mesh), conv "
            "operators, linear-attention or mamba operators and their slab, paired KV heads "
            "or layers of unequal head counts",
        "pp": pp_mesh is not None
        and "the pipeline's stage body is the dense layer's",
        "ring_prefill": (sp_mesh is not None or engine_cfg.ring_prefill_threshold > 0)
        and "ring attention reads expanded K and V per head",
        "spec_decode": engine_cfg.spec_decode != "off" and (
            _BLOCK_STEP + "there is no next token to draft" if blocks else
            "a rejected draft has already overwritten the convolution's rolling "
            "state past the cursor the lane goes on from" if hybrid else
            "a rejected draft has already updated the linear or mamba layers' state in place "
            "past the cursor the lane goes on from" if linear else
            "a window block is given back by the cursor a verify row may fall "
            "behind" if windowed else
            "verify rows were not compared with the reference for this model"),
    }
    for option, why in refused.items():
        if why:
            raise UnsupportedModelOption(option, model_cfg.name, why)


def refuse_mm_embeds(model_cfg) -> None:
    """A REQUEST's multimodal embedding rows, refused by name as the request
    is admitted (the one refusal here that no start-up can make): a model
    of the wide-key page is served as its text model alone (the published
    configuration has no key of a vision or audio tower), and embedding rows
    spliced into a wave were never compared through that page."""
    if model_cfg.wide_key:
        raise UnsupportedModelOption(
            "mm_embeds", model_cfg.name,
            "the text model alone is served: no tower is modelled, and embedding "
            "rows were never compared through the wide-key page")


def resolve(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh):
    """``(model_cfg, engine_cfg)`` as the engine serves them, or the
    refusal: the four resolutions above in the order they have always
    run, then the checks of the configurations and the meshes."""
    model_cfg = _unpaired_where_not_carried(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh)
    _refuse_uncarried_options(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh)
    engine_cfg = _resolve_window_pool(model_cfg, engine_cfg)
    engine_cfg = _resolve_block_megastep(model_cfg, engine_cfg)
    bs = engine_cfg.block_size
    for b in engine_cfg.prefill_buckets:
        if b % bs:
            raise ValueError(f"prefill bucket {b} not a multiple of block_size {bs}")
    if engine_cfg.scheduling not in ("waves", "chunked"):
        raise ValueError(
            f"unknown scheduling policy {engine_cfg.scheduling!r} "
            "(expected 'waves' or 'chunked')"
        )
    chunked = engine_cfg.scheduling == "chunked"
    if engine_cfg.prefill_chunk and engine_cfg.prefill_chunk % bs:
        raise ValueError(
            f"prefill_chunk {engine_cfg.prefill_chunk} not a multiple "
            f"of block_size {bs} (chunk boundaries must respect block "
            "granularity so both schedulers commit identical layouts)"
        )
    if engine_cfg.max_num_batched_tokens > engine_cfg.prefill_buckets[-1]:
        raise ValueError(
            f"max_num_batched_tokens {engine_cfg.max_num_batched_tokens} "
            f"exceeds the largest prefill bucket "
            f"{engine_cfg.prefill_buckets[-1]} (mixed steps bucket their "
            "total tokens)"
        )
    if engine_cfg.prefill_chunk > engine_cfg.token_budget:
        raise ValueError(
            f"prefill_chunk {engine_cfg.prefill_chunk} exceeds the "
            f"per-step token budget {engine_cfg.token_budget}"
        )
    if chunked and (
        engine_cfg.token_budget < engine_cfg.decode_buckets[-1] + bs
    ):
        raise ValueError(
            f"max_num_batched_tokens {engine_cfg.token_budget} cannot fit "
            f"the decode width {engine_cfg.decode_buckets[-1]} plus one "
            f"{bs}-token prefill chunk; raise the budget or shrink "
            "decode_buckets"
        )
    if chunked and sp_mesh is not None:
        raise ValueError(
            "scheduling='chunked' is not wired for sp meshes yet; "
            "those engines keep 'waves'"
        )
    if engine_cfg.spec_decode not in ("off", "ngram"):
        raise ValueError(
            f"unknown spec_decode {engine_cfg.spec_decode!r} "
            "(expected 'off' or 'ngram')"
        )
    if engine_cfg.spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {engine_cfg.spec_k}")
    if engine_cfg.megastep_k < 1:
        raise ValueError(
            f"megastep_k must be >= 1 (1 disables fusion), got "
            f"{engine_cfg.megastep_k}"
        )
    from dynamo_tpu.engine.kv_quant import KV_DTYPES

    if engine_cfg.kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"unknown kv_dtype {engine_cfg.kv_dtype!r} "
            f"(expected one of {KV_DTYPES})"
        )
    if (
        engine_cfg.kv_quantized
        and jax.default_backend() == "tpu"
        and model_cfg.head_dim % 128 == 0
        and engine_cfg.block_size % 8 == 0
    ):
        # The TPU serving attention (library ragged kernel) cannot
        # read int8 pages directly; the first cut dequantizes ONE
        # LAYER's referenced (or, when smaller, all) pages to the
        # model dtype before each call. That transient is bounded
        # (~1/num_layers of a bf16 cache) but it is extra read
        # traffic — capacity win only. Say so once, loudly, so the
        # doubled-capacity deployment knows what it bought.
        log.warning(
            "kv_dtype=int8 on TPU: serving attention dequantizes "
            "per-layer pages before the library kernel (capacity "
            "win, no traffic win; transient ~1/%d of a bf16 cache "
            "per call). TPOT cost against bf16 KV: not measured "
            "(ROADMAP S4).",
            model_cfg.num_layers,
        )
    if engine_cfg.spec_decode != "off" and pp_mesh is not None:
        raise ValueError(
            "speculative decoding under pipeline parallelism is not "
            "wired yet (the pp microbatch planner samples one row per "
            "sequence); run spec on a tp/dp or single-chip engine"
        )
    if engine_cfg.async_exec and sp_mesh is not None:
        raise ValueError(
            "async_exec=True cannot be honoured on an sp mesh (the "
            "ring prefill path commits in place); leave it unset and "
            "the engine keeps the synchronous loop"
        )
    if engine_cfg.max_waiting < 0:
        raise ValueError(
            f"max_waiting must be >= 0 (0 = unbounded), got "
            f"{engine_cfg.max_waiting}"
        )
    if engine_cfg.fair_quantum < 0:
        raise ValueError(
            f"fair_quantum must be >= 0 (0 = token budget), got "
            f"{engine_cfg.fair_quantum}"
        )
    if pp_mesh is not None:
        if mesh is not None or sp_mesh is not None:
            raise ValueError(
                "pp_mesh is mutually exclusive with mesh (tp/dp) and "
                "sp_mesh for now (pp x tp composition: future work)"
            )
        pp = int(pp_mesh.shape["pp"])
        if model_cfg.is_moe:
            # Reject at construction, not at the first prefill wave.
            raise ValueError(
                "pipeline parallelism for MoE presets is not built yet "
                "(compose pp with the EP dispatch inside each stage)"
            )
        if model_cfg.num_layers % pp:
            raise ValueError(
                f"pp={pp} must divide num_layers={model_cfg.num_layers}"
            )
        if model_cfg.vocab_size % pp:
            raise ValueError(
                f"pp={pp} must divide vocab_size={model_cfg.vocab_size}"
            )
        from dynamo_tpu.parallel.pipeline import pp_microbatches

        micro = pp_microbatches(pp)
        for kind, buckets in (("prefill", engine_cfg.prefill_buckets),
                              ("decode", engine_cfg.decode_buckets)):
            for b in buckets:
                if b % micro:
                    raise ValueError(
                        f"{kind} bucket {b} not a multiple of pp microbatch "
                        f"count {micro}"
                    )
    elif mesh is not None:
        dp = int(mesh.shape["dp"])
        for b in engine_cfg.decode_buckets:
            if b % dp:
                raise ValueError(
                    f"decode bucket {b} not a multiple of dp={dp}"
                )
    if engine_cfg.disk_kv_dir and engine_cfg.host_kv_blocks <= 0:
        raise ValueError("disk_kv_dir (G3) requires host_kv_blocks > 0 (G2)")
    if sp_mesh is not None and mesh is not None:
        raise ValueError("sp_mesh (sequence parallel) and mesh (tp/dp) "
                         "are mutually exclusive for now")
    return model_cfg, engine_cfg
