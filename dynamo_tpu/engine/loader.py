"""HF checkpoint loading: llama-family safetensors/torch -> stacked params.

Capability parity: the reference resolves HF repos into engine weights via
its local_model/hub path (`lib/llm/src/local_model.rs:429`, `hub.rs:127`);
here the weights map into the engine's stacked-layer pytree (one leading
num_layers axis per weight, ready for `lax.scan`). Local files only — the
environment has zero egress.

Convention notes: HF Linear weights are [out, in] (torch) -> transposed;
HF llama checkpoints use the half-split ("rotate_half") RoPE convention,
which is exactly `model.rope`, so weights drop in without permutation.
"""

from __future__ import annotations

import json
import logging
from pathlib import Path
from typing import Any

import numpy as np

from dynamo_tpu.engine.config import ModelConfig

log = logging.getLogger("dynamo_tpu.loader")


# Published model types whose layer is the latent-attention, sigmoid-routed
# one (ModelConfig.attention "mla", router_scoring "sigmoid").
LATENT_SPARSE_TYPES = ("axk1",)


def _latent_sparse_config(hf: dict, experts_held) -> ModelConfig:
    if hf.get("topk_method", "none") != "none":
        raise NotImplementedError(
            f"topk_method={hf['topk_method']!r}: a bias on the router's "
            "choice is not implemented (only 'none')"
        )
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        attention="mla",
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        rope_scaling=hf.get("rope_scaling"),
        first_dense_layers=hf.get("first_k_dense_replace", 0),
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        router_scoring=hf.get("scoring_func", "sigmoid"),
        n_group=hf.get("n_group", 1),
        topk_group=hf.get("topk_group", 1),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        routed_scaling_factor=hf.get("routed_scaling_factor", 1.0),
        num_shared_experts=hf.get("n_shared_experts", 0),
        experts_held=experts_held,
    )


# Published model types whose layers are of two kinds by ``layer_types``:
# gated short convolutions among grouped-query attention (LFM2).
HYBRID_CONV_TYPES = ("lfm2_moe",)


def _hybrid_conv_config(hf: dict) -> ModelConfig:
    rope = hf.get("rope_parameters") or {}
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1000000.0))),
        rms_norm_eps=hf.get("norm_eps", 1e-5),
        tie_embeddings=hf.get("tie_word_embeddings", True),
        layer_types=tuple(hf["layer_types"]),
        conv_L_cache=hf["conv_L_cache"],
        conv_bias=hf.get("conv_bias", False),
        qk_norm=True,
        first_dense_layers=hf.get("num_dense_layers", 0),
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        router_scoring="sigmoid",
        norm_topk_prob=hf.get("norm_topk_prob", True),
        routed_scaling_factor=hf.get("routed_scaling_factor", 1.0),
        router_bias=hf.get("use_expert_bias", False),
        router_norm_eps=1e-6,
    )


# Published model types whose layers are gated-delta-rule linear attention
# among multi-head attention without rope (Olmo-Hybrid).
HYBRID_LINEAR_TYPES = ("olmo_hybrid",)


def _hybrid_linear_config(hf: dict) -> ModelConfig:
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_theta") is not None or hf.get("attention_bias"):
        raise NotImplementedError(
            f"{hf['model_type']}: a rotary embedding (rope_theta="
            f"{rope.get('rope_theta')!r}) or attention_bias is not modelled for it")
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        rope_theta=None,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        layer_types=tuple(hf["layer_types"]),
        qk_norm=True,
        qk_norm_over="projection",
        post_norm=True,
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=hf.get("linear_allow_neg_eigval", False),
    )


# Published model types whose blocks are ONE sub-layer by
# ``hybrid_override_pattern``: a Mamba-2 mixer, attention without rope, or
# sigmoid-routed un-gated experts (Nemotron-3-Nano).
HYBRID_SSM_TYPES = ("nemotron_h",)
_SSM_LETTERS = {"M": "mamba", "*": "full_attention", "E": "moe"}


def _hybrid_ssm_config(hf: dict, experts_held) -> ModelConfig:
    """The published keys as ``chipbench/architectures/nemotron_h.py`` reads
    them."""
    pattern = hf["hybrid_override_pattern"]
    if (len(pattern) != hf["num_hidden_layers"] or set(pattern) - set(_SSM_LETTERS)
            or hf.get("mlp_hidden_act", "relu2") != "relu2" or hf.get("attention_bias")
            or hf.get("mlp_bias") or hf.get("mamba_proj_bias") or hf.get("use_bias")):
        raise NotImplementedError(
            f"{hf['model_type']}: hybrid_override_pattern {pattern!r} beside num_hidden_layers="
            f"{hf['num_hidden_layers']}: each layer one of {sorted(_SSM_LETTERS)} (a '-' block "
            "is not modelled), mlp_hidden_act relu2, and no bias in a projection")
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
        rope_theta=None,
        rms_norm_eps=hf.get("norm_eps", hf.get("layer_norm_epsilon", 1e-5)),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        layer_types=tuple(_SSM_LETTERS[c] for c in pattern),
        ssm_num_heads=hf["mamba_num_heads"],
        ssm_head_dim=hf["mamba_head_dim"],
        ssm_state_size=hf["ssm_state_size"],
        ssm_n_groups=hf["n_groups"],
        ssm_conv_kernel=hf["conv_kernel"],
        ssm_chunk_size=hf["chunk_size"],
        ssm_conv_bias=hf.get("use_conv_bias", True),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        router_scoring="sigmoid",
        n_group=hf.get("n_group", 1),
        topk_group=hf.get("topk_group", 1),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        routed_scaling_factor=hf.get("routed_scaling_factor", 1.0),
        num_shared_experts=hf.get("n_shared_experts", 0),
        shared_expert_intermediate_size=(
            hf.get("moe_shared_expert_intermediate_size", 0) if hf.get("n_shared_experts") else 0),
        experts_held=experts_held,
        router_bias=True,
        mlp_activation="relu2",
    )


# Published model types whose attention layers are of two kinds by
# ``layer_types``, full and sliding-window, with query heads per layer, rope
# parameters per kind and a per-head output gate (Laguna).
WINDOWED_TYPES = ("laguna",)


def _windowed_config(hf: dict, experts_held) -> ModelConfig:
    """The published keys as ``chipbench/architectures/laguna.py`` reads
    them; what the file has no key for (the gate's and the router's
    nonlinearity) is the family's convention, listed under ``assumed`` in
    the benchmark's configuration of it."""
    kinds = list(hf.get("mlp_layer_types") or ["sparse"] * hf["num_hidden_layers"])
    dense = kinds.index("sparse") if "sparse" in kinds else len(kinds)
    if any(k != "sparse" for k in kinds[dense:]):
        raise NotImplementedError("a dense MLP layer after a sparse one is not implemented")
    if hf.get("gating", "per-head") != "per-head" or hf.get("moe_router_logit_softcapping"):
        raise NotImplementedError("only per-head gating and an uncapped router are implemented")
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        layer_types=tuple(hf["layer_types"]),
        heads_per_layer=tuple(hf["num_attention_heads_per_layer"]),
        sliding_window=hf["sliding_window"],
        rope_by_kind=hf["rope_parameters"],
        attn_gate=True,
        first_dense_layers=dense,
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        router_scoring="sigmoid",
        norm_topk_prob=hf.get("norm_topk_prob", True),
        routed_scaling_factor=hf.get("moe_routed_scaling_factor", 1.0),
        num_shared_experts=1,
        experts_held=experts_held,
    )


# Published model types whose key is wider than their value, with KV heads,
# rope's base and a sink logit by layer kind (``hybrid_layer_pattern``: 0 full,
# 1 sliding-window), and bias-chosen sigmoid experts without a shared one
# (MiMo-V2).
WIDE_KEY_TYPES = ("mimo_v2",)
_PATTERN_KINDS = ("full_attention", "sliding_attention")


def _wide_key_config(hf: dict, experts_held) -> ModelConfig:
    """The published keys as ``chipbench/architectures/mimo_v2.py`` reads
    them (the language model only: a checkpoint's towers and MTP layers are
    not loaded); what the file gives no equation for is listed under
    ``assumed`` in the benchmark's configuration of it."""
    L = hf["num_hidden_layers"]
    freq = list(hf.get("moe_layer_freq") or [1] * L)
    dense = freq.index(1) if 1 in freq else L
    if 0 in freq[dense:]:
        raise NotImplementedError("a dense MLP layer after a sparse one is not implemented")
    if hf.get("scoring_func", "sigmoid") != "sigmoid" or hf.get("n_shared_experts") \
            or hf.get("topk_method", "noaux_tc") != "noaux_tc" \
            or (hf.get("rope_scaling") or {}).get("rope_type", "default") != "default":
        raise NotImplementedError(
            "only sigmoid scores with a choice bias (noaux_tc), no shared expert and "
            "a plain rope are implemented")
    rotated = {"rope_type": "default",
               "partial_rotary_factor": hf.get("partial_rotary_factor", 1)}
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        window_kv_heads=hf.get("swa_num_key_value_heads", 0),
        head_dim=hf["head_dim"],
        v_head_dim=hf["v_head_dim"],
        attn_value_scale=hf.get("attention_value_scale", 1.0),
        rms_norm_eps=hf.get("layernorm_epsilon", 1e-5),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        layer_types=tuple(_PATTERN_KINDS[i] for i in hf["hybrid_layer_pattern"]),
        sliding_window=hf["sliding_window"],
        rope_by_kind={
            "full_attention": {**rotated, "rope_theta": hf["rope_theta"]},
            "sliding_attention": {**rotated, "rope_theta": hf["swa_rope_theta"]},
        },
        attn_sinks=tuple(kind for kind, key in zip(_PATTERN_KINDS, (
            "add_full_attention_sink_bias", "add_swa_attention_sink_bias")) if hf.get(key)),
        first_dense_layers=dense,
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        router_scoring="sigmoid",
        router_bias=True,
        n_group=hf.get("n_group", 1),
        topk_group=hf.get("topk_group", 1),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        routed_scaling_factor=hf.get("routed_scaling_factor") or 1.0,
        experts_held=experts_held,
    )


# Published model types that generate by diffusion over blocks on a
# Qwen3-MoE body (SDAR): QK-normed GQA, softmax-routed whole experts.
BLOCK_SPARSE_TYPES = ("sdar_moe",)


def _block_sparse_config(hf: dict) -> ModelConfig:
    """The published keys as ``chipbench/architectures/sdar_moe.py`` reads
    them. ``config.json`` gives neither block length nor schedule: the
    released usage's are taken unless the file brings keys of those names
    (the benchmark's configuration lists them under ``assumed``)."""
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1 \
            or hf.get("use_sliding_window") or hf.get("rope_scaling"):
        raise NotImplementedError(
            "sdar_moe with dense layers among the sparse ones, a sliding window or "
            "scaled rope is not implemented")
    return ModelConfig(
        name=hf["model_type"],
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim", hf["hidden_size"] // hf["num_attention_heads"]),
        rope_theta=float(hf.get("rope_theta", 1000000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        qk_norm=True,
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        router_scoring="softmax",
        norm_topk_prob=hf.get("norm_topk_prob", True),
        block_length=hf.get("block_length", 4),
        denoising_steps=hf.get("denoising_steps", 4),
        confidence_threshold=hf.get("confidence_threshold", 0.9),
        mask_token_id=hf.get("mask_token_id", 151669),
    )


def config_from_hf(path: str | Path, experts_held=None) -> ModelConfig:
    """``experts_held`` ``(rank, of)``: the share of a sparse model's routed
    experts to load (a model without a stated share refuses it)."""
    with open(Path(path) / "config.json") as f:
        hf = json.load(f)
    if hf.get("model_type") in LATENT_SPARSE_TYPES:
        return _latent_sparse_config(hf, experts_held)
    if hf.get("model_type") in WINDOWED_TYPES:
        return _windowed_config(hf, experts_held)
    if hf.get("model_type") in WIDE_KEY_TYPES:
        return _wide_key_config(hf, experts_held)
    if hf.get("model_type") in BLOCK_SPARSE_TYPES and experts_held is None:
        return _block_sparse_config(hf)
    if hf.get("model_type") in HYBRID_CONV_TYPES and experts_held is None:
        return _hybrid_conv_config(hf)
    if hf.get("model_type") in HYBRID_LINEAR_TYPES and experts_held is None:
        return _hybrid_linear_config(hf)
    if hf.get("model_type") in HYBRID_SSM_TYPES:
        return _hybrid_ssm_config(hf, experts_held)
    if experts_held is not None:
        raise ValueError(
            f"experts_held={experts_held} for model_type {hf.get('model_type')!r}: only "
            f"{LATENT_SPARSE_TYPES + WINDOWED_TYPES + WIDE_KEY_TYPES + HYBRID_SSM_TYPES} "
            "state a share"
        )
    head_dim = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return ModelConfig(
        name=hf.get("model_type", "llama"),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=head_dim,
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        # Qwen2-family checkpoints carry qkv biases (the architecture's
        # one delta from llama; qwen3 dropped them again).
        attn_qkv_bias=hf.get("model_type") == "qwen2",
        # Ouro (looped): the layers run total_ut_steps times a token, with
        # sandwich norms (modeling_ouro.py: input_layernorm_2,
        # post_attention_layernorm_2) and an exit gate. ModelConfig
        # refuses an early_exit_threshold under 1: adaptive exit is not
        # implemented.
        ut_steps=hf.get("total_ut_steps", 1),
        early_exit_threshold=hf.get("early_exit_threshold", 1.0),
        sandwich_norm=hf.get("model_type") == "ouro",
    )


def _fuse_np(arrs: list[np.ndarray], tp: int) -> np.ndarray:
    """numpy twin of model.fuse_qkv/fuse_gu: concatenate per-shard blocks
    ``[a0_s | a1_s | ...]`` along the output axis, host-side."""
    splits = [np.split(a, tp, axis=-1) for a in arrs]
    return np.concatenate(
        [blk for s in range(tp) for blk in (sp[s] for sp in splits)], axis=-1
    )


def _read_state_dict(path: Path) -> dict[str, np.ndarray]:
    """All tensors from safetensors shards or torch .bin files, as numpy."""
    tensors: dict[str, np.ndarray] = {}
    st_files = sorted(path.glob("*.safetensors"))
    if st_files:
        from safetensors import safe_open

        for f in st_files:
            with safe_open(f, framework="np") as sf:
                for key in sf.keys():
                    tensors[key] = sf.get_tensor(key)
        return tensors
    bin_files = sorted(path.glob("pytorch_model*.bin"))
    if not bin_files:
        raise FileNotFoundError(f"no safetensors or torch checkpoints in {path}")
    import torch

    for f in bin_files:
        sd = torch.load(f, map_location="cpu", weights_only=True)
        for key, t in sd.items():
            tensors[key] = t.float().numpy()
    return tensors


def _quantize_np(w: np.ndarray) -> dict[str, Any]:
    """Host-side numpy twin of model.quantize_weight (per-output-channel
    symmetric int8) — quantizing BEFORE any device transfer is what lets
    a 16 GB chip load a model whose bf16 weights alone would not fit."""
    scale = np.maximum(np.abs(w).max(axis=-2, keepdims=True) / 127.0, 1e-8)
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return {"w": q, "scale": scale.astype(np.float32)}


def _half_split(w: np.ndarray, dr: int) -> np.ndarray:
    """The last ``dr`` output columns of ``w`` ``[..., in, out]`` from the
    checkpoint's interleaved rope pairs ``(2i, 2i + 1)`` to the half-split
    ones ``(i, i + dr/2)`` that ``model.rope_apply`` turns (the published
    code makes the same permutation of q and k at run time)."""
    order = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    return np.concatenate([w[..., :-dr], w[..., -dr:][..., order]], axis=-1)


def _load_latent_sparse(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """The latent-attention, sigmoid-routed model's tree
    (``model._init_latent_attention`` / ``_init_shared_sparse_mlp``) from
    the checkpoint's names: ``self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
    kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}``, a dense
    layer's ``mlp.{gate,up,down}_proj``, a sparse layer's ``mlp.gate``,
    ``mlp.experts.<e>.*`` (the HELD experts only) and
    ``mlp.shared_experts.*``."""
    np_dt = np.dtype(dt)
    L, Ld = cfg.num_layers, cfg.first_dense_layers
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv = cfg.v_head_dim
    lo, hi = cfg.experts_held_range

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    def proj(l: int, name: str) -> np.ndarray:
        return t(f"model.layers.{l}.{name}.weight").T  # [in, out]

    def stack(name: str, layers=range(L), fix=lambda w: w) -> np.ndarray:
        return np.asarray(np.stack([fix(proj(l, name)) for l in layers]), np_dt)

    def norms(name: str) -> np.ndarray:
        return np.asarray(
            np.stack([t(f"model.layers.{l}.{name}.weight") for l in range(L)]), np_dt)

    def q_b(w):  # per head [nope | rope]: the rope part of every head
        w = w.reshape(w.shape[0], H, dn + dr)
        return _half_split(w, dr).reshape(w.shape[0], H * (dn + dr))

    layers = {
        "attn_norm": norms("input_layernorm"),
        "mlp_norm": norms("post_attention_layernorm"),
        "wq_a": stack("self_attn.q_a_proj"),
        "q_norm": norms("self_attn.q_a_layernorm"),
        "wq_b": stack("self_attn.q_b_proj", fix=q_b),
        "wkv_a": stack("self_attn.kv_a_proj_with_mqa", fix=lambda w: _half_split(w, dr)),
        "kv_norm": norms("self_attn.kv_a_layernorm"),
        "wk_b": stack("self_attn.kv_b_proj", fix=lambda w: np.transpose(
            w.reshape(w.shape[0], H, dn + dv)[..., :dn], (1, 2, 0))),
        "wv_b": stack("self_attn.kv_b_proj", fix=lambda w: np.transpose(
            w.reshape(w.shape[0], H, dn + dv)[..., dn:], (1, 0, 2))),
        "wo": stack("self_attn.o_proj"),
    }
    sparse = range(Ld, L)

    def gate_up(prefix: str, layers_):
        return np.asarray(np.concatenate(
            [stack(f"{prefix}.gate_proj", layers_), stack(f"{prefix}.up_proj", layers_)],
            axis=-1), np_dt)

    moe = {
        "w_router": stack("mlp.gate", sparse),
        # one array a sparse layer (model._init_shared_sparse_mlp)
        "w_gu": tuple(np.stack(
            [gate_up(f"mlp.experts.{e}", [l])[0] for e in range(lo, hi)]) for l in sparse),
        "w_down": tuple(np.stack(
            [stack(f"mlp.experts.{e}.down_proj", [l])[0] for e in range(lo, hi)])
            for l in sparse),
    }
    if cfg.num_shared_experts:
        moe["shared_wgu"] = gate_up("mlp.shared_experts", sparse)
        moe["shared_down"] = stack("mlp.shared_experts.down_proj", sparse)
    params: dict[str, Any] = {"layers": layers, "moe": moe}
    if Ld:
        params["dense_mlp"] = {
            "wgu": np.asarray(_fuse_np(
                [stack("mlp.gate_proj", range(Ld)), stack("mlp.up_proj", range(Ld))], tp),
                np_dt),
            "w_down": stack("mlp.down_proj", range(Ld)),
        }
    return params


def _load_hybrid_conv(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """The tree of a model with conv and attention layers
    (``model.init_params``: ``layers`` the two norms of every layer,
    ``conv`` / ``attn`` one entry a layer of that kind, ``dense_mlp``,
    ``moe``) from the checkpoint's names: ``operator_norm``, ``ffn_norm``;
    a conv layer's ``conv.{in_proj, conv, out_proj}`` (``conv.conv.weight
    [h, 1, L]`` becomes the taps ``[L, h]``; ``in_proj``'s columns are ``[B |
    C | x]`` as published); an attention layer's ``self_attn.{q_proj,
    k_proj, v_proj, out_proj, q_layernorm, k_layernorm}`` (rotate-half
    rope: no permutation); a dense layer's ``feed_forward.{w1, w3, w2}``
    (gate, up, down); a sparse layer's ``feed_forward.gate``,
    ``feed_forward.expert_bias`` and ``feed_forward.experts.<e>.{w1, w3,
    w2}``; the final norm is ``model.embedding_norm``."""
    np_dt = np.dtype(dt)
    L, Ld, E = cfg.num_layers, cfg.first_dense_layers, cfg.num_experts

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    def proj(l: int, name: str) -> np.ndarray:
        return t(f"model.layers.{l}.{name}.weight").T  # [in, out]

    def stack(name: str, layers, fix=lambda w: w) -> np.ndarray:
        return np.asarray(np.stack([fix(proj(l, name)) for l in layers]), np_dt)

    def norms(name: str, layers=range(L)) -> np.ndarray:
        return np.asarray(
            np.stack([t(f"model.layers.{l}.{name}.weight") for l in layers]), np_dt)

    conv, attn, sparse = cfg.layers_of("conv"), cfg.layers_of("attention"), range(Ld, L)

    def gate_up(prefix: str, l: int) -> np.ndarray:
        return np.concatenate([proj(l, f"{prefix}.w1"), proj(l, f"{prefix}.w3")], axis=-1)

    params: dict[str, Any] = {
        "layers": {"attn_norm": norms("operator_norm"), "mlp_norm": norms("ffn_norm")},
        "conv": {
            "in_proj": stack("conv.in_proj", conv),
            # published [h, 1, L] -> [L, h]: tap j, L - 1 - j positions back
            "conv_w": np.asarray(np.stack(
                [t(f"model.layers.{l}.conv.conv.weight")[:, 0, :].T for l in conv]), np_dt),
            "out_proj": stack("conv.out_proj", conv),
        },
        "attn": {
            "wqkv": np.asarray(_fuse_np(
                [stack(f"self_attn.{n}", attn) for n in ("q_proj", "k_proj", "v_proj")],
                tp), np_dt),
            "wo": stack("self_attn.out_proj", attn),
            "q_layernorm": norms("self_attn.q_layernorm", attn),
            "k_layernorm": norms("self_attn.k_layernorm", attn),
        },
        "moe": {
            "w_router": stack("feed_forward.gate", sparse),
            # one array a sparse layer (model._init_shared_sparse_mlp)
            "w_gu": tuple(np.asarray(np.stack(
                [gate_up(f"feed_forward.experts.{e}", l) for e in range(E)]), np_dt)
                for l in sparse),
            "w_down": tuple(np.asarray(np.stack(
                [proj(l, f"feed_forward.experts.{e}.w2") for e in range(E)]), np_dt)
                for l in sparse),
        },
    }
    if cfg.router_bias:
        params["moe"]["expert_bias"] = np.stack(
            [t(f"model.layers.{l}.feed_forward.expert_bias") for l in sparse])
    if Ld:
        params["dense_mlp"] = {
            "wgu": np.asarray(_fuse_np(
                [stack("feed_forward.w1", range(Ld)), stack("feed_forward.w3", range(Ld))],
                tp), np_dt),
            "w_down": stack("feed_forward.w2", range(Ld)),
        }
    return params


def _load_hybrid_ssm(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """The tree of a model of one-sub-layer blocks (``model.init_params``:
    ``layers`` the one norm a block, ``ssm`` / ``attn`` / ``moe`` one entry a
    layer of that kind) from the checkpoint's names, ASSUMED from the family's
    code (no checkpoint is here to try): ``backbone.embeddings``,
    ``backbone.layers.N.norm`` and ``backbone.layers.N.mixer.*`` whatever the
    block is: a Mamba-2 mixer's ``in_proj`` (``[z | x | B | C | dt]`` rows,
    split into ``w_zx`` and ``w_dt``), ``conv1d.{weight [channels, 1, K],
    bias}``, ``A_log``, ``D``, ``dt_bias``, ``norm`` and ``out_proj``; an
    attention block's ``{q_proj, k_proj, v_proj, o_proj}`` (no rope, so no
    permutation); an expert block's ``gate.{weight [E, h],
    e_score_correction_bias}``, ``experts.E.{up_proj, down_proj}`` for the HELD
    experts (stored with zero columns / rows up to
    ``cfg.expert_stored_width``) and ``shared_experts.{up_proj, down_proj}``;
    the final norm is ``backbone.norm_f``."""
    np_dt = np.dtype(dt)

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    def proj(l: int, name: str) -> np.ndarray:
        return t(f"backbone.layers.{l}.mixer.{name}.weight").T  # [in, out]

    def stack(name: str, layers, fix=lambda w: w) -> np.ndarray:
        return np.asarray(np.stack([fix(proj(l, name)) for l in layers]), np_dt)

    def leaves(name: str, layers, dtype=np_dt) -> np.ndarray:
        return np.asarray(np.stack([t(f"backbone.layers.{l}.{name}") for l in layers]), dtype)

    ssm, attn, moe = cfg.layers_of("ssm"), cfg.layers_of("attention"), cfg.sparse_layers
    H = cfg.ssm_num_heads
    lo, hi = cfg.experts_held_range
    im, pad = cfg.moe_intermediate_size, cfg.expert_stored_width - cfg.moe_intermediate_size

    def held(l: int, name: str, pad_axis: int) -> np.ndarray:
        w = np.stack([proj(l, f"experts.{e}.{name}") for e in range(lo, hi)])   # [Eh, in, out]
        width = [(0, 0)] * 3
        width[pad_axis] = (0, pad)
        return np.asarray(np.pad(w, width), np_dt)

    return {
        "embed": np.asarray(t("backbone.embeddings.weight"), np_dt),
        "final_norm": np.asarray(t("backbone.norm_f.weight"), np_dt),
        "layers": {"attn_norm": leaves("norm.weight", range(cfg.num_layers))},
        "ssm": {
            "w_zx": stack("in_proj", ssm, lambda w: w[:, :-H]),
            "w_dt": stack("in_proj", ssm, lambda w: w[:, -H:]),
            # published [channels, 1, K] -> [K, channels]: tap j, K - 1 - j back
            "conv_w": np.asarray(np.stack(
                [t(f"backbone.layers.{l}.mixer.conv1d.weight")[:, 0, :].T for l in ssm]), np_dt),
            "conv_b": leaves("mixer.conv1d.bias", ssm),
            "A_log": leaves("mixer.A_log", ssm, np.float32),
            "D": leaves("mixer.D", ssm, np.float32),
            "dt_bias": leaves("mixer.dt_bias", ssm, np.float32),
            "ssm_norm": leaves("mixer.norm.weight", ssm),
            "w_out": stack("out_proj", ssm),
        },
        "attn": {
            "wqkv": np.asarray(_fuse_np(
                [stack(n, attn) for n in ("q_proj", "k_proj", "v_proj")], tp), np_dt),
            "wo": stack("o_proj", attn),
        },
        "moe": {
            "w_router": stack("gate", moe),
            "expert_bias": leaves("mixer.gate.e_score_correction_bias", moe, np.float32),
            "w_gu": tuple(held(l, "up_proj", 2) for l in moe),
            "w_down": tuple(held(l, "down_proj", 1) for l in moe),
            "shared_wgu": stack("shared_experts.up_proj", moe),
            "shared_down": stack("shared_experts.down_proj", moe),
        },
    }


def _load_hybrid_linear(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """The tree of a model with linear-attention and attention layers
    (``model.init_params``: ``layers`` the two OUTPUT norms and the SwiGLU of
    every layer, ``linear`` / ``attn`` one entry a layer of that kind) from
    the checkpoint's names, ASSUMED from the family's code (no checkpoint is
    here to try): ``post_attention_layernorm``, ``post_feedforward_layernorm``,
    ``mlp.{gate_proj, up_proj, down_proj}``; a linear layer's
    ``linear_attn.{q_proj, k_proj, v_proj, g_proj, b_proj, a_proj, o_proj}``,
    ``linear_attn.{q_conv1d, k_conv1d, v_conv1d}.weight`` (``[channels, 1,
    K]`` each, which become the taps ``[K, q | k | v]``),
    ``linear_attn.{A_log, dt_bias}`` and ``linear_attn.o_norm``; an attention
    layer's ``self_attn.{q_proj, k_proj, v_proj, o_proj, q_norm, k_norm}``
    (no rope, so no permutation); the final norm is ``model.norm``."""
    np_dt = np.dtype(dt)
    L = cfg.num_layers

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    def proj(l: int, name: str) -> np.ndarray:
        return t(f"model.layers.{l}.{name}.weight").T  # [in, out]

    def stack(name: str, layers, fix=lambda w: w) -> np.ndarray:
        return np.asarray(np.stack([fix(proj(l, name)) for l in layers]), np_dt)

    def leaves(name: str, layers, dtype=np_dt) -> np.ndarray:
        return np.asarray(np.stack([t(f"model.layers.{l}.{name}") for l in layers]), dtype)

    lin, attn = cfg.layers_of("linear"), cfg.layers_of("attention")

    def cat(names, layers) -> np.ndarray:
        return np.concatenate([stack(n, layers) for n in names], axis=-1)

    return {
        "layers": {
            "attn_norm": leaves("post_attention_layernorm.weight", range(L)),
            "mlp_norm": leaves("post_feedforward_layernorm.weight", range(L)),
            "wgu": np.asarray(_fuse_np(
                [stack("mlp.gate_proj", range(L)), stack("mlp.up_proj", range(L))], tp), np_dt),
            "w_down": stack("mlp.down_proj", range(L)),
        },
        "linear": {
            "w_qkv": cat([f"linear_attn.{n}_proj" for n in "qkv"], lin),
            "w_z": stack("linear_attn.g_proj", lin),
            "w_ba": cat(["linear_attn.b_proj", "linear_attn.a_proj"], lin),
            # published [channels, 1, K] a part -> [K, q | k | v]: tap j, K - 1 - j back
            "conv_w": np.asarray(np.stack([np.concatenate(
                [t(f"model.layers.{l}.linear_attn.{n}_conv1d.weight")[:, 0, :].T for n in "qkv"],
                axis=-1) for l in lin]), np_dt),
            "A_log": leaves("linear_attn.A_log", lin, np.float32),
            "dt_bias": leaves("linear_attn.dt_bias", lin, np.float32),
            "o_norm": leaves("linear_attn.o_norm.weight", lin),
            "w_out": stack("linear_attn.o_proj", lin),
        },
        "attn": {
            "wqkv": np.asarray(_fuse_np(
                [stack(f"self_attn.{n}", attn) for n in ("q_proj", "k_proj", "v_proj")],
                tp), np_dt),
            "wo": stack("self_attn.o_proj", attn),
            "q_layernorm": leaves("self_attn.q_norm.weight", attn),
            "k_layernorm": leaves("self_attn.k_norm.weight", attn),
        },
    }


def _load_windowed(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """The tree of a model with full and window attention layers
    (``model._init_attention_by_kind``: ``layers`` the two norms of every
    layer, ``attn`` / ``attn_window`` one entry a layer of that kind, each
    with its own query heads; ``dense_mlp``, ``moe`` as the latent sparse
    model's) from the checkpoint's names: ``input_layernorm``,
    ``post_attention_layernorm``; ``self_attn.{q_proj, k_proj, v_proj,
    o_proj}`` and the gate ``self_attn.g_proj [heads, h]`` (rotate-half
    rope: no permutation); a dense layer's ``mlp.{gate,up,down}_proj``; a
    sparse layer's ``mlp.gate``, ``mlp.experts.<e>.*`` (the HELD experts
    only) and ``mlp.shared_expert.*``."""
    np_dt = np.dtype(dt)
    L, Ld = cfg.num_layers, cfg.first_dense_layers
    lo, hi = cfg.experts_held_range

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    def proj(l: int, name: str) -> np.ndarray:
        return t(f"model.layers.{l}.{name}.weight").T  # [in, out]

    def stack(name: str, layers) -> np.ndarray:
        return np.asarray(np.stack([proj(l, name) for l in layers]), np_dt)

    def norms(name: str) -> np.ndarray:
        return np.asarray(
            np.stack([t(f"model.layers.{l}.{name}.weight") for l in range(L)]), np_dt)

    def gate_up(prefix: str, layers) -> np.ndarray:
        return np.concatenate(
            [stack(f"{prefix}.gate_proj", layers), stack(f"{prefix}.up_proj", layers)], axis=-1)

    def attention(layers) -> dict[str, Any]:
        return {
            "wqkv": np.asarray(_fuse_np(
                [stack(f"self_attn.{n}", layers) for n in ("q_proj", "k_proj", "v_proj")],
                tp), np_dt),
            "wo": stack("self_attn.o_proj", layers),
            "wg": stack("self_attn.g_proj", layers),
        }

    sparse = range(Ld, L)
    params: dict[str, Any] = {
        "layers": {"attn_norm": norms("input_layernorm"),
                   "mlp_norm": norms("post_attention_layernorm")},
        "attn": attention(cfg.layers_of("attention")),
        "attn_window": attention(cfg.layers_of("window")),
        "moe": {
            "w_router": stack("mlp.gate", sparse),
            # one array a sparse layer (model._init_shared_sparse_mlp)
            "w_gu": tuple(np.stack(
                [gate_up(f"mlp.experts.{e}", [l])[0] for e in range(lo, hi)]) for l in sparse),
            "w_down": tuple(np.stack(
                [stack(f"mlp.experts.{e}.down_proj", [l])[0] for e in range(lo, hi)])
                for l in sparse),
            "shared_wgu": gate_up("mlp.shared_expert", sparse),
            "shared_down": stack("mlp.shared_expert.down_proj", sparse),
        },
    }
    if Ld:
        params["dense_mlp"] = {
            "wgu": np.asarray(_fuse_np(
                [stack("mlp.gate_proj", range(Ld)), stack("mlp.up_proj", range(Ld))], tp),
                np_dt),
            "w_down": stack("mlp.down_proj", range(Ld)),
        }
    return params


def _load_wide_key(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """The tree of a model of the wide-key page (``model.
    _init_attention_by_kind`` with ``cfg.wide_key``: ``attn`` / ``attn_window``
    one entry a layer of that kind, ``wqkv`` = ``[q | k | v]`` at the kind's
    KV heads and the two widths, ``sink`` float32 where the kind has one;
    ``dense_mlp``; ``moe`` with the choice bias and no shared expert) from
    the checkpoint's names: ``self_attn.{q_proj, k_proj, v_proj, o_proj}``,
    ``self_attn.attention_sink_bias [heads]``, ``mlp.gate`` with
    ``mlp.gate.e_score_correction_bias [E]``, ``mlp.experts.<e>.*`` (the HELD
    experts only). Rotate-half rope: no permutation."""
    np_dt = np.dtype(dt)
    L, Ld = cfg.num_layers, cfg.first_dense_layers
    lo, hi = cfg.experts_held_range

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    def stack(name: str, layers) -> np.ndarray:
        return np.asarray(
            np.stack([t(f"model.layers.{l}.{name}.weight").T for l in layers]), np_dt)

    def norms(name: str) -> np.ndarray:
        return np.asarray(
            np.stack([t(f"model.layers.{l}.{name}.weight") for l in range(L)]), np_dt)

    def gate_up(prefix: str, layers) -> np.ndarray:
        return np.concatenate(
            [stack(f"{prefix}.gate_proj", layers), stack(f"{prefix}.up_proj", layers)], axis=-1)

    def attention(kind: str) -> dict[str, Any]:
        layers = cfg.layers_of(kind)
        group = {
            "wqkv": np.concatenate(
                [stack(f"self_attn.{n}", layers) for n in ("q_proj", "k_proj", "v_proj")],
                axis=-1),
            "wo": stack("self_attn.o_proj", layers),
        }
        if cfg.has_sink(kind):
            group["sink"] = np.stack(
                [t(f"model.layers.{l}.self_attn.attention_sink_bias") for l in layers])
        return group

    sparse = range(Ld, L)
    params: dict[str, Any] = {
        "layers": {"attn_norm": norms("input_layernorm"),
                   "mlp_norm": norms("post_attention_layernorm")},
        "attn": attention("attention"),
        "attn_window": attention("window"),
        "moe": {
            "w_router": stack("mlp.gate", sparse),
            "expert_bias": np.stack(
                [t(f"model.layers.{l}.mlp.gate.e_score_correction_bias") for l in sparse]),
            # one array a sparse layer (model._init_shared_sparse_mlp)
            "w_gu": tuple(np.stack(
                [gate_up(f"mlp.experts.{e}", [l])[0] for e in range(lo, hi)]) for l in sparse),
            "w_down": tuple(np.stack(
                [stack(f"mlp.experts.{e}.down_proj", [l])[0] for e in range(lo, hi)])
                for l in sparse),
        },
    }
    if Ld:
        params["dense_mlp"] = {
            "wgu": np.asarray(_fuse_np(
                [stack("mlp.gate_proj", range(Ld)), stack("mlp.up_proj", range(Ld))], tp),
                np_dt),
            "w_down": stack("mlp.down_proj", range(Ld)),
        }
    return params


def _load_block_sparse(cfg: ModelConfig, sd: dict, dt, tp: int) -> dict[str, Any]:
    """An sdar_moe checkpoint into the tree ``model.init_params`` builds for
    it (``layers``: norms, ``wqkv``, ``wo``, the head norms; ``moe``: router
    and every expert), under the base family's names: ``self_attn.{q,k,v,
    o}_proj``, ``q_norm`` / ``k_norm``, ``mlp.gate``, ``mlp.experts.E.{gate,
    up,down}_proj``."""
    L, E = cfg.num_layers, cfg.num_experts
    np_dt = np.dtype(dt)

    def w(l: int, name: str) -> np.ndarray:
        return np.asarray(sd[f"model.layers.{l}.{name}.weight"], np.float32)

    def stack(name: str, transpose: bool = True) -> np.ndarray:
        return np.asarray(np.stack(
            [w(l, name).T if transpose else w(l, name) for l in range(L)]), np_dt)

    def gate_up(l: int, e: int) -> np.ndarray:
        return np.concatenate([w(l, f"mlp.experts.{e}.gate_proj").T,
                               w(l, f"mlp.experts.{e}.up_proj").T], axis=1)

    return {
        "layers": {
            "attn_norm": stack("input_layernorm", False),
            "mlp_norm": stack("post_attention_layernorm", False),
            "wqkv": np.asarray(_fuse_np(
                [stack(f"self_attn.{n}") for n in ("q_proj", "k_proj", "v_proj")], tp), np_dt),
            "wo": stack("self_attn.o_proj"),
            "q_layernorm": stack("self_attn.q_norm", False),
            "k_layernorm": stack("self_attn.k_norm", False),
        },
        "moe": {
            "w_router": stack("mlp.gate"),
            # one array a sparse layer (model._init_shared_sparse_mlp)
            "w_gu": tuple(np.asarray(np.stack([gate_up(l, e) for e in range(E)]), np_dt)
                          for l in range(L)),
            "w_down": tuple(np.asarray(np.stack(
                [w(l, f"mlp.experts.{e}.down_proj").T for e in range(E)]), np_dt)
                for l in range(L)),
        },
    }


def load_hf_llama(
    path: str | Path, dtype=None, tp: int = 1, quant: str | None = None,
    experts_held: tuple[int, int] | None = None,
) -> tuple[ModelConfig, Any]:
    """Returns (ModelConfig, params pytree) from an HF llama/qwen2/ouro/
    axk1/lfm2_moe/laguna/mimo_v2 checkpoint (``experts_held``: see :func:`config_from_hf`).

    ``tp`` fixes the shard-blocked layout of the fused wqkv/wgu projections
    (model.fuse_qkv/fuse_gu) and must match the serving mesh's tp axis.
    ``quant='int8'`` quantizes the projections host-side so the device
    only ever sees the int8 footprint (the llama3-8b-on-one-chip mode).

    The returned pytree lives on HOST (numpy; bf16 via ml_dtypes): the
    caller's placement (EngineCore device_put / shard_params) is the
    FIRST device transfer, so sharded serving never materializes the
    full model on one chip — a 70B pod loads rank-local shards only.
    """
    if quant not in (None, "int8"):
        raise ValueError(f"unknown quantization {quant!r}")
    path = Path(path)
    cfg = config_from_hf(path, experts_held)
    dt = dtype or cfg.jax_dtype
    sd = _read_state_dict(path)

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

    if cfg.latent or cfg.layer_groups or cfg.block_length:
        if quant is not None or tp != 1:
            raise NotImplementedError(
                f"quant={quant!r} / tp={tp} for {cfg.name!r}: experts, latent "
                "projections, conv, linear-attention and mamba operators and layers of more than one kind load "
                "unquantised, in the tp=1 layout"
            )
        np_dt = np.dtype(dt)
        load = (_load_hybrid_conv if cfg.hybrid else
                _load_hybrid_linear if cfg.linear else
                _load_hybrid_ssm if cfg.ssm else
                _load_wide_key if cfg.wide_key else
                _load_windowed if cfg.windowed else
                _load_block_sparse if cfg.block_length else _load_latent_sparse)
        params = load(cfg, sd, dt, tp)
        final_norm = "model.embedding_norm.weight" if cfg.hybrid else "model.norm.weight"
        params["fuse_tp"] = np.asarray(tp, np.int32)
        if not cfg.ssm:   # (nemotron_h names its trunk ``backbone``: _load_hybrid_ssm)
            params.update({
                "embed": np.asarray(t("model.embed_tokens.weight"), np_dt),
                "final_norm": np.asarray(t(final_norm), np_dt),
            })
        if not cfg.tie_embeddings:
            params["lm_head"] = np.asarray(t("lm_head.weight").T, np_dt)
        log.info("loaded %s: %d layers, vocab %d, routed experts [%d, %d) of %d",
                 path, cfg.num_layers, cfg.vocab_size, *cfg.experts_held_range,
                 cfg.num_experts)
        return cfg, params

    def proj(i: int, name: str) -> np.ndarray:
        return t(f"model.layers.{i}.{name}.weight").T  # [in, out]

    def stack(name: str) -> np.ndarray:
        return np.stack([proj(i, name) for i in range(cfg.num_layers)])

    L = cfg.num_layers
    layers = {
        "attn_norm": np.stack([t(f"model.layers.{i}.input_layernorm.weight") for i in range(L)]),
        "mlp_norm": np.stack(
            [t(f"model.layers.{i}.post_attention_layernorm.weight") for i in range(L)]
        ),
        # Host-side numpy fuse (same shard-blocked layout as model.fuse_qkv
        # / fuse_gu): the two largest weight groups must not round-trip
        # through the device during loading — at 70B scale that double
        # transfer OOMs a single chip before serving even starts.
        "wqkv": _fuse_np(
            [
                stack("self_attn.q_proj"),
                stack("self_attn.k_proj"),
                stack("self_attn.v_proj"),
            ],
            tp,
        ),
        "wo": stack("self_attn.o_proj"),
        "wgu": _fuse_np([stack("mlp.gate_proj"), stack("mlp.up_proj")], tp),
        "w_down": stack("mlp.down_proj"),
    }
    if cfg.attn_qkv_bias:
        def bias(name: str) -> np.ndarray:
            return np.stack(
                [t(f"model.layers.{i}.{name}.bias") for i in range(L)]
            )

        layers["bqkv"] = _fuse_np(
            [
                bias("self_attn.q_proj"),
                bias("self_attn.k_proj"),
                bias("self_attn.v_proj"),
            ],
            tp,
        )
    if cfg.sandwich_norm:
        for ours, theirs in (("attn_post_norm", "input_layernorm_2"),
                             ("mlp_post_norm", "post_attention_layernorm_2")):
            layers[ours] = np.stack(
                [t(f"model.layers.{i}.{theirs}.weight") for i in range(L)]
            )
    np_dt = np.dtype(dt)  # bf16 numpy dtype via jax's ml_dtypes registration

    def place(name: str, v: np.ndarray):
        if quant == "int8" and name in ("wqkv", "wo", "wgu", "w_down"):
            return _quantize_np(v)  # projections int8; norms/bias at dt
        return np.asarray(v, np_dt)

    params: dict[str, Any] = {
        "embed": np.asarray(t("model.embed_tokens.weight"), np_dt),
        "layers": {k: place(k, v) for k, v in layers.items()},
        "final_norm": np.asarray(t("model.norm.weight"), np_dt),
        # The fuse layout is tp-dependent; record it so serving can verify
        # params match the mesh (EngineCore asserts fuse_tp == mesh tp).
        "fuse_tp": np.asarray(tp, np.int32),
    }
    if cfg.ut_steps > 1:
        params["exit_gate"] = {  # Linear(h, 1): weight [1, h], bias [1]
            "w": np.asarray(t("model.early_exit_gate.weight").reshape(-1), np_dt),
            "b": np.asarray(t("model.early_exit_gate.bias").reshape(()), np_dt),
        }
    if not cfg.tie_embeddings:
        head = t("lm_head.weight").T
        params["lm_head"] = (
            _quantize_np(head) if quant == "int8" else np.asarray(head, np_dt)
        )
    log.info(
        "loaded %s: %d layers, vocab %d%s", path, L, cfg.vocab_size,
        " (int8 weight-only)" if quant == "int8" else "",
    )
    return cfg, params
