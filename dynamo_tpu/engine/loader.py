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


def config_from_hf(path: str | Path) -> ModelConfig:
    with open(Path(path) / "config.json") as f:
        hf = json.load(f)
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


def load_hf_llama(
    path: str | Path, dtype=None, tp: int = 1, quant: str | None = None
) -> tuple[ModelConfig, Any]:
    """Returns (ModelConfig, params pytree) from an HF llama/qwen2/ouro
    checkpoint.

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
    cfg = config_from_hf(path)
    dt = dtype or cfg.jax_dtype
    sd = _read_state_dict(path)

    def t(key: str) -> np.ndarray:
        return np.asarray(sd[key], np.float32)

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
