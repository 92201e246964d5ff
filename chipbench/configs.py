"""A configuration file is the model's published ``config.json`` keys at
the top level, as they are run, plus how this repo serves it.

    source, reduced, assumed, deployment   what it is and how it was cut
    <published keys>                       hidden_size, num_hidden_layers, ...
    serve: {quant, engine: {...}}          weight type and EngineConfig overrides

:func:`model_fields` maps the published keys onto the program's
``ModelConfig`` fields; nothing else in the benchmark knows either naming.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent

# published key -> ModelConfig field
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attn_qkv_bias",
    "torch_dtype": "dtype",
}


def load_config(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    cfg["name"] = name
    return cfg


def model_fields(cfg: dict) -> dict:
    """``ModelConfig(**model_fields(cfg))`` is the model as published."""
    fields = {ours: cfg[theirs] for theirs, ours in _KEYS.items() if theirs in cfg}
    fields.setdefault(
        "head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    fields["name"] = cfg["name"]
    return fields


def engine_overrides(cfg: dict) -> dict:
    """EngineConfig overrides; JSON lists become the tuples it expects."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg["serve"]["engine"].items()}
