"""A configuration file is the model's published ``config.json`` keys at
the top level, as they are run, plus how this repo serves it.

    source, reduced, assumed, deployment   what it is and how it was cut
    model_type                             names its module in architectures/
    <published keys>                       the sizes, under the published names
    serve: {quant, engine: {...}}          weight type and EngineConfig overrides

:func:`model_fields` maps the published keys onto the program's
``ModelConfig`` fields with the key map of the configuration's
architecture (``chipbench/architectures/<model_type>.py``).
"""

from __future__ import annotations

import json
from pathlib import Path

from chipbench import architectures

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    cfg["name"] = name
    return cfg


def model_fields(cfg: dict) -> dict:
    """``ModelConfig(**model_fields(cfg))`` is the model as published."""
    arch = architectures.of(cfg)
    fields = arch.derived(cfg)
    fields.update((ours, cfg[theirs]) for theirs, ours in arch.KEYS.items() if theirs in cfg)
    fields["name"] = cfg["name"]
    return fields


def engine_overrides(cfg: dict) -> dict:
    """EngineConfig overrides; JSON lists become the tuples it expects."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg["serve"]["engine"].items()}
