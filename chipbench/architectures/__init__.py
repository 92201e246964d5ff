"""Architectures: one module here per published ``model_type``, found by
that name the way traffic generators and readers are. Nothing else in the
benchmark knows a model's published keys, the leaves of the engine's
parameter tree, or how an architecture's bytes and operations are counted.

A module's surface (``SURFACE``; :func:`get` refuses a module that lacks
any of it):

- ``KEYS``: published ``config.json`` key -> ``ModelConfig`` field, the
  whole map for this architecture;
- ``derived(cfg) -> dict``: the ``ModelConfig`` fields a published
  configuration may leave out, worked out from those it has (``head_dim``);
- ``reference_logits(params, mf, ids, rows, **options)``: the plain float32
  forward pass on the engine's own parameter tree, de-quantised piece by
  piece, ``[len(rows), vocab]``. ``mf`` are the ``ModelConfig`` fields;
- what the yardstick needs from shapes: ``decode_weight_bytes(mf, quant,
  observed)``, ``kv_bytes_per_token(mf, kv_bytes)``,
  ``attn_decode_bytes_per_layer(context_tokens, mf, block_size, kv_bytes)``,
  ``forward_flops_per_token(mf, context)``.

The tolerance of the comparison (``reference.check.LOGPROB_ATOL``) is not
part of it: an architecture may not bring its own.
"""

from __future__ import annotations

import importlib
import pkgutil
from collections.abc import Callable
from dataclasses import dataclass

SURFACE = ("KEYS", "derived", "reference_logits", "decode_weight_bytes",
           "kv_bytes_per_token", "attn_decode_bytes_per_layer",
           "forward_flops_per_token")


@dataclass(frozen=True)
class Observed:
    """What the program's always-on counters say of the measured window,
    for a count that depends on the traffic and not on shapes alone: a
    sparse model's decode step reads the experts its batch touches. A
    field is None where the reader could not tell."""

    # live lanes per decode dispatch, averaged over the window's dispatches
    decode_lanes_mean: float | None = None
    # counter(name, labels=None): the window's change of any counter of the
    # worker's /metrics, or None; for what an architecture counts itself
    counter: Callable[..., float | None] = lambda name, labels=None: None


UNKNOWN = Observed()


class UnknownArchitecture(ValueError):
    """No module here is named after this ``model_type``."""


def known() -> list[str]:
    return sorted(m.name for m in pkgutil.iter_modules(__path__)
                  if not m.name.startswith("_"))


def get(model_type: str):
    if model_type not in known():
        raise UnknownArchitecture(
            f"no architecture module for model_type {model_type!r}; known: "
            f"{known()}. Add chipbench/architectures/<model_type>.py with the "
            "surface its package describes before running a configuration of it.")
    module = importlib.import_module(f"{__name__}.{model_type}")
    missing = [name for name in SURFACE if not hasattr(module, name)]
    if missing:
        raise UnknownArchitecture(
            f"chipbench/architectures/{model_type}.py lacks {missing}")
    return module


def of(cfg: dict):
    """The architecture module of a configuration file's ``model_type``."""
    return get(cfg.get("model_type"))
