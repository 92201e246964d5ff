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

One member is OPTIONAL (``OPTIONAL``; the six modules here have none):

- ``score_probe(cfg, params, prompt, probe, **options) -> dict``: the
  reference's side of one probe, for an architecture whose step yields
  something other than the next token of each sequence, so that "generated
  token j is predicted from row ``len(prompt) + j - 1`` of one causal
  forward" (``reference.check.score_probe``, the default) does not describe
  it: a block-diffusion model reads a token from its OWN row of a forward
  whose input holds mask tokens at the block's places still hidden when that
  token was chosen. ``probe`` is what ``reference.check.run_probe`` returned:
  ``tokens``, ``top_ids``, ``top_lps`` and, for such a module alone,
  ``extra``: a list with one dict a generated token that carries every key
  of the program's log-probability entry other than ``top`` (the denoising
  step's index, say). It returns what the default returns, one item a
  generated token: ``top_lps`` (the reference's log-probability of every id
  in ``top_ids``), ``argmax``, ``argmax_lp`` and ``finite``.
  ``reference.check`` calls it in place of its own wherever a module has one.
  **``extra`` is the program's CLAIM of its schedule, not an input of the
  reference.** A module that only rebuilt each step's input from it would
  replay a wrong unmasking order and pass it. So the module owes two
  checks, both inside the return's shape (it still brings no tolerance):
  the claim is LEGAL (every place of a block chosen at one step, steps
  counted from the block's first; otherwise ``finite`` is False and
  ``compare`` fails), and it is the schedule the REFERENCE would have kept:
  where the reference's own forward over a step's input would have chosen
  another place than the claimed one, the token's ``argmax`` is no token
  (-1) and its ``argmax_lp`` the confidence of the reference's place, so
  that ``compare`` holds the two to its near-tie rule as it holds two top
  tokens. ``tests/chipbench/data/toy_block_arch.py`` does both, and its
  tests show a legal but wrong order caught that a replay passes. The
  engine's entry format for such a step does not exist yet: the first
  module that reads one fixes the keys it trusts, and says so here.

The tolerance of the comparison (``reference.check.LOGPROB_ATOL``) and the
comparison itself (``reference.check.compare``) are not part of either: an
architecture may not bring its own.
"""

from __future__ import annotations

import importlib
import pkgutil
from collections.abc import Callable
from dataclasses import dataclass

SURFACE = ("KEYS", "derived", "reference_logits", "decode_weight_bytes",
           "kv_bytes_per_token", "attn_decode_bytes_per_layer",
           "forward_flops_per_token")
OPTIONAL = ("score_probe",)


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
