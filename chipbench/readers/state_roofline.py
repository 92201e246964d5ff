"""Roofline share of a linear-attention layer's decode STATE UPDATE: the
least one call could take, every live lane's float32 state read once and
written once at the HBM rate, as % of the measured device seconds a call of
the ops under the ``jax.named_scope`` ``scope`` inside the program
``module``. One call a LINEAR layer a fused decode iteration: calls =
executions of ``module`` in the traced slice x megastep k x linear layers.

It reads the scope and not a kernel's name, so that it reads the same work
whatever implements it (a Pallas kernel, or the gather, products and scatter
of plain XLA). The bytes are the architecture's
(``state_step_bytes_per_layer`` at the live lanes a decode dispatch carried
over the window, from the worker's always-on counters as
``trace_reduce._observed`` takes them), the rate ``chipbench/peaks.py``'s,
the seconds the ops' self times from the same ``.xplane.pb`` ``scope_share``
reads (``scope_roofline.scope_seconds``). Nothing to read (no trace, no op
under the scope, a program without such layers, an architecture that counts
no such bytes, no decode dispatch in the window): None."""

from __future__ import annotations

from chipbench import architectures, peaks
from chipbench.configs import model_fields
from chipbench.manifest import ROOT
from chipbench.readers import trace_reduce
from chipbench.readers.scope_roofline import scope_seconds
from chipbench.trace import phases
from chipbench.trace.reduce import find_xplane


def share(need_bytes: float, seconds: float, calls: int, hbm_bytes_per_s: float) -> float:
    """``need_bytes`` at the HBM rate as % of ``seconds / calls``."""
    return 100.0 * (need_bytes / hbm_bytes_per_s) / (seconds / calls)


def read(ctx, scope: str, module: str):
    tr = ctx.trace
    if not tr or not tr.get("devices"):
        return None
    arch = architectures.of(ctx.config)
    count = getattr(arch, "state_step_bytes_per_layer", None)
    executions = (tr["modules"].get(module) or {}).get("count")
    path = find_xplane(ROOT / "chipbench_out" / ctx.cell["name"] / "side-0" / "trace")
    if count is None or not executions or path is None:
        return None
    lanes = trace_reduce._observed(ctx).decode_lanes_mean
    seconds = scope_seconds(phases.load(path), scope, module)
    if not lanes or not seconds:
        return None
    mf = model_fields(ctx.config)
    layers = sum(kind == "linear_attention" for kind in mf.get("layer_types") or ())
    if not layers:
        return None
    calls = executions * trace_reduce._megastep_k(ctx) * layers
    return share(count(lanes, mf), seconds, calls, peaks.peaks(ctx.device_kind).hbm_bytes_per_s)
