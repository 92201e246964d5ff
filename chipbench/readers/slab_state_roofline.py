"""Roofline share of a slab layer's decode STATE UPDATE, for a layer kind
given as an argument: the least one call could take, every live lane's float32
state read once and written once at the HBM rate, as % of the measured device
seconds a call of the ops under the ``jax.named_scope`` ``scope`` inside the
program ``module``. One call a layer of ``layer_kind`` (a published name among
the configuration's layer kinds: "mamba", "linear_attention") a fused decode
iteration: calls = executions of ``module`` in the traced slice x megastep k x
layers of that kind.

``readers/state_roofline.py`` is the same reduction with the kind fixed to
"linear_attention"; its sum (``share``) and its seconds
(``scope_roofline.scope_seconds``) are used here as they are. It reads the
scope and not a kernel's name, so that it reads the same work whatever
implements it. The bytes are the architecture's
(``state_step_bytes_per_layer`` at the live lanes a decode dispatch carried
over the window), the rate ``chipbench/peaks.py``'s. Nothing to read (no
trace, no op under the scope, a program or a configuration without such
layers, an architecture that counts no such bytes, no decode dispatch in the
window): None."""

from __future__ import annotations

from chipbench import architectures, peaks
from chipbench.configs import model_fields
from chipbench.manifest import ROOT
from chipbench.readers import trace_reduce
from chipbench.readers.scope_roofline import scope_seconds
from chipbench.readers.state_roofline import share
from chipbench.trace import phases
from chipbench.trace.reduce import find_xplane


def read(ctx, layer_kind: str, scope: str, module: str):
    tr = ctx.trace
    if not tr or not tr.get("devices"):
        return None
    count = getattr(architectures.of(ctx.config), "state_step_bytes_per_layer", None)
    executions = (tr["modules"].get(module) or {}).get("count")
    path = find_xplane(ROOT / "chipbench_out" / ctx.cell["name"] / "side-0" / "trace")
    if count is None or not executions or path is None:
        return None
    mf = model_fields(ctx.config)
    layers = sum(kind == layer_kind for kind in mf.get("layer_types") or ())
    lanes = trace_reduce._observed(ctx).decode_lanes_mean
    seconds = scope_seconds(phases.load(path), scope, module)
    if not layers or not lanes or not seconds:
        return None
    calls = executions * trace_reduce._megastep_k(ctx) * layers
    return share(count(lanes, mf), seconds, calls, peaks.peaks(ctx.device_kind).hbm_bytes_per_s)
