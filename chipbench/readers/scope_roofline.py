"""Roofline share of a bandwidth-bound decode attention that is NOT one
named kernel but the ops under a ``jax.named_scope`` (``scope``) inside the
program ``module``: the least one call could take, reading the cached rows
of the blocks in use once at the HBM rate, as % of the scope's measured
device seconds per call. One call a layer a fused decode iteration: calls =
executions of ``module`` in the traced slice x megastep k x layers.

What ``trace_reduce``'s ``attn_roofline`` is to a kernel the trace names.
The bytes are the architecture's (``attn_decode_bytes_per_layer`` over the
contexts of the streams in flight at the slice's middle, taken as
``trace_reduce`` takes them), the rate ``chipbench/peaks.py``'s, the seconds
the ops' self times from the same ``.xplane.pb`` ``scope_share`` reads.
Nothing to read (no trace, no op under the scope, no stream in flight):
None."""

from __future__ import annotations

from chipbench import architectures, peaks, stats
from chipbench.configs import model_fields
from chipbench.manifest import ROOT
from chipbench.readers import trace_reduce
from chipbench.trace import phases
from chipbench.trace.reduce import _module_name, _self_times, find_xplane


def scope_seconds(trace: dict, scope: str, module: str) -> float:
    """Device seconds of the ops under ``scope`` inside ``module``;
    ``trace`` as ``phases.load`` gives it."""
    ops, modules = trace["ops"], trace["modules"]
    starts = [m[1] for m in modules]
    inside = 0.0
    for op, own in zip(ops, _self_times(ops)):
        name = (_module_name(op[3]) if op[3]
                else phases._enclosing_module(modules, starts, op[1]))
        if name == module and scope in op[4].split("/"):
            inside += own
    return inside * 1e-9


def live_contexts(ctx) -> list[int]:
    """Context (prompt plus the tokens sent so far) of each stream in
    flight at the middle of the traced slice; a stream still running is
    taken to run at the median pace of those that ended."""
    a, b = trace_reduce._slice(ctx)
    mid = (a + b) / 2
    pace = stats.percentile(
        [v for r in ctx.records if r.ok
         for v in [stats.tpot_ms(r.first, r.finished, r.completion_tokens)] if v], 50)
    live = []
    for r in ctx.records:
        if r.first is None or r.first > mid or (r.finished or mid + 1) < mid:
            continue
        n = r.completion_tokens or r.req.max_tokens
        if r.finished:
            sent = 1 + (n - 1) * (mid - r.first) / max(r.finished - r.first, 1e-9)
        else:
            sent = min(n, 1 + (mid - r.first) * 1000.0 / pace) if pace else 1
        live.append(int((r.prompt_tokens or len(r.req.prompt)) + sent))
    return live


def read(ctx, scope: str, module: str):
    tr = ctx.trace
    if not tr or not tr.get("devices"):
        return None
    executions = (tr["modules"].get(module) or {}).get("count")
    path = find_xplane(ROOT / "chipbench_out" / ctx.cell["name"] / "side-0" / "trace")
    if not executions or path is None:
        return None
    live = live_contexts(ctx)
    if not live:
        return None
    seconds = scope_seconds(phases.load(path), scope, module)
    if not seconds:
        return None
    mf = model_fields(ctx.config)
    calls = executions * trace_reduce._megastep_k(ctx) * mf["num_layers"]
    need = architectures.of(ctx.config).attn_decode_bytes_per_layer(
        live, mf, ctx.config["serve"]["engine"]["block_size"])
    return 100.0 * (need / peaks.peaks(ctx.device_kind).hbm_bytes_per_s) / (seconds / calls)
