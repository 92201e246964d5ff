"""Numbers from the reduced device trace (``chipbench.trace.reduce``) of
the traced slice of the window. ``stat`` selects:

- ``idle_share``: 100 x (1 - busy / window), in %;
- ``module_ms``: device ms per execution of the program ``module``,
  divided by ``per`` ("megastep_k": the decode iterations one dispatch
  fuses) where given;
- ``module_ms_per_ktok``: device ms of ``module`` per 1,000 prompt tokens
  that requests prefilled (prompt less cached) with a first token inside
  the traced slice;
- ``op_share``: device time of the ops whose name contains ``op`` inside
  ``module``, as % of all op time inside ``module``;
- ``weight_floor_share``: the least a decode step could take, streaming
  the weights it must read once at the chip's HBM rate, as % of the
  measured step;
- ``attn_roofline``: the least one decode-attention call could take,
  reading the K/V blocks in use once at the HBM rate, as % of the
  kernel's measured time per call (it is bandwidth-bound: one query token
  per sequence);
- ``step_mfu``: the operations one decode step NEEDS (the architecture's
  ``forward_flops_per_token`` at the mean context of the streams in flight,
  times the live lanes a decode dispatch carried over the window) over the
  measured step, as % of the chip's bf16 peak (int8 weight-only products
  run in bf16 too). What the program computes beyond that (held experts on
  rows that did not choose them, padded lanes) does not count.

The bytes and operations of the last three are counted by the
configuration's architecture (``chipbench/architectures``), the rates are
``chipbench/peaks.py``'s.
"""

from __future__ import annotations

from chipbench import architectures, peaks, stats
from chipbench.configs import model_fields
from chipbench.readers import prometheus


def _megastep_k(ctx) -> int:
    """The megastep length the worker resolved for itself (it answers the
    reference check with it); there is no default to fall back to."""
    return int(ctx.harness["megastep_k"])


def _module(ctx, module: str):
    m = ctx.trace["modules"].get(module)
    return m if m and m["count"] else None


def _module_ms(ctx, module: str, per_step: bool) -> float | None:
    """Device ms per execution of ``module``; per fused decode step with
    ``per_step``."""
    m = _module(ctx, module)
    if m is None:
        return None
    return 1000.0 * m["seconds"] / m["count"] / (_megastep_k(ctx) if per_step else 1)


def _slice(ctx) -> tuple[float, float]:
    """The traced slice on the parent's monotonic clock."""
    a = ctx.harness["trace_started_unix"] - ctx.unix_minus_monotonic
    return a, a + ctx.harness["trace_seconds"]


def _observed(ctx) -> architectures.Observed:
    """The window as the worker's always-on counters saw it, for an
    architecture whose bytes depend on the traffic. Live lanes per decode
    dispatch is exact where every decode dispatch is a megastep (k > 1),
    as in every cell so far, and None where the engine counted none."""
    counter = lambda name, labels=None: prometheus.delta(  # noqa: E731
        ctx, "worker", {"name": name, "labels": labels})
    lanes = counter("dynamo_engine_decode_live_lanes_total")
    dispatches = counter("dynamo_scheduler_megastep_dispatches_total")
    return architectures.Observed(
        decode_lanes_mean=lanes / dispatches if lanes is not None and dispatches else None,
        counter=counter)


def _live_contexts(ctx) -> list[int]:
    """Context of each stream in flight at the slice's middle: its prompt
    plus the tokens it had been sent by then."""
    mid = sum(_slice(ctx)) / 2
    pace = stats.percentile(
        [v for r in ctx.records if r.ok
         for v in [stats.tpot_ms(r.first, r.finished, r.completion_tokens)] if v], 50)
    live = []
    for r in ctx.records:
        if r.first is None or r.first > mid or (r.finished or mid + 1) < mid:
            continue
        n = r.completion_tokens or r.req.max_tokens
        # tokens sent by the slice's middle, evenly spread as in
        # stats.tokens_in_window; a stream still running is taken to
        # run at the median pace of those that ended
        if r.finished:
            sent = 1 + (n - 1) * (mid - r.first) / max(r.finished - r.first, 1e-9)
        else:
            sent = min(n, 1 + (mid - r.first) * 1000.0 / pace) if pace else 1
        live.append(int((r.prompt_tokens or len(r.req.prompt)) + sent))
    return live


def read(ctx, stat: str, module: str | None = None, op: str | None = None,
         per: str | None = None):
    tr = ctx.trace
    if not tr or not tr.get("window_s") or not tr.get("devices"):
        return None
    if stat == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if stat == "module_ms":
        return _module_ms(ctx, module, per == "megastep_k")
    if stat == "module_ms_per_ktok":
        m = _module(ctx, module)
        a, b = _slice(ctx)
        tokens = sum((r.prompt_tokens or 0) - r.cached_tokens for r in ctx.records
                     if r.first is not None and a <= r.first < b)
        return 1e6 * m["seconds"] / tokens if m and tokens else None
    if stat == "op_share":
        inside = [(k, s) for k, s, _ in tr["ops"] if k.startswith(module + "/")]
        total = sum(s for _, s in inside)
        return 100.0 * sum(s for k, s in inside if op in k) / total if total else None
    arch = architectures.of(ctx.config)
    mf = model_fields(ctx.config)
    pk = peaks.peaks(ctx.device_kind)
    if stat == "weight_floor_share":
        step = _module_ms(ctx, module, per_step=True)
        floor_ms = 1000.0 * arch.decode_weight_bytes(
            mf, ctx.config["serve"].get("quant"), _observed(ctx)) / pk.hbm_bytes_per_s
        return 100.0 * floor_ms / step if step else None
    if stat == "step_mfu":
        step = _module_ms(ctx, module, per_step=True)
        lanes = _observed(ctx).decode_lanes_mean
        live = _live_contexts(ctx)
        if not step or not lanes or not live:
            return None
        flops = arch.forward_flops_per_token(mf, int(sum(live) / len(live))) * lanes
        return 100.0 * flops / (step / 1000.0) / pk.bf16_flops
    if stat == "attn_roofline":
        kernel = [(s, c) for k, s, c in tr["ops"]
                  if k.startswith(module + "/") and op in k]
        seconds, calls = sum(s for s, _ in kernel), sum(c for _, c in kernel)
        live = _live_contexts(ctx)
        if not calls or not live:
            return None
        need = arch.attn_decode_bytes_per_layer(
            live, mf, ctx.config["serve"]["engine"]["block_size"])
        return 100.0 * (need / pk.hbm_bytes_per_s) / (seconds / calls)
    raise ValueError(f"unknown trace stat {stat!r}")
