"""Numbers from ``phase_summary.json``: the traced slice under the
engine's own names (``chipbench.trace.phases``, run here once per run as a
child process, on the trace the worker left under
``chipbench_out/<cell>/side-0/trace``). ``stat`` selects:

- ``idle_share``: device 0's idle seconds that lie under the step-loop
  phases listed in ``phases`` (``unattributed``: under no annotation), as %
  of all its idle seconds in the slice. The five metrics that read this
  split the idle time between them, so they add up to 100;
- ``section_share``: device seconds of the ops under the model section
  ``section`` (``unscoped``: under none), as % of all op seconds, inside
  the program ``module`` where one is given.

A program without the annotations (or, for ``section_share``, without a
single scoped op) gives nothing to read: None. The step clock's counters
over the window, scraped at its open and close, are written into the same
file under ``counters`` for whoever reads the run: seconds per phase,
dispatches, and the window's length they should add up to."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from chipbench.manifest import ROOT
from chipbench.readers import prometheus

_PHASE_SECONDS = "dynamo_engine_step_phase_seconds_total"


def _counters(ctx) -> dict:
    phases: dict[str, float] = {}
    for text in ctx.scrape_close.get("worker", []):
        for name, labels, _ in prometheus.parse(text):
            if name == _PHASE_SECONDS:
                phases[labels["phase"]] = prometheus.delta(
                    ctx, "worker", {"name": name, "labels": {"phase": labels["phase"]}})
    return {"window_s": ctx.t_close - ctx.t_open, "phase_seconds": phases,
            "dispatches": prometheus.delta(
                ctx, "worker", {"name": "dynamo_engine_dispatches_total"})}


def summary(ctx) -> dict:
    out = ROOT / "chipbench_out" / ctx.cell["name"] / "phase_summary.json"
    if not out.exists():   # the run's directory was emptied at its start
        trace_dir = out.parent / "side-0" / "trace"
        proc = subprocess.run(
            [sys.executable, "-m", "chipbench.trace.phases", str(trace_dir), str(out)],
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, timeout=600)
        try:
            found = json.loads(out.read_text())
        except (OSError, ValueError):
            found = {"error": proc.stderr.decode(errors="replace")[-800:]}
        found["counters"] = _counters(ctx)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(found))
    return json.loads(out.read_text())


def read(ctx, stat: str, phases: list | None = None, section: str | None = None,
         module: str | None = None):
    if not ctx.trace:
        return None
    s = summary(ctx)
    if "error" in s:
        return None
    if stat == "idle_share":
        if not s["phases"] or not s["idle_total_s"]:
            return None
        return 100.0 * sum(s["idle_s"].get(p, 0.0) for p in phases) / s["idle_total_s"]
    if stat == "section_share":
        if s["unscoped_s"] >= s["ops_s"]:
            return None
        inside = [sec for m, sec in s["sections"].items() if module in (None, m)]
        total = sum(v for sec in inside for v in sec.values())
        return 100.0 * sum(sec.get(section, 0.0) for sec in inside) / total if total else None
    raise ValueError(f"unknown phase-summary stat {stat!r}")
