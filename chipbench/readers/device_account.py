"""``device_account_error``: the step clock's account of the device held
to the device trace.

The program keeps, from its host clock's readings alone, an estimate of
each dispatch's device seconds and of the seconds the device had nothing
queued before it (``dynamo_tpu/tracing/stepclock.py``), and writes it on
the ``engine/commit`` annotation opened at the dispatch's landing (``no``,
``kind``, ``device_ms``, ``starved_lower_ms``, ``starved_upper_ms``,
``late``); ``engine/dispatch`` carries the same ``no``. ``compare`` lays
them beside what the device did, for the dispatches whose two annotations
lie inside the traced slice:

- on a TPU, a dispatch's programs are those the runtime enqueued from the
  start of its ``engine/dispatch`` annotation to the start of the next
  ``engine/h2d`` (``DoEnqueueProgram`` events: the runtime may enqueue
  just after the jitted call returned; what the next dispatch's staging
  enqueues, the feedback gather, is not this one's), found on the device
  by ``run_id`` (``chipbench.trace.phases.load``): their
  seconds against ``device_ms``, in all and by ``kind``; between two
  consecutive such dispatches, the device's time from the end of the
  one's last program to the start of the other's first, where the other
  was enqueued only after the one had ended (the device had nothing
  queued: ``trace_idle_between_s``), against the account's
  ``[starved_lower_ms, starved_upper_ms]``; where it was enqueued before,
  the few microseconds between two queued programs are the device's own
  (``trace_launch_gaps_s``);
- the CPU backend (the tests' rehearsal) has no program events, only ops
  on host threads: there the union of the ops' time between a landing
  that waited and the last landing of the slice stands for the programs'
  seconds, and the rest of that stretch for the idle.

The metric is ``100 x |account - programs| / programs`` over the slice.
``read`` runs ``compare`` once a run, as a child process (the trace is
large), and leaves what it found in ``device_account.json`` beside the
run's other records. No trace, or annotations without the attrs (a program
from before the account): None.

Run as a program: ``python -m chipbench.readers.device_account <trace dir> <out.json>``."""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
from collections import defaultdict
from pathlib import Path

from chipbench.manifest import ROOT


def _landings(trace: dict) -> dict[int, dict]:
    """``no`` -> what the ``engine/commit`` annotation of that landing
    says, with the annotation's start (the landing, host clock, ns)."""
    out = {}
    for phase, start, _, st in trace["phases"]:
        if phase == "commit" and "no" in st and "device_ms" in st:
            out[int(float(st["no"]))] = {
                "at": start, "kind": st.get("kind", ""),
                "device_s": float(st["device_ms"]) * 1e-3,
                "lower_s": float(st.get("starved_lower_ms", 0)) * 1e-3,
                "upper_s": float(st.get("starved_upper_ms", 0)) * 1e-3,
                "late": int(float(st.get("late", 0)))}
    return out


def _programs(trace: dict) -> dict[int, tuple[float, list]]:
    """``no`` -> (the first enqueue's time on the host's clock, the
    device's ``[start, end]`` pairs on its own clock; ns) of the programs
    enqueued from the start of that dispatch's annotation to the start of
    the next ``engine/h2d``."""
    runs = defaultdict(list)
    for _, start, dur, run_id in trace["modules"]:
        if run_id:
            runs[run_id].append((start, start + dur))
    enqueues = sorted(trace["enqueues"])
    times = [t for t, _ in enqueues]
    staging = sorted(start for phase, start, _, _ in trace["phases"] if phase == "h2d")
    out = {}
    for phase, start, dur, st in trace["phases"]:
        if phase != "dispatch" or "no" not in st:
            continue
        i = bisect.bisect_right(staging, start)
        end = staging[i] if i < len(staging) else float("inf")
        mine = [(t, iv) for t, run_id in enqueues[bisect.bisect_left(times, start):
                                                  bisect.bisect_left(times, end)]
                for iv in runs.get(run_id, ())]
        if mine:
            out[int(float(st["no"]))] = (mine[0][0], sorted(iv for _, iv in mine))
    return out


def _sums(nos, landings) -> dict:
    return {"dispatches": len(nos), "late": sum(landings[n]["late"] for n in nos),
            "account_device_s": sum(landings[n]["device_s"] for n in nos)}


def _by_run_id(trace: dict, landings: dict) -> dict | None:
    from chipbench.trace.phases import clock_shift

    programs = _programs(trace)
    nos = sorted(set(programs) & set(landings))
    if not nos:
        return None
    seconds = {n: sum(e - s for s, e in programs[n][1]) * 1e-9 for n in nos}
    out = _sums(nos, landings)
    out["programs_device_s"] = sum(seconds.values())
    by_kind: dict[str, dict] = {}
    for n in nos:
        k = by_kind.setdefault(landings[n]["kind"], {"account_s": 0.0, "programs_s": 0.0})
        k["account_s"] += landings[n]["device_s"]
        k["programs_s"] += seconds[n]
    out["by_kind"] = by_kind
    shift = clock_shift(trace)[0]        # device clock + shift = host clock
    idle = launch = lower = upper = 0.0
    pairs = 0
    for a, b in zip(nos, nos[1:]):
        if b != a + 1:
            continue
        pairs += 1
        ended = programs[a][1][-1][1]
        gap = max(0.0, programs[b][1][0][0] - ended)
        if programs[b][0] > ended + shift:   # enqueued after the device ran dry
            idle += gap
        else:
            launch += gap
        lower += landings[b]["lower_s"]
        upper += landings[b]["upper_s"]
    out.update(pairs=pairs, trace_idle_between_s=idle * 1e-9, trace_launch_gaps_s=launch * 1e-9,
               account_starved_lower_s=lower, account_starved_upper_s=upper)
    return out


def _by_op_union(trace: dict, landings: dict) -> dict | None:
    """The CPU backend's stand-in (module docstring)."""
    from chipbench.trace.reduce import _union

    first = next((n for n in sorted(landings)
                  if n - 1 in landings and not landings[n - 1]["late"]), None)
    if first is None or not trace["ops"]:
        return None
    nos = [n for n in sorted(landings) if n >= first]
    t0, t1 = landings[first - 1]["at"], landings[nos[-1]]["at"]
    busy = sum(min(b, t1) - max(a, t0)
               for a, b in _union([(op[1], op[1] + op[2]) for op in trace["ops"]
                                   if op[1] < t1 and op[1] + op[2] > t0]))
    if busy <= 0:
        return None
    out = _sums(nos, landings)
    out.update(programs_device_s=busy * 1e-9, pairs=len(nos),
               trace_idle_between_s=(t1 - t0 - busy) * 1e-9,
               account_starved_lower_s=sum(landings[n]["lower_s"] for n in nos),
               account_starved_upper_s=sum(landings[n]["upper_s"] for n in nos))
    return out


def compare(trace: dict) -> dict:
    """``trace``: what ``chipbench.trace.phases.load`` gives. ``error_pct``
    is None where there is nothing to compare."""
    landings = _landings(trace)
    found = None
    if landings:
        found = (_by_run_id if trace["modules"] else _by_op_union)(trace, landings)
    if not found:
        return {"error_pct": None, "landings": len(landings)}
    found["error_pct"] = (100.0 * abs(found["account_device_s"] - found["programs_device_s"])
                          / found["programs_device_s"])
    return found


def main(argv: list[str]) -> int:
    from chipbench.trace import phases
    from chipbench.trace.reduce import find_xplane

    trace_dir, out = Path(argv[0]), Path(argv[1])
    path = find_xplane(trace_dir)
    if path is None:
        out.write_text(json.dumps({"error_pct": None, "error": f"no .xplane.pb under {trace_dir}"}))
        return 1
    out.write_text(json.dumps(compare(phases.load(path))))
    return 0


def read(ctx):
    if not ctx.trace:
        return None
    out = ROOT / "chipbench_out" / ctx.cell["name"] / "device_account.json"
    if not out.exists():   # the run's directory was emptied at its start
        subprocess.run(
            [sys.executable, "-m", "chipbench.readers.device_account",
             str(out.parent / "side-0" / "trace"), str(out)],
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, timeout=600)
    try:
        return json.loads(out.read_text()).get("error_pct")
    except (OSError, ValueError):
        return None


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
