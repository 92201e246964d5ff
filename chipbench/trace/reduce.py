"""From ``.xplane.pb`` to numbers.

``load_xplane`` (needs JAX, for ``jax.profiler.ProfileData``; runs in a
child process on the CPU after the serving processes have stopped) turns
the file into plain lists; ``reduce`` (pure Python, tested on a recorded
slice) turns those into:

- ``busy_s`` / ``window_s``: the union of the intervals in which an
  operation ran on a device, averaged over the devices traced, and the
  length of the traced window;
- ``modules``: per jitted program (``_megastep_body``, ...) the number
  of executions and their device seconds;
- ``ops``: device seconds per operation, named ``<program>/<op>`` with the
  op's trailing instance number dropped (and its shape where the trace
  carries one), so that the names survive a recompile;
- ``gaps``: idle seconds of device 0 by what the host was doing: the
  innermost Python frame running at the gap's middle on a thread that
  was not just waiting.

Run as a program: ``python -m chipbench.trace.reduce <trace dir> <out.json>
[--slice-ms N <slice.json>]`` (the slice is what the tests record)."""

from __future__ import annotations

import bisect
import functools
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

_WAITING = ("sleep", "wait", "select", "acquire", "poll", "epoll", "get", "recv",
            "_worker", "run_forever", "_run_once", "join")
MIN_GAP_NS = 20_000
_FRAME = re.compile(r"^\$([A-Za-z0-9_]+\.py):[0-9]+ ")


@functools.cache
def program_files() -> frozenset[str]:
    """Base names of the program's Python files (the Python tracer
    records base names only)."""
    root = Path(__file__).resolve().parents[2] / "dynamo_tpu"
    return frozenset(p.name for p in root.rglob("*.py")) - {"__init__.py"}


def load_xplane(path: Path) -> dict:
    """{"devices": [{"name", "ops": [[name, start, dur, module, shape]],
    "modules": [[name, start, dur]]}], "host": [{"thread", "events":
    [[name, start, dur]]}]} with times in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        if is_device and "TPU" in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        st = dict(e.stats)
                        dev["ops"].append([
                            e.name[:160], e.start_ns, e.duration_ns,
                            str(st.get("hlo_module", "") or ""),
                            _shape_of(e.name)])
            if dev["ops"]:
                devices.append(dev)
        elif plane.name == "/host:CPU":
            cpu_ops = []
            for line in plane.lines:
                events = list(line.events)
                if line.name.startswith("python") or any(
                        e.name.startswith("$") for e in events[:50]):
                    host.append({"thread": line.name, "events": [
                        [e.name, e.start_ns, e.duration_ns] for e in events]})
                else:
                    # The CPU backend (rehearsals) records its ops on host
                    # threads, with the module as a stat.
                    for e in events:
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            cpu_ops.append([e.name[:160], e.start_ns, e.duration_ns,
                                            str(st.get("hlo_module", "")), ""])
            if cpu_ops and not any(d["name"].startswith("/device:TPU") for d in devices):
                devices.append({"name": "/host:CPU (cpu backend)", "ops": cpu_ops,
                                "modules": []})
    return {"devices": devices, "host": host}


def _shape_of(text: str) -> str:
    """Output shape of an op from its HLO text, which a TPU trace uses as
    the event's name (``%fusion.1 = f32[8,1,37888]{2,1,0:T(8,128)} fusion(...``),
    as ``f32_8_1_37888_``; empty for a tuple or where there is none."""
    m = re.match(r"^%?[\w.\-]+ = ([a-z]+[0-9]+)\[([0-9,]*)\]", text)
    return f"{m.group(1)}_{m.group(2).replace(',', '_')}_" if m else ""


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-/:]", "_", name)


def _module_name(name: str) -> str:
    """``jit__megastep_body(123)`` -> ``_megastep_body``."""
    name = re.sub(r"\(.*\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def _op_base(name: str) -> str:
    return re.sub(r"\.[0-9]+$", "", name.lstrip("%").split(" ")[0])


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _enclosing(modules: list, t: float, cursor: list[int]) -> str:
    """Name of the module execution that contains time ``t`` (modules
    sorted by start; ``cursor`` advances monotonically with ``t``)."""
    i = cursor[0]
    while i + 1 < len(modules) and modules[i + 1][1] <= t:
        i += 1
    cursor[0] = i
    if modules and modules[i][1] <= t < modules[i][1] + modules[i][2]:
        return _module_name(modules[i][0])
    return ""


def _self_times(dev_ops: list) -> list[float]:
    """Duration of each op less that of the ops nested in it (a ``while``
    or ``conditional`` spans its body's ops on the same line), for ops
    sorted by start and, at equal starts, longest first."""
    own = [op[2] for op in dev_ops]
    stack: list[int] = []   # indices of the ops open at this point
    for i, (_, start, dur, _, _) in enumerate(dev_ops):
        while stack and dev_ops[stack[-1]][1] + dev_ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [max(0.0, x) for x in own]


def _stack_at(events: list, starts: list, t: float, look_back: int = 4000):
    """Events of one thread's line that cover ``t``, innermost first:
    going back from the last event that started by ``t``, every one that
    has not ended yet (a later start that still covers ``t`` is deeper)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - look_back), -1):
        name, start, dur = events[j]
        if start + dur >= t:
            yield name


def reduce(trace: dict) -> dict:
    devices = trace["devices"]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "modules": {}, "ops": [], "gaps": []}
    starts = [op[1] for d in devices for op in d["ops"]]
    ends = [op[1] + op[2] for d in devices for op in d["ops"]]
    t0, t1 = min(starts), max(ends)
    # The traced window runs from the return of the profiler's
    # start_trace to the call of its stop_trace, where the Python tracer
    # saw them; device ops outside it (there are none) would widen it.
    for h in trace["host"]:
        for name, start, dur in h["events"]:
            if name.endswith(" start_trace"):
                t0 = min(t0, start + dur)
            elif name.endswith(" stop_trace"):
                t1 = max(t1, start)
    window_ns = t1 - t0
    busy_ns = 0.0
    ops: dict[str, float] = defaultdict(float)
    calls: dict[str, int] = defaultdict(int)
    modules: dict[str, dict] = defaultdict(lambda: {"count": 0, "seconds": 0.0})
    first_busy: list[tuple[float, float]] = []
    for di, dev in enumerate(devices):
        mods = sorted(dev["modules"], key=lambda m: m[1])
        for name, _, dur in mods:
            m = modules[_module_name(name)]
            m["count"] += 1
            m["seconds"] += dur * 1e-9
        cursor = [0]
        dev_ops = sorted(dev["ops"], key=lambda o: (o[1], -o[2]))
        for (name, start, _, module, shape), own in zip(dev_ops, _self_times(dev_ops)):
            module = _module_name(module) if module else _enclosing(mods, start, cursor)
            key = _clean(f"{module}/{_op_base(name)}{'_' + shape if shape else ''}")
            ops[key] += own * 1e-9
            calls[key] += 1
        busy = _union([(o[1], o[1] + o[2]) for o in dev_ops])
        busy_ns += sum(b - a for a, b in busy)
        if di == 0:
            first_busy = busy
        if not mods:   # CPU backend: programs from the ops' own stat
            by_mod: dict[str, list] = defaultdict(list)
            for name, start, dur, module, _ in dev_ops:
                by_mod[_module_name(module)].append((start, start + dur))
            for mod, spans in by_mod.items():
                modules[mod]["seconds"] += sum(b - a for a, b in _union(spans)) * 1e-9
    gaps: dict[str, float] = defaultdict(float)
    threads = [(h["events"], [e[1] for e in h["events"]])
               for h in _dispatcher_first(trace["host"])]
    edges = [t0] + [x for a, b in first_busy for x in (a, b)] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < MIN_GAP_NS:
            gaps["gaps_under_20us"] += (b - a) * 1e-9
            continue
        gaps[_attribute(threads, (a + b) / 2)] += (b - a) * 1e-9
    return {
        "busy_s": busy_ns * 1e-9 / len(devices),
        "window_s": window_ns * 1e-9,
        "devices": len(devices),
        "modules": {k: v for k, v in modules.items()},
        "ops": sorted(([k, v, calls[k]] for k, v in ops.items()),
                      key=lambda kv: -kv[1]),
        "gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1]),
    }


def _attribute(threads: list, t: float) -> str:
    """What the host was doing at ``t``. ``threads`` has the thread that
    dispatches the device's programs first: the device idles because that
    thread is not dispatching, so its innermost frame of the program's
    own files names the gap. Where it ran none of the program's code
    (it waited for work), the other threads' program frames do; failing
    those, the wait itself."""
    fallback = None
    for events, starts in threads:
        innermost = None
        for frame in _stack_at(events, starts, t):
            innermost = innermost or frame
            m = _FRAME.match(frame)
            if m and m.group(1) in program_files():
                return _clean(frame.lstrip("$"))
        if innermost is not None and fallback is None:
            label = _clean(innermost.lstrip("$"))
            waits = any(w in innermost.split(" ")[-1].lower() for w in _WAITING)
            fallback = f"waiting:{label}" if waits else label
    return fallback or "unattributed"


def _dispatcher_first(host: list) -> list:
    """Host threads, the one with most jitted-program calls first."""
    calls = lambda h: sum(e[0].startswith("PjitFunction(") for e in h["events"])  # noqa: E731
    return sorted(host, key=calls, reverse=True)


def find_xplane(trace_dir: Path) -> Path | None:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def slice_of(trace: dict, ms: float) -> dict:
    """The first ``ms`` milliseconds of device activity (with the host
    events that overlap them): small enough to keep among the tests."""
    t0 = min(op[1] for d in trace["devices"] for op in d["ops"])
    t1 = t0 + ms * 1e6
    keep = lambda ev, i: [e for e in ev if e[i] < t1 and e[i] + e[i + 1] > t0]  # noqa: E731
    return {
        "devices": [{"name": d["name"], "ops": keep(d["ops"], 1),
                     "modules": keep(d["modules"], 1)} for d in trace["devices"]],
        "host": [{"thread": h["thread"], "events": keep(h["events"], 1)}
                 for h in trace["host"]],
    }


def main(argv: list[str]) -> int:
    trace_dir, out = Path(argv[0]), Path(argv[1])
    path = find_xplane(trace_dir)
    if path is None:
        out.write_text(json.dumps({"error": f"no .xplane.pb under {trace_dir}"}))
        return 1
    trace = load_xplane(path)
    out.write_text(json.dumps(reduce(trace)))
    if len(argv) >= 5 and argv[2] == "--slice-ms":
        Path(argv[4]).write_text(json.dumps(slice_of(trace, float(argv[3]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
