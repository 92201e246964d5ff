"""The engine's own names on the profile: from ``.xplane.pb`` to
``phase_summary.json``.

The program wraps each phase of its step loop in a
``jax.profiler.TraceAnnotation`` (``engine/admit`` ... ``engine/commit``,
nested in one ``engine/step`` per ``step()`` call whose ``after`` stat
names the gap before it: ``between_steps`` or ``no_work``) and puts
``jax.named_scope`` sections on its device ops (``embed``, ``qkv``,
``kv_write``, ``attn``, ``o_proj``, ``mlp``, ``lm_head``, ``sample``,
``router``, ``experts``), which a TPU trace carries in each op's ``tf_op``
stat. ``load`` reads the file once (plain protobuf against a schema
declared here: ``jax.profiler.ProfileData`` does not show the stats of an
op's metadata, where ``tf_op`` lives); ``summarize`` (pure Python, tested
on a hand-made trace and on a slice recorded on the v5e) gives:

- ``idle_s``: device 0's idle seconds inside the traced window, split by
  the phase whose annotation covers them, by exact overlap; what no
  annotation covers is ``unattributed`` (the phase running when the
  trace began, and whatever follows the last step event). The gaps
  between two steps are derived from the space between two
  ``engine/step`` events;
- ``idle_inside_programs_s``: the part of that idle time that lies inside
  a program's execution (bubbles between its ops), whatever the host did;
- ``sections``: per jitted program, device seconds (self time, so a
  ``while`` does not count its body twice) by section; ops under no
  section are ``unscoped``. ``top_ops`` lists the heaviest ops (and the
  heaviest unscoped ones) with their section, so that a bare ``fusion``
  has a name;
- ``phases``: per phase, how many intervals lay inside the window and
  their seconds; ``dispatches``: per kind, count, how many were enqueued
  with a step in flight, lanes and tokens real against padded;
- ``clock_shift_ns``: what was added to the device's times to put them on
  the host's clock. The two clocks differ by about a millisecond in a
  v5e trace. Each program execution carries a ``run_id`` on both sides:
  it cannot start on the device before the host began to enqueue it
  (lower bound L) nor end after the host's completion callback ran
  (upper bound U); the shift is the value in [L, U] nearest to zero.

Nothing here raises on a trace of a program that has none of this: the
summary then has no phases and no sections, and the reader
(``chipbench/readers/phase_summary.py``) returns nothing.

Run as a program: ``python -m chipbench.trace.phases <trace dir> <out.json>``."""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

from chipbench.trace.reduce import _module_name, _self_times, _union, find_xplane

SECTIONS = ("embed", "qkv", "kv_write", "attn", "o_proj", "mlp", "lm_head",
            "sample", "router", "experts")
IN_STEP = ("admit", "plan", "assemble", "h2d", "dispatch", "land", "commit")
GAPS = ("between_steps", "no_work")


def _xspace_class():
    """``XSpace`` of tsl/profiler/protobuf/xplane.proto, as far as it is
    read here, built without generated code."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    I, S, M, D, U, B = (T.TYPE_INT64, T.TYPE_STRING, T.TYPE_MESSAGE, T.TYPE_DOUBLE,
                        T.TYPE_UINT64, T.TYPE_BYTES)
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane", syntax="proto3")

    def message(name, fields, maps=()):
        m = f.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            fd = m.field.add(name=fname, number=number, type=ftype,
                             label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL)
            if type_name:
                fd.type_name = f".{f.package}.{type_name}"
        for fname, number, value_type in maps:
            entry = m.nested_type.add(name=fname.title().replace("_", "") + "Entry")
            entry.options.map_entry = True
            entry.field.add(name="key", number=1, type=I, label=T.LABEL_OPTIONAL)
            entry.field.add(name="value", number=2, type=M, label=T.LABEL_OPTIONAL,
                            type_name=f".{f.package}.{value_type}")
            m.field.add(name=fname, number=number, type=M, label=T.LABEL_REPEATED,
                        type_name=f".{f.package}.{name}.{entry.name}")

    message("XStat", [("metadata_id", 1, I, 0, 0), ("double_value", 2, D, 0, 0),
                      ("uint64_value", 3, U, 0, 0), ("int64_value", 4, I, 0, 0),
                      ("str_value", 5, S, 0, 0), ("bytes_value", 6, B, 0, 0),
                      ("ref_value", 7, U, 0, 0)])
    message("XEvent", [("metadata_id", 1, I, 0, 0), ("offset_ps", 2, I, 0, 0),
                       ("duration_ps", 3, I, 0, 0), ("stats", 4, M, 1, "XStat")])
    message("XLine", [("id", 1, I, 0, 0), ("name", 2, S, 0, 0), ("timestamp_ns", 3, I, 0, 0),
                      ("events", 4, M, 1, "XEvent")])
    message("XEventMetadata", [("id", 1, I, 0, 0), ("name", 2, S, 0, 0),
                               ("display_name", 4, S, 0, 0), ("stats", 5, M, 1, "XStat")])
    message("XStatMetadata", [("id", 1, I, 0, 0), ("name", 2, S, 0, 0)])
    message("XPlane", [("id", 1, I, 0, 0), ("name", 2, S, 0, 0), ("lines", 3, M, 1, "XLine")],
            maps=[("event_metadata", 4, "XEventMetadata"),
                  ("stat_metadata", 5, "XStatMetadata")])
    message("XSpace", [("planes", 1, M, 1, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{f.package}.XSpace"))


def _stats(stats, names: dict) -> dict:
    """An event's (or its metadata's) stats by name; a ``ref_value``
    points at a stat metadata's name."""
    out = {}
    for st in stats:
        if st.str_value:
            value = st.str_value
        elif st.ref_value:
            value = names.get(st.ref_value, "")
        else:
            value = st.int64_value or st.uint64_value or st.double_value
        out[names.get(st.metadata_id, "")] = value
    return out


def load(path: Path) -> dict:
    """Plain lists, times in ns: ``ops`` ``[name, start, dur, module,
    tf_op]`` and ``modules`` ``[name, start, dur, run_id]`` of device 0,
    ``phases`` ``[phase, start, dur, stats]`` (``engine/<phase>`` events,
    ``step`` among them), ``enqueues`` / ``completes`` ``[time, run_id]``
    from the runtime's own host events, and the profiler's ``window``."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    out = {"ops": [], "modules": [], "phases": [], "enqueues": [], "completes": [],
           "window": [None, None], "device": ""}
    host_ops = []
    tpu = sorted((p for p in space.planes if p.name.startswith("/device:TPU:")),
                 key=lambda p: p.name)
    for plane in tpu[:1]:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        out["device"] = plane.name
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            meta = {}
            for ev in line.events:
                m = meta.get(ev.metadata_id)
                if m is None:
                    em = plane.event_metadata[ev.metadata_id]
                    m = meta[ev.metadata_id] = (em.name[:160], _stats(em.stats, names))
                start = line.timestamp_ns + ev.offset_ps / 1000.0
                if line.name == "XLA Ops":
                    out["ops"].append([m[0], start, ev.duration_ps / 1000.0, "",
                                       str(m[1].get("tf_op", ""))])
                else:
                    run_id = _stats(ev.stats, names).get("run_id", "")
                    out["modules"].append([m[0], start, ev.duration_ps / 1000.0, str(run_id)])
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        kinds = {}
        for mid, em in plane.event_metadata.items():
            name = em.name
            if name.startswith("engine/"):
                kinds[mid] = ("phase", name[len("engine/"):])
            elif name == "DoEnqueueProgram":
                kinds[mid] = ("enqueue", "")
            elif name == "CompleteCallbacks":
                kinds[mid] = ("complete", "")
            elif name.startswith("$") and name.endswith(" start_trace"):
                kinds[mid] = ("window", 0)
            elif name.startswith("$") and name.endswith(" stop_trace"):
                kinds[mid] = ("window", 1)
        for line in plane.lines:
            base = line.timestamp_ns
            for ev in line.events:
                kind = kinds.get(ev.metadata_id)
                if kind is None:
                    if tpu or not ev.stats:
                        continue
                    # The CPU backend (rehearsals) runs its ops on host threads.
                    st = _stats(ev.stats, names)
                    if "hlo_op" in st:
                        host_ops.append([plane.event_metadata[ev.metadata_id].name[:160],
                                         base + ev.offset_ps / 1000.0, ev.duration_ps / 1000.0,
                                         str(st.get("hlo_module", "")), str(st.get("tf_op", ""))])
                    continue
                start = base + ev.offset_ps / 1000.0
                if kind[0] == "phase":
                    out["phases"].append([kind[1], start, ev.duration_ps / 1000.0,
                                          {k: str(v) for k, v in _stats(ev.stats, names).items()}])
                elif kind[0] == "window":
                    end = start + ev.duration_ps / 1000.0
                    out["window"][kind[1]] = end if kind[1] == 0 else start
                else:
                    run_id = _stats(ev.stats, names).get("run_id", "")
                    out["enqueues" if kind[0] == "enqueue" else "completes"].append(
                        [start, str(run_id)])
    if not tpu:
        out["ops"], out["device"] = host_ops, "/host:CPU (cpu backend)"
    for key in ("ops", "modules", "phases"):
        out[key].sort(key=lambda e: (e[1], -e[2]))
    return out


def clock_shift(trace: dict) -> tuple[float, float | None, float | None]:
    """(shift, L, U) in ns; see the module's docstring."""
    starts = {m[3]: m[1] for m in trace["modules"] if m[3]}
    ends = {m[3]: m[1] + m[2] for m in trace["modules"] if m[3]}
    lower = [t - starts[r] for t, r in trace["enqueues"] if r in starts]
    upper = [t - ends[r] for t, r in trace["completes"] if r in ends]
    lo = max(lower) if lower else None
    hi = min(upper) if upper else None
    if lo is not None and hi is not None and lo > hi:
        lo = hi = (lo + hi) / 2          # the bounds cross: take the middle
    shift = 0.0
    if lo is not None and lo > 0:
        shift = lo
    elif hi is not None and hi < 0:
        shift = hi
    return shift, lo, hi


def section_of(tf_op: str) -> str:
    """The innermost section on an op's scope path
    (``jit(_megastep_body)/while/body/closed_call/mlp/dot_general:``)."""
    for part in reversed(tf_op.split("/")):
        if part in SECTIONS:
            return part
    return "unscoped"


def host_intervals(phases: list) -> list[tuple[float, float, str]]:
    """Non-overlapping ``(start, end, phase)`` on the host's clock: the
    leaf annotations, and between two ``engine/step`` events the gap the
    later one names."""
    out = [(s, s + d, p) for p, s, d, _ in phases if p in IN_STEP]
    steps = [(s, s + d, st.get("after", "")) for p, s, d, st in phases if p == "step"]
    if steps:
        # Before the first step event: its gap reaches back to the last
        # phase of a step the trace began inside, or past the trace's start.
        before = [e for _, e, _ in out if e <= steps[0][0]]
        steps.insert(0, (float("-inf"), max(before, default=float("-inf")), ""))
    for (_, prev_end, _), (start, _, after) in zip(steps, steps[1:]):
        if after in GAPS and start > prev_end:
            out.append((prev_end, start, after))
    return sorted(out)


def _enclosing_module(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] <= t < modules[i][1] + modules[i][2]:
        return _module_name(modules[i][0])
    return ""


def summarize(trace: dict) -> dict:
    ops = trace["ops"]
    if not ops:
        return {"error": "no device ops in the trace"}
    shift, lo, hi = clock_shift(trace)
    t0 = min(op[1] for op in ops) + shift
    t1 = max(op[1] + op[2] for op in ops) + shift
    w0, w1 = trace.get("window", [None, None])
    t0 = min(t0, w0) if w0 is not None else t0
    t1 = max(t1, w1) if w1 is not None else t1

    busy = _union([(op[1] + shift, op[1] + op[2] + shift) for op in ops])
    edges = [t0] + [x for a, b in busy for x in (a, b)] + [t1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    spans = host_intervals(trace["phases"])
    span_starts = [s for s, _, _ in spans]
    idle_ns: dict[str, float] = defaultdict(float)
    for a, b in idle:
        covered = 0.0
        i = max(0, bisect.bisect_right(span_starts, a) - 1)
        while i < len(spans) and spans[i][0] < b:
            s, e, phase = spans[i]
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                idle_ns[phase] += overlap
                covered += overlap
            i += 1
        idle_ns["unattributed"] += max(0.0, (b - a) - covered)

    # Idle inside a program's execution (bubbles between its ops) is not
    # the host's doing, whatever phase the host is in meanwhile.
    runs = _union([(m[1] + shift, m[1] + m[2] + shift) for m in trace["modules"]])
    run_starts = [s for s, _ in runs]
    inside_ns = 0.0
    for a, b in idle:
        i = max(0, bisect.bisect_right(run_starts, a) - 1)
        while i < len(runs) and runs[i][0] < b:
            inside_ns += max(0.0, min(b, runs[i][1]) - max(a, runs[i][0]))
            i += 1

    phase_stats: dict[str, dict] = {}
    for s, e, phase in spans:
        inside = min(e, t1) - max(s, t0)
        if inside > 0:
            st = phase_stats.setdefault(phase, {"count": 0, "seconds": 0.0})
            st["count"] += 1
            st["seconds"] += inside * 1e-9

    dispatches: dict[str, dict] = {}
    for p, _, _, st in trace["phases"]:
        if p != "dispatch":
            continue
        d = dispatches.setdefault(st.get("kind", ""), defaultdict(float))
        d["count"] += 1
        for key in ("pipelined", "lanes", "width", "real", "padded"):
            d[key] += float(st.get(key, 0) or 0)

    modules = trace["modules"]
    module_starts = [m[1] for m in modules]
    sections: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    by_op: dict[tuple[str, str, str], float] = defaultdict(float)
    for op, own in zip(ops, _self_times(ops)):
        module = _module_name(op[3]) if op[3] else _enclosing_module(
            modules, module_starts, op[1])
        section = section_of(op[4])
        sections[module][section] += own * 1e-9
        name = op[0].lstrip("%").split(" ")[0]
        by_op[(module, name, section)] += own * 1e-9
    ops_s = sum(v for sec in sections.values() for v in sec.values())
    unscoped_s = sum(sec.get("unscoped", 0.0) for sec in sections.values())
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
    top = ranked[:25] + [kv for kv in ranked[25:] if kv[0][2] == "unscoped"][:5]
    return {
        "device": trace.get("device", ""),
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(b - a for a, b in busy) * 1e-9,
        "clock_shift_ns": shift, "clock_shift_bounds_ns": [lo, hi],
        "idle_s": {k: v * 1e-9 for k, v in sorted(idle_ns.items(), key=lambda kv: -kv[1])},
        "idle_total_s": sum(idle_ns.values()) * 1e-9,
        "idle_inside_programs_s": inside_ns * 1e-9,
        "phases": phase_stats,
        "dispatches": {k: dict(v) for k, v in dispatches.items()},
        "sections": {m: dict(sec) for m, sec in sections.items()},
        "ops_s": ops_s, "unscoped_s": unscoped_s,
        "top_ops": [[m, n, sec, s] for (m, n, sec), s in top],
    }


def main(argv: list[str]) -> int:
    trace_dir, out = Path(argv[0]), Path(argv[1])
    path = find_xplane(trace_dir)
    if path is None:
        out.write_text(json.dumps({"error": f"no .xplane.pb under {trace_dir}"}))
        return 1
    trace = load(path)
    out.write_text(json.dumps(summarize(trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
