"""Read ``BENCHMARK.json`` and the data files a cell names, and check them
against the benchmark's contract (the checks the tests run; the driver
runs its own)."""

from __future__ import annotations

import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def load(path: Path | None = None) -> dict:
    return json.loads((path or ROOT / "BENCHMARK.json").read_text())


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in the manifest; it has "
                   f"{[w['name'] for w in manifest['workloads']]}")


def metrics_of(manifest: dict, kind: str, cell_name: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries the cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_file(kind: str, name: str) -> Path:
    """The metric's reader file. A name split by suffix
    (``queue_wait_ms_mean.chat``, so that it can name another end-to-end
    metric under ``moves``) reads with its base name's file unless it has
    one of its own."""
    folder = HERE / ("end_to_end" if kind == "end_to_end" else "layer_metrics")
    own = folder / f"{name}.json"
    return own if own.exists() or "." not in name else folder / f"{name.rsplit('.', 1)[0]}.json"


def topology_of(cell_entry: dict) -> str:
    """The topology follows from the chips a cell asks for."""
    return "one-worker" if cell_entry["chips"] == 1 else "four-replicas-kv-router"


def problems(manifest: dict, root: Path = ROOT) -> list[str]:
    """Everything wrong with the manifest and the files it names; empty
    when it is sound."""
    bad: list[str] = []
    if set(manifest) != KEYS:
        bad.append(f"keys {sorted(manifest)} are not exactly {sorted(KEYS)}")
        return bad
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append("run_seconds outside 1..51")
    paths = manifest["paths"]
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for label, entries in (("config", manifest["configs"]), ("workload", manifest["workloads"]),
                           ("metric", manifest["end_to_end"] + manifest["per_layer"])):
        names = [e["name"] for e in entries]
        if len(set(names)) != len(names):
            bad.append(f"duplicate {label} names")
        bad += [f"{label} name {n!r} is not a name" for n in names if not NAME.match(n)]
    if "setup_s" not in e2e:
        bad.append("no setup_s among end_to_end")
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        if not any(c["file"].startswith(p + "/") for p in paths):
            bad.append(f"config {c['name']}: file outside paths")
        if not (root / c["file"]).exists():
            bad.append(f"config {c['name']}: {c['file']} does not exist")
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            bad.append(f"config {c['name']} is used by no cell")
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    if len(set(pairs)) != len(pairs):
        bad.append("a (config, traffic) pair appears twice")
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w['name']}: keys {sorted(w)}")
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"workload {w['name']}: why is not one line of 1..200 characters")
        for n in (w["config"], w["traffic"]):
            if not NAME.match(n):
                bad.append(f"workload {w['name']}: {n!r} is not a name")
        if not (root / "chipbench" / "traffic" / f"{w['traffic']}.json").exists():
            bad.append(f"workload {w['name']}: no traffic file {w['traffic']}.json")
        if not (root / "chipbench" / "topologies" / f"{topology_of(w)}.json").exists():
            bad.append(f"workload {w['name']}: no topology file {topology_of(w)}.json")
        mine_e2e = metrics_of(manifest, "end_to_end", w["name"])
        mine_layer = metrics_of(manifest, "per_layer", w["name"])
        if len(mine_e2e) < 2 or not mine_layer:
            bad.append(f"workload {w['name']}: needs setup_s, another end-to-end "
                       "metric and a per-layer metric")
    for kind in ("end_to_end", "per_layer"):
        for m in manifest[kind]:
            want = {"name", "unit", "better", "source"} | (
                {"bound"} if kind == "end_to_end" else {"layer", "moves"})
            if set(m) - {"workloads"} != want:
                bad.append(f"metric {m['name']}: keys {sorted(m)}")
                continue
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']!r}")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"metric {m['name']}: an end-to-end metric is "
                               "taken by the benchmark itself")
                if not 0 < m["bound"] <= 0.1:
                    bad.append(f"metric {m['name']}: bound {m['bound']}")
            for wname in m.get("workloads", ()):
                if wname not in cells:
                    bad.append(f"metric {m['name']}: unknown workload {wname}")
            if not metric_file(kind, m["name"]).exists() and m["name"] != "setup_s":
                bad.append(f"metric {m['name']}: no reader file "
                           f"{metric_file(kind, m['name']).relative_to(HERE)}")
            if kind == "per_layer":
                if m["moves"] not in e2e:
                    bad.append(f"metric {m['name']}: moves unknown {m['moves']!r}")
                    continue
                moved = e2e[m["moves"]]
                for wname in m.get("workloads", cells):
                    if "workloads" in moved and wname not in moved["workloads"]:
                        bad.append(f"metric {m['name']} moves {m['moves']}, which "
                                   f"cell {wname} does not report")
    # One entry a metric: entries that read with the same file and move the
    # same end-to-end metric are ONE entry with a list of cells.
    seen: dict[tuple[str, str], str] = {}
    for m in manifest["per_layer"]:
        key = (metric_file("per_layer", m["name"]).stem, m.get("moves"))
        if key in seen:
            bad.append(f"metrics {seen[key]} and {m['name']} read with {key[0]}.json and "
                       f"move {key[1]}: one entry with a list of cells")
        seen.setdefault(key, m["name"])
    return bad
