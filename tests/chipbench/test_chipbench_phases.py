"""chipbench.trace.phases and the phase_summary reader: a hand-made trace
(exact-overlap split of the idle time, ``unattributed``, ``unscoped``, the
clock shift), a slice recorded on the v5e with the engine's annotations in
it, the manifest's new entries, and the new metrics in the CPU rehearsal's
tiny cells."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import manifest
from chipbench.readers import phase_summary
from chipbench.trace import phases
from chipbench_entries import layer_entry

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests" / "chipbench" / "data"
TINY = "tests/chipbench/data/tiny_manifest_phases.json"
MS = 1e6   # ns

NEW = ["host_ms_per_dispatch", "between_steps_ms_per_dispatch", "land_wait_ms_per_dispatch",
       "decode_lane_occupancy", "megastep_useful_share", "prefill_bucket_fill",
       "preemptions_per_kdispatch", "lm_head_time_share", "unscoped_time_share"]
COUNTERS = NEW[:7]
# The five idle_*_share metrics that split a slice's idle seconds by these
# phases went in PR 41 (device_starved_share says it of the whole window); the
# reader keeps the stat, and the tests below keep it to its arithmetic.
IDLE = {"in_step": ["admit", "plan", "assemble", "h2d", "dispatch", "commit"],
        "between_steps": ["between_steps"], "land": ["land"], "no_work": ["no_work"],
        "unattributed": ["unattributed"]}


def hand_made(shift_ms: float = 0.0) -> dict:
    """Two steps on the host's clock; the device's clock runs ``shift_ms``
    behind it. Device: busy 10-40 and 62-90 ms (host time); idle 0-10,
    40-62 and 90-100 of a 100 ms window."""
    d = -shift_ms * MS
    op = lambda name, a, b, tf: [name, a * MS + d, (b - a) * MS, "", tf]  # noqa: E731
    ph = lambda p, a, b, **st: [p, a * MS, (b - a) * MS, st]              # noqa: E731
    scope = "jit(_megastep_body)/while/body/closed_call/"
    return {
        "device": "/device:TPU:0",
        "window": [0.0, 100 * MS],
        "ops": [
            op("%while.2 = (s32[]) while(...)", 10, 40, "jit(_megastep_body)/while"),
            op("%fusion.10 = (bf16[8]) fusion(...)", 10, 25, scope + "mlp/dot_general:"),
            op("%fusion.11 = bf16[8] fusion(...)", 25, 37, scope + "attn/pallas_call:"),
            op("%copy.3 = bf16[8] copy(...)", 37, 40, ""),
            op("%fusion.7 = f32[8,100] fusion(...)", 62, 90,
               "jit(_megastep_body)/lm_head/dot_general:"),
        ],
        "modules": [["jit__megastep_body(1)", 10 * MS + d, 30 * MS, "7"],
                    ["jit__megastep_body(1)", 62 * MS + d, 28 * MS, "8"]],
        "phases": [
            ph("step", 2, 48, after="no_work"),
            ph("admit", 2, 4), ph("plan", 4, 8), ph("dispatch", 8, 12, kind="megastep",
                                                    lanes="3", width="4", pipelined="0"),
            ph("land", 12, 44), ph("commit", 44, 48),
            ph("step", 55, 96, after="between_steps"),
            ph("admit", 55, 56), ph("plan", 56, 60), ph("dispatch", 60, 63, kind="megastep",
                                                        lanes="4", width="4", pipelined="1"),
            ph("land", 63, 92), ph("commit", 92, 96),
        ],
        # run 7 was enqueued at 9.5 ms and its completion seen at 40.2 ms
        "enqueues": [[9.5 * MS, "7"], [61.5 * MS, "8"]],
        "completes": [[40.2 * MS, "7"], [90.3 * MS, "8"]],
    }


def test_idle_is_split_by_exact_overlap():
    s = phases.summarize(hand_made())
    idle = {k: round(v * 1e3, 6) for k, v in s["idle_s"].items()}
    # 0-2 no_work (the gap the first step names reaches back past the
    # trace's start), 2-4 admit, 4-8 plan, 8-10 dispatch; 40-44 land,
    # 44-48 commit, 48-55 between_steps, 55-56 admit, 56-60 plan, 60-62
    # dispatch; 90-92 land, 92-96 commit, 96-100 after the last step.
    assert idle == {"no_work": 2.0, "admit": 3.0, "plan": 8.0, "dispatch": 4.0,
                    "land": 6.0, "commit": 8.0, "between_steps": 7.0,
                    "unattributed": 4.0}
    assert s["idle_total_s"] == pytest.approx(0.042)
    assert s["window_s"] == pytest.approx(0.1) and s["busy_s"] == pytest.approx(0.058)
    assert s["phases"]["land"] == {"count": 2, "seconds": pytest.approx(0.061)}
    assert s["phases"]["between_steps"] == {"count": 1, "seconds": pytest.approx(0.007)}
    assert s["dispatches"]["megastep"]["count"] == 2
    assert s["dispatches"]["megastep"]["pipelined"] == 1
    assert s["dispatches"]["megastep"]["lanes"] == 7


def test_a_gap_is_not_named_by_its_middle():
    """The idle gap 40-62 ms has its middle at 51, between two steps; by
    exact overlap only 7 of its 22 ms are between_steps."""
    s = phases.summarize(hand_made())
    assert s["idle_s"]["between_steps"] == pytest.approx(0.007)


def test_ops_fall_under_their_innermost_section_and_the_rest_is_unscoped():
    s = phases.summarize(hand_made())
    sec = s["sections"]["_megastep_body"]
    # the while's own time is what its body leaves: 30 - 15 - 12 - 3 = 0
    assert sec == {"unscoped": pytest.approx(0.003), "mlp": pytest.approx(0.015),
                   "attn": pytest.approx(0.012), "lm_head": pytest.approx(0.028)}
    assert s["ops_s"] == pytest.approx(0.058) and s["unscoped_s"] == pytest.approx(0.003)
    assert s["top_ops"][0] == ["_megastep_body", "fusion.7", "lm_head", pytest.approx(0.028)]
    assert phases.section_of("jit(f)/mlp/experts/dot_general:") == "experts"
    assert phases.section_of("jit(f)/while/body/add") == "unscoped"


def test_the_devices_clock_is_put_on_the_hosts():
    """A program cannot start on the device before the host began to
    enqueue it: with the device 1.25 ms behind, run 7 starts at 8.75 ms on
    its own clock, 0.75 ms before its enqueue."""
    plain = phases.summarize(hand_made())
    assert plain["clock_shift_ns"] == 0.0
    assert plain["clock_shift_bounds_ns"] == [pytest.approx(-0.5 * MS), pytest.approx(0.2 * MS)]
    late = phases.summarize(hand_made(shift_ms=1.25))
    assert late["clock_shift_ns"] == pytest.approx(0.75 * MS)
    assert late["clock_shift_bounds_ns"] == [pytest.approx(0.75 * MS), pytest.approx(1.45 * MS)]
    # Shifted by its lower bound, each edge of a gap is off by the half
    # millisecond the bound is loose, no more.
    for k, v in plain["idle_s"].items():
        assert late["idle_s"][k] == pytest.approx(v, abs=0.00101), k


def test_a_trace_of_a_program_without_annotations_reads_as_nothing(tmp_path, monkeypatch):
    bare = hand_made()
    bare["phases"] = []
    bare["ops"] = [[n, a, d, m, ""] for n, a, d, m, _ in bare["ops"]]
    s = phases.summarize(bare)
    assert s["phases"] == {} and list(s["idle_s"]) == ["unattributed"]
    assert s["unscoped_s"] == pytest.approx(s["ops_s"])
    monkeypatch.setattr(phase_summary, "ROOT", tmp_path)
    out = tmp_path / "chipbench_out" / "cell" / "phase_summary.json"
    out.parent.mkdir(parents=True)
    out.write_text(json.dumps(s))
    ctx = SimpleNamespace(cell={"name": "cell"}, trace={"devices": 1})
    assert phase_summary.read(ctx, "idle_share", phases=["land"]) is None
    assert phase_summary.read(ctx, "section_share", section="unscoped") is None
    assert phase_summary.read(SimpleNamespace(cell={"name": "cell"}, trace=None),
                              "idle_share", phases=["land"]) is None
    out.write_text(json.dumps({"error": "no .xplane.pb"}))
    assert phase_summary.read(ctx, "idle_share", phases=["land"]) is None


def test_the_reader_on_the_hand_made_summary(tmp_path, monkeypatch):
    monkeypatch.setattr(phase_summary, "ROOT", tmp_path)
    out = tmp_path / "chipbench_out" / "cell" / "phase_summary.json"
    out.parent.mkdir(parents=True)
    out.write_text(json.dumps(phases.summarize(hand_made())))
    ctx = SimpleNamespace(cell={"name": "cell"}, trace={"devices": 1})
    shares = {name: phase_summary.read(ctx, "idle_share", phases=phases_of)
              for name, phases_of in IDLE.items()}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["in_step"] == pytest.approx(100 * 23 / 42)
    assert shares["land"] == pytest.approx(100 * 6 / 42)
    assert shares["unattributed"] == pytest.approx(100 * 4 / 42)
    lm = json.loads(manifest.metric_file("per_layer", "lm_head_time_share").read_text())
    assert phase_summary.read(ctx, **lm["args"]) == pytest.approx(100 * 28 / 58)
    un = json.loads(manifest.metric_file("per_layer", "unscoped_time_share").read_text())
    assert phase_summary.read(ctx, **un["args"]) == pytest.approx(100 * 3 / 58)


def test_recorded_v5e_slice():
    """A slice of a traced run of qwen1p5b-chat-steady on the v5e (one
    prefill wave and one megastep, whole), with the engine's annotations
    and scopes in it, reduces to what was recorded with it."""
    trace = json.loads((DATA / "phase_slice.json").read_text())
    want = json.loads((DATA / "phase_slice.expect.json").read_text())
    got = phases.summarize(trace)
    assert got["device"].startswith("/device:TPU")
    for key in ("window_s", "busy_s", "idle_total_s", "ops_s", "unscoped_s", "clock_shift_ns"):
        assert got[key] == pytest.approx(want[key]), key
    assert got["idle_s"] == pytest.approx(want["idle_s"])
    assert got["phases"].keys() == want["phases"].keys()
    assert sum(got["idle_s"].values()) == pytest.approx(got["idle_total_s"])
    # the engine's names are in it: phases from the annotations, sections
    # from the ops' scope metadata, and the two clocks were apart
    assert {"plan", "dispatch", "land", "commit", "between_steps"} <= got["phases"].keys()
    assert {"attn", "mlp", "qkv", "lm_head"} <= got["sections"]["_megastep_body"].keys()
    megastep = got["sections"]["_megastep_body"]
    assert megastep["unscoped"] < 0.02 * sum(megastep.values())
    assert got["dispatches"]["prefill"]["pipelined"] == 0   # the served loop is synchronous
    assert got["clock_shift_ns"] != 0.0


def test_manifest_has_the_new_metrics_for_both_cells():
    man = manifest.load()
    for base in NEW:
        for cell, moves in (("qwen7b-decode-batch", "tpot_ms_p50"),
                            ("qwen1p5b-chat-steady", "tpot_ms_mean")):
            m = layer_entry(man, base, cell)
            if base == "prefill_bucket_fill" and moves == "tpot_ms_p50":
                # batch's fill reads with prefill_wave_fill.json (PERF.md section 7)
                assert m is None and layer_entry(man, "prefill_wave_fill", cell)
                continue
            assert cell in m["workloads"] and m["moves"] == moves
            assert m["source"] == ("program_counter" if base in COUNTERS else "device_trace")
    # the retired five are gone, and nothing reads with their files
    assert not [m["name"] for m in man["per_layer"] if m["name"].startswith("idle_")]
    assert not list((ROOT / "chipbench" / "layer_metrics").glob("idle_*"))
    assert manifest.problems(manifest.load(ROOT / TINY)) == []


@pytest.mark.parametrize("workload,suffix", [("tiny-open-ph", "chat"), ("tiny-closed-ph", "batch")])
def test_new_metrics_in_the_cpu_rehearsal(workload, suffix):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload, "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    # by reader file: a rehearsal cell's names carry a suffix or not as the
    # benchmark's own do (PR 41)
    got = {manifest.metric_file("per_layer", k).stem: v["value"]
           for k, v in result["metrics"].items()}
    for base in COUNTERS:
        if base == "prefill_bucket_fill" and suffix == "batch":
            continue
        assert isinstance(got[base], float), base
    assert 0 < got["decode_lane_occupancy"] <= 100
    assert 0 < got["megastep_useful_share"] <= 100
    assert got["host_ms_per_dispatch"] > got["between_steps_ms_per_dispatch"] > 0
    summary = json.loads((ROOT / "chipbench_out" / workload / "phase_summary.json").read_text())
    # the slice's idle seconds still add up by phase in the summary
    assert sum(summary["idle_s"].values()) == pytest.approx(summary["idle_total_s"])
    counters = summary["counters"]
    # between the two scrapes, which a busy CPU does not take exactly at the
    # window's open and close (on the chip, 45 s: 44.99 s)
    assert sum(counters["phase_seconds"].values()) == pytest.approx(counters["window_s"], abs=0.5)
    assert counters["dispatches"] > 0 and summary["dispatches"]
