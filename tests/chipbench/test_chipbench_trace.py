"""Reduction from trace events to busy/idle, per-op time and idle-gap
attribution: on a hand-made trace whose answers can be counted, and on a
slice recorded on the v5e (tests/chipbench/data/trace_slice.json)."""

import json
from pathlib import Path

import pytest

from chipbench.trace import reduce as tr

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def hand_made():
    """10 ms window. Device: two megasteps (1-4 ms, 6-9 ms), each one
    fusion and one attention op; idle 0-1, 4-6 (host in _plan_decode), 9-10."""
    ops = []
    for base in (1, 6):
        ops.append(["%while.2 = (s32[]) while(...)", base * MS, 3 * MS, "", ""])
        ops.append(["%fusion.12 = f32[8,1,37888]{2,1,0} fusion(...)", base * MS, 2 * MS, "",
                    "f32_8_1_37888_"])
        ops.append(["%ragged_paged_attention_kernel.3 = (bf16[8]) custom-call(...)",
                    (base + 2) * MS, 1 * MS, "", ""])
    return {
        "devices": [{"name": "/device:TPU:0", "ops": ops,
                     "modules": [["jit__megastep_body(7)", 1 * MS, 3 * MS],
                                 ["jit__megastep_body(7)", 6 * MS, 3 * MS]]}],
        "host": [
            {"thread": "python", "events": [
                ["$profiler.py:101 start_trace", -1 * MS, 1 * MS],
                ["$threading.py:1 run", 0, 10 * MS],
                ["$core.py:2887 step", 0.2 * MS, 9.5 * MS],
                ["$core.py:3069 _plan_decode", 4.1 * MS, 1.8 * MS],
                ["$numpy asarray", 4.5 * MS, 0.2 * MS],
                ["$profiler.py:213 stop_trace", 10 * MS, 5 * MS]]},
            {"thread": "python2", "events": [["$time sleep", 0, 10 * MS]]},
        ],
    }


def test_hand_made_trace():
    got = tr.reduce(hand_made())
    assert got["window_s"] == pytest.approx(0.010)
    assert got["busy_s"] == pytest.approx(0.006)
    assert got["devices"] == 1
    assert got["modules"]["_megastep_body"] == {"count": 2, "seconds": pytest.approx(0.006)}
    ops = {k: (s, c) for k, s, c in got["ops"]}
    assert ops["_megastep_body/fusion_f32_8_1_37888_"] == (pytest.approx(0.004), 2)
    assert ops["_megastep_body/ragged_paged_attention_kernel"] == (pytest.approx(0.002), 2)
    assert ops["_megastep_body/while"] == (pytest.approx(0.0), 2)     # all of it is its body's
    gaps = dict(got["gaps"])
    # the 2 ms gap falls in the program's _plan_decode (the innermost frame,
    # numpy's asarray, is not the program's); the edge gaps in its step()
    assert gaps["core.py:3069__plan_decode"] == pytest.approx(0.002)
    assert gaps["core.py:2887_step"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"])


def test_overlapping_ops_are_counted_once_in_busy():
    t = hand_made()
    t["devices"][0]["ops"].append(["copy.1", 1.5 * MS, 1 * MS, "", ""])
    assert tr.reduce(t)["busy_s"] == pytest.approx(0.006)


def test_two_devices_average():
    t = hand_made()
    t["devices"].append({"name": "/device:TPU:1", "modules": [],
                         "ops": [["fusion.1", 1 * MS, 2 * MS, "jit__megastep_body", ""]]})
    got = tr.reduce(t)
    assert got["devices"] == 2 and got["busy_s"] == pytest.approx(0.004)


def test_waiting_host_and_tiny_gaps():
    t = hand_made()
    t["host"][0]["events"] = t["host"][0]["events"][:1] + t["host"][0]["events"][-1:]
    t["devices"][0]["ops"].append(["x.1", 4.000 * MS, 1.99 * MS, "", ""])  # leaves 10 us
    gaps = dict(tr.reduce(t)["gaps"])
    assert gaps["gaps_under_20us"] == pytest.approx(10e-6)
    assert gaps["waiting:time_sleep"] == pytest.approx(0.002)


def test_no_device_no_numbers():
    got = tr.reduce({"devices": [], "host": []})
    assert got["busy_s"] == 0.0 and got["devices"] == 0 and got["ops"] == []


def test_slice_keeps_what_overlaps():
    t = tr.slice_of(hand_made(), 3.5)
    assert len(t["devices"][0]["ops"]) == 3 and len(t["devices"][0]["modules"]) == 1
    assert all(e[1] < 4.5 * MS for h in t["host"] for e in h["events"])


@pytest.mark.parametrize("name,want", [
    ("jit__megastep_body(123)", "_megastep_body"),
    ("jit__prefill_and_sample", "_prefill_and_sample"),
    ("_megastep_body", "_megastep_body"),
])
def test_module_names(name, want):
    assert tr._module_name(name) == want


def test_shape_from_the_hlo_text():
    assert tr._shape_of("%fusion.1 = f32[8,1,37888]{2,1,0:T(8,128)} fusion(f32[8] %p)") == "f32_8_1_37888_"
    assert tr._shape_of("%copy.1420 = s32[32]{0:T(128)} copy(s32[32]{0:T(128)} %positions.1)") == "s32_32_"
    assert tr._shape_of("%while.3 = (s32[], f32[8]) while(...)") == ""
    assert tr._shape_of("dot_general.1") == ""


def test_self_time_of_nested_ops():
    ops = [["while.1", 0, 100, "", ""], ["fusion.1", 10, 30, "", ""],
           ["kernel.1", 50, 40, "", ""], ["copy.1", 120, 5, "", ""]]
    assert tr._self_times(ops) == [30, 30, 40, 5]


def test_recorded_slice_from_the_v5e():
    path = DATA / "trace_slice.json"
    if not path.exists():
        pytest.skip("no recorded slice in this tree")
    meta = json.loads((DATA / "trace_slice.expect.json").read_text())
    got = tr.reduce(json.loads(path.read_text()))
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(meta["busy_s"], rel=1e-6)
    assert got["window_s"] == pytest.approx(meta["window_s"], rel=1e-6)
    assert got["ops"][0][0] == meta["top_op"]
    assert set(meta["modules"]) <= set(got["modules"])
