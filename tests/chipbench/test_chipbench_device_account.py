"""The five metrics of the step clock's account of the device (ISSUE 37):
``decode_ms_per_token``, ``prefill_stall_ms_per_token``,
``host_stall_ms_per_token``, ``device_starved_share`` (counters, from a
scraped pair) and ``device_account_error`` (the account held to the device
trace by a reader of its own): read from hand-made scrapes and a hand-made
trace, absent where the program keeps no account (the parent of the PR that
brought them), entered in the manifest for every cell (PR 41: one entry a
metric, ``.chat`` beside it for the cell that moves ``tpot_ms_mean``), and
reported by the CPU rehearsal's tiny cell under a manifest of its own."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import manifest
from chipbench.readers import device_account, read_metric

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_account.json"
CELL = "tiny-closed-a"
NEW = {
    "decode_ms_per_token": ("ms", "program_counter", "device programs"),
    "prefill_stall_ms_per_token": ("ms", "program_counter", "scheduler"),
    "host_stall_ms_per_token": ("ms", "program_counter", "scheduler"),
    "device_starved_share": ("%", "program_counter", "device"),
    "device_account_error": ("%", "device_trace", "device"),
}


def scrape(tokens, decode, wave, host, upper, lower, phases) -> dict:
    lines = [
        f'dynamo_engine_decode_tokens_committed_total{{service="engine"}} {tokens}',
        f'dynamo_engine_lane_seconds_total{{service="engine",state="decode"}} {decode}',
        f'dynamo_engine_lane_seconds_total{{service="engine",state="behind_prefill"}} {wave}',
        f'dynamo_engine_lane_seconds_total{{service="engine",state="behind_host"}} {host}',
        'dynamo_engine_device_starved_seconds_total{service="engine",bound="upper",'
        f'phase="plan",after="prefill"}} {upper * 0.75}',
        'dynamo_engine_device_starved_seconds_total{service="engine",bound="upper",'
        f'phase="h2d",after="megastep"}} {upper * 0.25}',
        'dynamo_engine_device_starved_seconds_total{service="engine",bound="lower",'
        f'phase="plan",after="prefill"}} {lower}',
        f'dynamo_engine_step_phase_seconds_total{{service="engine",phase="land",'
        f'blocks="device_wait"}} {phases * 0.8}',
        f'dynamo_engine_step_phase_seconds_total{{service="engine",phase="plan",'
        f'blocks="host"}} {phases * 0.2}',
    ]
    return {"worker": ["\n".join(lines) + "\n"], "frontend": []}


@pytest.mark.parametrize("name,expected", [
    ("decode_ms_per_token", 1000.0 * 400.0 / 32000),
    ("prefill_stall_ms_per_token.chat", 1000.0 * 40.0 / 32000),
    ("host_stall_ms_per_token", 1000.0 * 1.6 / 32000),
    ("device_starved_share.chat", 100.0 * 0.2 / 45.0),       # the upper bound only
])
def test_read_from_a_scraped_pair(name, expected):
    ctx = SimpleNamespace(
        scrape_open=scrape(1000, 10.0, 1.0, 0.4, 0.2, 0.1, 5.0),
        scrape_close=scrape(33000, 410.0, 41.0, 2.0, 0.4, 0.15, 50.0))
    assert read_metric("per_layer", name, ctx) == pytest.approx(expected)


@pytest.mark.parametrize("name", list(NEW))
def test_a_program_without_the_account_reads_as_nothing(name):
    parent = {"worker": ['dynamo_engine_step_phase_seconds_total{service="engine",'
                         'phase="plan",blocks="host"} 3.0\n'
                         'dynamo_engine_dispatches_total{service="engine"} 9.0\n'],
              "frontend": []}
    ctx = SimpleNamespace(scrape_open=parent, scrape_close=parent, trace=None,
                          cell={"name": "no-such-cell"})
    assert read_metric("per_layer", name, ctx) is None


# -- the account against a hand-made trace ------------------------------------------

MS = 1e6   # ns


def hand_made(attrs: bool = True, device_ms=(40.0, 90.0, 91.0)) -> dict:
    """Three dispatches inside a slice: a wave then two megasteps. The
    device's clock runs 1 ms behind the host's. Dispatch 2 starts 6 ms
    after the wave ends (the device starved), 3 right behind 2."""
    shift = -1 * MS
    programs = [("jit__prefill_and_sample(1)", 10 * MS, 40 * MS, "r1"),
                ("jit__megastep_body(2)", 56 * MS, 90 * MS, "r2"),
                ("jit__megastep_body(2)", 146 * MS, 91 * MS, "r3")]
    kinds = ("prefill", "megastep", "megastep")
    phases, enqueues = [], []
    for i, (_, start, dur, run_id) in enumerate(programs):
        no = str(7 + i)
        enq = [9.5 * MS, 55.5 * MS, 100 * MS][i]
        phases.append(["h2d", enq - 3 * MS, 2.5 * MS, {}])
        # the runtime enqueues the second program just AFTER the jitted call returned
        phases.append(["dispatch", enq - 0.5 * MS, 0.4 * MS if i == 1 else 0.7 * MS,
                       {"no": no, "kind": kinds[i]} if attrs else {"kind": kinds[i]}])
        enqueues.append([enq, run_id])
        landed = start + dur + 0.2 * MS
        stats = {}
        if attrs:
            stats = {"no": no, "kind": kinds[i], "device_ms": str(device_ms[i]),
                     "starved_lower_ms": ["0.0", "4.5", "0.0"][i],
                     "starved_upper_ms": ["0.0", "7.0", "0.0"][i],
                     "late": ["0.0", "0.0", "0.0"][i]}
        phases.append(["commit", landed, 1.5 * MS, stats])
    # a feedback gather enqueued under ``h2d``: the staging of dispatch 8, not
    # a program of dispatch 7
    enqueues.append([54 * MS, "r9"])
    modules = [[n, s + shift, d, r] for n, s, d, r in programs]
    modules.append(["jit_gather_feedback(3)", 54.2 * MS + shift, 0.01 * MS, "r9"])
    return {"ops": [["%fusion", m[1], m[2], "", ""] for m in modules], "modules": modules,
            "phases": sorted(phases, key=lambda e: e[1]), "enqueues": enqueues,
            "completes": [], "window": [0.0, 250 * MS], "device": "/device:TPU:0"}


def test_the_reader_pairs_each_estimate_with_its_programs():
    got = device_account.compare(hand_made())
    assert got["dispatches"] == 3 and got["late"] == 0
    assert got["programs_device_s"] == pytest.approx(0.221)
    assert got["account_device_s"] == pytest.approx(0.221)
    assert got["error_pct"] == pytest.approx(0.0, abs=1e-9)
    assert got["by_kind"]["prefill"] == pytest.approx({"account_s": 0.040, "programs_s": 0.040})
    assert got["by_kind"]["megastep"]["programs_s"] == pytest.approx(0.181)
    # two consecutive pairs; the device idled 6 ms before dispatch 8, none before 9
    assert got["pairs"] == 2
    assert got["trace_idle_between_s"] == pytest.approx(0.006)
    assert got["account_starved_lower_s"] <= got["trace_idle_between_s"] \
        <= got["account_starved_upper_s"]
    # dispatch 9 was enqueued while 8 ran: what lies between them is the device's own
    assert got["trace_launch_gaps_s"] == pytest.approx(0.0)
    late = hand_made()
    late["modules"][2][1] += 0.02 * MS       # 9 starts 20 us after 8 ends, though queued
    got = device_account.compare(late)
    assert got["trace_launch_gaps_s"] == pytest.approx(2e-5)
    assert got["trace_idle_between_s"] == pytest.approx(0.006)


def test_the_error_is_the_share_by_which_the_account_misses():
    got = device_account.compare(hand_made(device_ms=(40.0, 94.42, 91.0)))
    assert got["error_pct"] == pytest.approx(100 * 4.42 / 221.0)


def test_without_the_attrs_or_without_programs_there_is_nothing_to_read():
    assert device_account.compare(hand_made(attrs=False))["error_pct"] is None
    empty = {"ops": [], "modules": [], "phases": [], "enqueues": [], "completes": [],
             "window": [None, None], "device": ""}
    assert device_account.compare(empty) == {"error_pct": None, "landings": 0}
    ctx = SimpleNamespace(trace=None, cell={"name": "no-such-cell"})
    assert device_account.read(ctx) is None                       # an untraced run
    ctx.trace = {"devices": 1}
    assert device_account.read(ctx) is None                       # no trace was left
    leftover = ROOT / "chipbench_out" / "no-such-cell"
    if leftover.exists():
        for f in leftover.iterdir():
            f.unlink()
        leftover.rmdir()


def test_the_cpu_backends_ops_stand_in_for_the_programs():
    """No program events on the CPU backend: the ops' union between a
    landing that waited and the slice's last landing."""
    trace = hand_made()
    trace["modules"], trace["enqueues"], trace["device"] = [], [], "/host:CPU (cpu backend)"
    trace["ops"] = [["fusion", 56 * MS, 45 * MS, "jit__megastep_body", ""],
                    ["fusion.1", 100 * MS, 46 * MS, "jit__megastep_body", ""],   # overlaps
                    ["fusion", 146 * MS, 91 * MS, "jit__megastep_body", ""]]
    got = device_account.compare(trace)
    # from dispatch 7's landing (50.2 ms) to dispatch 9's (237.2 ms)
    assert got["dispatches"] == 2
    assert got["programs_device_s"] == pytest.approx(0.181)
    assert got["account_device_s"] == pytest.approx(0.181)
    assert got["trace_idle_between_s"] == pytest.approx(0.187 - 0.181)
    assert got["error_pct"] == pytest.approx(0.0, abs=1e-9)


# -- the manifest ------------------------------------------------------------------------


def test_manifest_has_the_account_for_every_cell():
    # Renamed in PR 41 (it was ..._the_ten_entries_for_the_two_dense_cells, and
    # tests/conftest.py, which that PR could not edit, still cuts the per-layer
    # list for a test of that name): the five stand wherever, for all six cells.
    man = manifest.load()
    by_name = {m["name"]: m for m in man["per_layer"]}
    chat = "qwen1p5b-chat-steady"
    # at least the five closed-loop cells of PR 41, wherever they stand in the
    # list; a later cell joins the list or not
    others = {"qwen7b-decode-batch", "ouro2p6b-reason-decode", "axk1-ep16-decode",
              "lfm2-24b-hybrid-decode", "laguna-s21-longctx-agents"}
    for base, (unit, source, layer) in NEW.items():
        for name, cells, moves in ((base, others, "tpot_ms_p50"),
                                   (f"{base}.chat", {chat}, "tpot_ms_mean")):
            m = by_name[name]
            assert cells <= set(m["workloads"]) and m["moves"] == moves
            assert (m["unit"], m["source"], m["layer"], m["better"]) == (
                unit, source, layer, "lower")
            path = manifest.metric_file("per_layer", m["name"])
            assert path.name == f"{base}.json"
            spec = json.loads(path.read_text())
            assert spec["doc"] and spec["reader"] == (
                "device_account" if base == "device_account_error" else "prometheus_ratio")
    assert manifest.problems(man) == []
    assert manifest.problems(manifest.load(ROOT / TINY)) == []


def test_the_four_counter_files_read_the_series_the_program_exports():
    from dynamo_tpu.tracing.stepclock import LANE_STATES

    lanes = {}
    for base, state in (("decode_ms_per_token", "decode"),
                        ("prefill_stall_ms_per_token", "behind_prefill"),
                        ("host_stall_ms_per_token", "behind_host")):
        args = json.loads(manifest.metric_file("per_layer", base).read_text())["args"]
        assert args["numerator"] == {"name": "dynamo_engine_lane_seconds_total",
                                     "labels": {"state": state}}
        assert args["denominator"] == {"name": "dynamo_engine_decode_tokens_committed_total"}
        assert args["scale"] == 1000.0 and args["endpoint"] == "worker"
        lanes[state] = base
    assert set(lanes) == set(LANE_STATES)
    args = json.loads(manifest.metric_file("per_layer", "device_starved_share").read_text())["args"]
    assert args["numerator"] == {"name": "dynamo_engine_device_starved_seconds_total",
                                 "labels": {"bound": "upper"}}
    assert args["denominator"] == {"name": "dynamo_engine_step_phase_seconds_total"}
    assert args["scale"] == 100.0


# -- the rehearsal ---------------------------------------------------------------------------


def test_the_rehearsal_reports_all_five():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got)
    # A token costs something in device steps; the rest is the host's on a
    # CPU, where the "device" is the host's own threads.
    assert got["decode_ms_per_token"] > 0
    assert got["prefill_stall_ms_per_token"] >= 0
    assert got["host_stall_ms_per_token"] >= 0
    assert 0 <= got["device_starved_share"] <= 100
    assert got["device_account_error"] >= 0
    found = json.loads((ROOT / "chipbench_out" / CELL / "device_account.json").read_text())
    assert found["dispatches"] > 10
    assert found["account_starved_lower_s"] <= found["account_starved_upper_s"]
