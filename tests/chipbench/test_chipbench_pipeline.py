"""The two metrics of the one-step-ahead loop, ``pipelined_dispatch_share``
and ``pipeline_drains_per_kdispatch``: read from a scraped pair, absent
where the program exports no such counter (the parent of the PR that
brought them), entered in the manifest for both cells, and reported by
the CPU rehearsal's tiny cell on the loop the engine chooses."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import manifest
from chipbench.readers import read_metric

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest_pipeline.json"
NEW = {"pipelined_dispatch_share": "%", "pipeline_drains_per_kdispatch": "count"}


def scrape(dispatches: int, pipelined: int | None, drains: int | None) -> dict:
    lines = [f'dynamo_engine_dispatches_total{{service="engine"}} {dispatches}.0']
    if pipelined is not None:
        lines.append(f'dynamo_engine_pipelined_dispatches_total{{service="engine"}} {pipelined}.0')
    if drains is not None:
        lines.append(f'dynamo_engine_pipeline_drains_total{{service="engine"}} {drains}.0')
    return {"worker": ["\n".join(lines) + "\n"], "frontend": []}


@pytest.mark.parametrize("name,expected", [
    ("pipelined_dispatch_share.batch", 100.0 * 380 / 400),
    ("pipeline_drains_per_kdispatch.chat", 1000.0 * 2 / 400),
])
def test_read_from_a_scraped_pair(name, expected):
    ctx = SimpleNamespace(scrape_open=scrape(100, 90, 1), scrape_close=scrape(500, 470, 3))
    assert read_metric("per_layer", name, ctx) == pytest.approx(expected)


@pytest.mark.parametrize("name", [f"{base}.batch" for base in NEW])
def test_a_program_without_the_counter_reads_as_nothing(name):
    ctx = SimpleNamespace(scrape_open=scrape(100, None, None),
                          scrape_close=scrape(500, None, None))
    assert read_metric("per_layer", name, ctx) is None
    # an untraced run scrapes nothing
    assert read_metric("per_layer", name, SimpleNamespace(scrape_open={}, scrape_close={})) is None


def test_manifest_has_them_for_both_cells():
    by_name = {m["name"]: m for m in manifest.load()["per_layer"]}
    for base, unit in NEW.items():
        for suffix, cell, moves in (("batch", "qwen7b-decode-batch", "tpot_ms_p50"),
                                    ("chat", "qwen1p5b-chat-steady", "tpot_ms_mean")):
            m = by_name[f"{base}.{suffix}"]
            assert cell in m["workloads"] and m["moves"] == moves
            assert (m["unit"], m["source"], m["layer"]) == (unit, "program_counter", "scheduler")
            spec = json.loads(manifest.metric_file("per_layer", m["name"]).read_text())
            assert spec["reader"] == "prometheus_ratio" and spec["doc"]
    assert manifest.problems(manifest.load()) == []
    assert manifest.problems(manifest.load(ROOT / TINY)) == []


def test_the_rehearsals_served_loop_is_pipelined():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-closed-p", "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--manifest", TINY, "--allow-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # A closed loop keeps the engine busy: only a dispatch into an empty
    # pipeline (the first, and one after a lull) is not pipelined.
    assert 50 < got["pipelined_dispatch_share.batch"] <= 100
    assert got["pipeline_drains_per_kdispatch.batch"] == 0
    assert got["preemptions_per_kdispatch"] == 0
    record = json.loads((ROOT / "chipbench_out" / "tiny-closed-p" / "run.json").read_text())
    assert record["compiled_in_window"] == []
