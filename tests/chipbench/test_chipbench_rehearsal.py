"""The whole command, end to end, on the CPU at the tiny size: both
topologies, with and without the profiler. A rehearsal of control flow,
not a measurement; each run has its own time limit."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY = "tests/chipbench/data/tiny_manifest.json"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def clean_env(*drop: str) -> dict:
    """The environment of a user's shell: without the virtual CPU devices
    and flags tests/conftest.py sets for this process."""
    return {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", *drop)}


def run_cell(workload: str, trace: int, *extra: str, timeout: float = 240):
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload, "--seed",
         "3000000019", "--seconds", "5", "--trace", str(trace), "--manifest", TINY,
         *extra],
        cwd=ROOT, env=clean_env(), capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("workload,trace,workers,must_have", [
    ("tiny-closed-1", 1, 1, {"tokens_per_dispatch", "device_idle_share",
                             "warmup_s", "correct_check_s", "closed_loop_ttft_ms_p50"}),
    ("tiny-sessions-4x1", 0, 4, {"setup_s", "ttft_ms_p90"}),
])
def test_whole_command_on_the_cpu(workload, trace, workers, must_have):
    proc = run_cell(workload, trace, "--allow-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) - {"breakdown"} == KEYS
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 5
    assert must_have <= set(result["metrics"]), result["metrics"]
    assert all(isinstance(m["value"], float) and m["unit"] for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == workers
    if trace:
        assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
        assert result["breakdown"]["device_ops"] and result["breakdown"]["idle_gaps"]
    record = json.loads((ROOT / "chipbench_out" / workload / "run.json").read_text())
    assert record["compiled_in_window"] == []
    assert record["reference"]["ok"] and record["reference"]["repeat_identical"]


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    # Without --allow-cpu the worker refuses the fallback device
    # (JAX_PLATFORMS is unset for it, as on a machine with no chip).
    env = clean_env("JAX_PLATFORMS")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "tiny-open-1", "--seed", "1",
         "--seconds", "2", "--trace", "0", "--manifest", TINY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "HARNESS FAULT" in proc.stderr
