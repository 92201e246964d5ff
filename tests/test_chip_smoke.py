"""chip_smoke.py's CPU rehearsal: the same three processes, requests and
kernel-check child as on the chip, at the `tiny` preset. Slow tier (four
real process fleets); the chip run itself is the driver's.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.e2e]

REPO = Path(__file__).resolve().parents[1]


def _smoke(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )


def test_cpu_tiny_rehearsal_passes_and_names_its_device():
    out = _smoke("--cpu-tiny")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    # (count follows the suite's XLA_FLAGS virtual-device setting)
    assert (last["device"]["platform"], last["device"]["kind"]) == ("cpu", "cpu")
    report = json.loads((REPO / "chip_smoke_out" / "report.json").read_text())
    assert report["repeat_cached_tokens"] > 0
    assert report["radix_index"] == report["radix_index_built"]
    assert report["startup"]["aggregated"]["warmup_phases"]


def test_cpu_rehearsal_serves_a_hybrid_model():
    """Conv state pages beside paired 64-wide heads through the same three
    processes; the kernel check's paired-heads case runs too."""
    out = _smoke("--cpu-tiny", "--cpu-preset", "tiny-lfm2")
    assert out.returncode == 0, out.stderr[-3000:]
    report = json.loads((REPO / "chip_smoke_out" / "report.json").read_text())
    assert report["cpu_preset"] == "tiny-lfm2" and report["repeat_cached_tokens"] > 0
    assert report["startup"]["aggregated"]["cache_layers"] == {"attention": 1, "conv": 5}
    assert any(k.startswith("decode/") for k in report["attention_traced"]["aggregated"])
    # its short prompts never make a wave: every sparse layer ran a step's path, and said so
    assert report["experts_traced"]["aggregated"].get("step/all_rows", 0) >= 4
    assert any("paired 64-wide heads" in c["name"] and c["ok"]
               for c in report["kernels"]["checks"])


def test_without_the_cpu_argument_a_missing_tpu_is_a_failure():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""  # no result of any kind


@pytest.mark.parametrize(
    "phase", ["worker-start", "bad-request", "kernel-mismatch"]
)
def test_a_broken_phase_fails_the_run(phase):
    out = _smoke("--cpu-tiny", "--inject", phase)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "FAILED" in out.stderr


def test_a_window_models_worker_is_held_to_its_window():
    """``judge_window``: a worker that says it has a window pool must have
    traced a window-decode call, and its table may be no wider than one
    dispatch's span."""
    import chip_smoke

    startup = {"window_blocks": 1024, "block_size": 32, "sliding_window": 512, "megastep_k": 8,
               "prefill_bucket_ms": {"256": 1.0, "2048": 9.0}, "window_table_blocks": 82}
    traced = {"decode/library": 3.0, "window-decode/library": 6.0}
    chip_smoke.judge_window("aggregated", startup, traced)
    chip_smoke.judge_window("aggregated", {"cache_layers": {"attention": 2}}, {})   # no pool
    with pytest.raises(chip_smoke.PhaseFailed, match="no window-decode"):
        chip_smoke.judge_window("aggregated", startup, {"decode/library": 3.0})
    with pytest.raises(chip_smoke.PhaseFailed, match="338 columns"):
        chip_smoke.judge_window("aggregated", {**startup, "window_table_blocks": 338}, traced)
    with pytest.raises(chip_smoke.PhaseFailed, match="reference on a TPU"):
        chip_smoke.judge_attention_traced(
            "aggregated", {"decode/library": 3.0, "window-decode/reference": 6.0}, "tpu")


@pytest.mark.parametrize("traced,platform,error", [
    ({"gqa-decode/pallas": 2.0, "window-gqa-decode/pallas": 5.0, "gqa-ragged/pallas": 2.0,
      "window-gqa-ragged/pallas": 5.0}, "tpu", None),
    ({"gqa-decode/jnp": 2.0, "window-gqa-decode/jnp": 3.0, "gqa-ragged/jnp": 2.0,
      "window-gqa-ragged/jnp": 5.0}, "cpu", None),
    ({"gqa-decode/jnp": 2.0, "window-gqa-decode/pallas": 5.0}, "tpu", "chunked jnp walk on a TPU"),
    ({"gqa-decode/pallas": 2.0, "window-gqa-decode/jnp": 5.0}, "tpu", "chunked jnp walk on a TPU"),
    ({"gqa-decode/pallas": 2.0, "window-gqa-decode/pallas": 5.0, "gqa-ragged/jnp": 2.0,
      "window-gqa-ragged/pallas": 5.0}, "tpu", "chunked jnp walk on a TPU"),
    ({"gqa-decode/pallas": 2.0, "window-gqa-decode/pallas": 5.0, "gqa-ragged/pallas": 2.0,
      "window-gqa-ragged/jnp": 5.0}, "tpu", "chunked jnp walk on a TPU"),
    ({"gqa-ragged/pallas": 2.0, "window-gqa-ragged/pallas": 5.0}, "tpu", "no decode-shaped"),
], ids=["the-kernels-on-a-tpu", "the-walk-on-the-cpu", "full-layers-walked-on-a-tpu",
        "window-layers-walked-on-a-tpu", "full-waves-walked-on-a-tpu",
        "window-waves-walked-on-a-tpu", "no-decode-call"])
def test_a_wide_key_models_worker_is_held_to_its_kernel(traced, platform, error):
    """A model of the wide-key page (MiMo): its decode steps trace ``gqa-decode``
    and ``window-gqa-decode`` and its waves ``gqa-ragged`` and
    ``window-gqa-ragged``, on a TPU a Pallas kernel in BOTH shapes and BOTH layer
    kinds (since PR 48 a wave is no longer the chunked ``jnp`` walk), and its
    window pool is held to its window like Laguna's."""
    import chip_smoke

    startup = {"window_blocks": 272, "block_size": 32, "sliding_window": 128, "megastep_k": 8,
               "prefill_bucket_ms": {"256": 1.0, "2048": 9.0}, "window_table_blocks": 70}
    if error is None:
        chip_smoke.judge_attention_traced("aggregated", traced, platform)
        chip_smoke.judge_window("aggregated", startup, traced)
    else:
        with pytest.raises(chip_smoke.PhaseFailed, match=error):
            chip_smoke.judge_attention_traced("aggregated", traced, platform)


def test_a_block_models_worker_is_held_to_its_block_calls(monkeypatch):
    """``judge_blocks``: a worker that says it generates by blocks must have
    traced a block-decode call and no causal one; on a TPU never the jnp
    reference; and its head ran on fewer rows than its block passes did."""
    import chip_smoke

    startup = {"block_length": 4, "denoising_steps": 2, "megastep_k": 6}
    traced = {"block-decode/library": 24.0, "block-ragged/library": 12.0}
    chip_smoke.judge_blocks("aggregated", startup, traced)
    chip_smoke.judge_attention_traced("aggregated", traced, "tpu")
    chip_smoke.judge_blocks("aggregated", {"cache_layers": {"attention": 2}}, {})   # no blocks
    with pytest.raises(chip_smoke.PhaseFailed, match="no block-decode"):
        chip_smoke.judge_blocks("aggregated", startup, {"block-ragged/library": 12.0})
    with pytest.raises(chip_smoke.PhaseFailed, match="causal attention call"):
        chip_smoke.judge_blocks("aggregated", startup, {**traced, "decode/library": 6.0})
    with pytest.raises(chip_smoke.PhaseFailed, match="reference on a TPU"):
        chip_smoke.judge_attention_traced(
            "aggregated", {"block-decode/reference": 24.0}, "tpu")
    rows = {"dynamo_engine_block_head_rows_total{": 600.0, "dynamo_engine_block_rows_total{": 1200.0}
    monkeypatch.setattr(chip_smoke, "metric_lines",
                        lambda url, prefix: [f'{prefix}service="engine"}} {rows[prefix]}'])
    chip_smoke.judge_blocks("aggregated", startup, traced, "http://worker/health")
    rows["dynamo_engine_block_head_rows_total{"] = 1200.0
    with pytest.raises(chip_smoke.PhaseFailed, match="1200 of 1200 block rows"):
        chip_smoke.judge_blocks("aggregated", startup, traced, "http://worker/health")
