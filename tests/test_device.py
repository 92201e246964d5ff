"""Nothing hides the device: compile-cache placement, the worker's
no-silent-CPU guard, the peaks table, asked-for-and-unavailable kernels,
one process per chip. Cheap by construction — no engine is built here.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu import device

pytestmark = [pytest.mark.unit, pytest.mark.pre_merge]

REPO = Path(__file__).resolve().parents[1]


# -- compile cache -------------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_wins_and_no_path_is_set_in_code(
    monkeypatch, restore_cache_dir
):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert device.enable_compile_cache() == "/placed/from/outside"
    # The helper left the config alone: JAX reads the variable itself.
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_inside_the_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = device.enable_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # Same path on every call and nothing in it that varies by process
    # or time: a second start must find what the first one compiled.
    assert device.enable_compile_cache() == first
    assert str(os.getpid()) not in first
    assert "tmp" not in first.lower()
    assert not re.search(r"\d{6,}", first)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


# -- device guard --------------------------------------------------------------


def test_guard_refuses_a_fallback_device_unless_cpu_was_asked_for(monkeypatch):
    # The suite runs with JAX_PLATFORMS=cpu: an explicit request, allowed.
    assert device.cpu_requested()
    assert device.require_accelerator("test")["platform"] == "cpu"
    # Same CPU backend, but nobody asked for it (what JAX does when
    # libtpu finds no chip): refused, with the way out in the message.
    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="found no TPU.*JAX_PLATFORMS=cpu"):
        device.require_accelerator("jax worker")


def test_guard_passes_a_tpu(monkeypatch):
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(device, "device_info", lambda: tpu)
    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    assert device.require_accelerator("jax worker") == tpu


# -- peaks -----------------------------------------------------------------------


def test_peaks_table_raises_on_an_unknown_device_kind():
    v5e = device.device_peaks("TPU v5 lite")
    assert (v5e.bf16_tflops, v5e.hbm_gbps) == (197.0, 819.0)
    assert v5e.source
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            device.device_peaks(kind)


def test_bench_refuses_to_run_without_a_tpu():
    sys.path.insert(0, str(REPO))
    import bench

    with pytest.raises(SystemExit, match="measures a TPU"):
        bench.main()


# -- kernels: asked for and unavailable is an error ------------------------------


def test_paged_attn_pallas_knob_raises_when_unavailable(monkeypatch):
    from dynamo_tpu.ops.paged_attention import paged_attention

    def call(head_dim, block_size):
        q = jnp.zeros((2, 4, head_dim), jnp.bfloat16)
        cache = jnp.zeros((2, 4 * block_size, head_dim), jnp.bfloat16)
        tables = jnp.zeros((2, 2), jnp.int32)
        return paged_attention(
            q, cache, cache, tables, jnp.ones((2,), jnp.int32),
            block_size=block_size,
        )

    assert call(16, 8).shape == (2, 4, 16)  # default knob: the XLA path
    monkeypatch.setenv("DYNAMO_TPU_PAGED_ATTN", "pallas")
    with pytest.raises(ValueError, match="unsupported geometry"):
        call(16, 8)  # head_dim 16: no lane-aligned page DMA
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        call(128, 32)  # geometry fine, but this suite runs on the CPU


def test_int8_page_kernel_raises_outside_interpret_mode():
    """Mosaic refused the int8-page variant on a v5e; compiling it is an
    error carrying the compiler's reason, not a crash deep in lowering."""
    from dynamo_tpu.ops.paged_attention import (
        INT8_PAGES_ON_TPU,
        paged_attention_pallas,
    )

    q = jnp.zeros((2, 4, 128), jnp.bfloat16)
    cache = jnp.zeros((2, 4 * 32, 128), jnp.int8)
    scale = jnp.ones((2, 4 * 32), jnp.float32)
    with pytest.raises(NotImplementedError, match="aligned to tiling"):
        paged_attention_pallas(
            q, cache, cache, jnp.zeros((2, 2), jnp.int32),
            jnp.ones((2,), jnp.int32), block_size=32,
            k_scale=scale, v_scale=scale,
        )
    assert "ROADMAP D6" in INT8_PAGES_ON_TPU


def test_ragged_attention_says_which_implementation_it_chose(caplog):
    from dynamo_tpu.ops import ragged_attention as ra

    ra._announce.cache_clear()
    q = jnp.zeros((4, 4, 16), jnp.float32)
    kv = jnp.zeros((3, 8, 4, 16), jnp.float32)
    args = (
        q, kv, jnp.ones((2,), jnp.int32), jnp.zeros((2, 2), jnp.int32),
        jnp.asarray([0, 2, 4], jnp.int32), jnp.asarray([2], jnp.int32),
    )
    with caplog.at_level("INFO", logger="dynamo_tpu.ops.ragged_attention"):
        ra.ragged_paged_attention(*args, sm_scale=0.25)
        ra.ragged_paged_attention(*args, sm_scale=0.25)
    said = [r.message for r in caplog.records if "ragged attention" in r.message]
    assert len(said) == 1  # once, not per call
    assert "jnp reference (backend is cpu" in said[0]
    assert "head_dim=16" in said[0]


# -- one process per chip ----------------------------------------------------------


def test_launchers_stay_off_jax_and_the_mocker_never_initialises_a_backend():
    """The frontend, the store and the planner's process spawner must not
    even import JAX; the mocker does (through the shared block allocator)
    but serving from it must never initialise a backend — otherwise a
    mocker or planner beside a JAX worker would take that worker's chip."""
    script = """
import asyncio, sys
import dynamo_tpu.frontend.main, dynamo_tpu.runtime.store
import dynamo_tpu.planner.connector
assert "jax" not in sys.modules, "a launcher imported jax"

import dynamo_tpu.backends.mocker.main
from dynamo_tpu.llm.mocker import MockEngineArgs, MockTpuEngine
from dynamo_tpu.llm.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime.engine import Context

async def serve():
    eng = MockTpuEngine(MockEngineArgs(num_kv_blocks=64, block_size=4,
                                       speedup_ratio=1000.0))
    req = PreprocessedRequest(model="m", token_ids=list(range(10)),
                              stop=StopConditions(max_tokens=4),
                              request_id="r").to_wire()
    return [o async for o in eng.generate(req, Context("r"))]

assert asyncio.run(serve())
import jax._src.xla_bridge as xb
assert not xb.backends_are_initialized(), "the mocker initialised a backend"
print("ok")
"""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-2000:]


def test_chip_smoke_parent_has_no_jax_import_at_module_level():
    """chip_smoke.py's own process holds no chip: JAX appears only inside
    the kernel-check child's function body."""
    import ast

    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    top = [
        n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))
    ]
    names = {a.name for n in top if isinstance(n, ast.Import) for a in n.names}
    names |= {n.module for n in top if isinstance(n, ast.ImportFrom)}
    assert not any(
        (m or "").split(".")[0] in ("jax", "dynamo_tpu", "numpy") for m in names
    ), names
