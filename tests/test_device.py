"""Nothing hides the device: compile-cache placement, the worker's
no-silent-CPU guard, the peaks table, which attention a program got,
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


# -- kernels: the choice is said ---------------------------------------------------


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


# -- the README names files that exist ---------------------------------------------


def test_the_readme_names_only_files_of_the_repo():
    """Every backticked word of README.md that ends in .py, .json, .md or
    .yml (wildcards and placeholders aside) is the path, or the tail of
    the path, of a file git lists: the README writes some paths relative
    to a package. A deleted file still named there fails here."""
    listed = subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True
    )
    files = listed.stdout.split() if listed.returncode == 0 else []
    if not files:  # not a git checkout: what is on disk
        files = [str(p.relative_to(REPO)) for p in REPO.rglob("*.*")]
    missing = set()
    for span in re.findall(r"`([^`\n]+)`", (REPO / "README.md").read_text()):
        for word in span.split():
            word = word.strip(",;:()\"'").rstrip(".")
            if re.search(r"\.(py|json|md|yml)$", word) and not re.search(r"[*<>{}$]", word):
                if not any(f == word or f.endswith("/" + word) for f in files):
                    missing.add(word)
    assert not missing, f"README.md names files the repo does not have: {sorted(missing)}"
