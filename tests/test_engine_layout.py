"""The engine's boxes and the way their arrows point (ISSUE 45).

``engine/core.py`` imports the device programs, the KV that leaves the
device, what an engine may be built with, and the placement; none of them
imports it back. What others read THROUGH ``engine.core`` stays readable
there, and the two serving programs stay attributes a live engine looks up
at every dispatch.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path

import pytest

from dynamo_tpu.engine import core as core_mod
from dynamo_tpu.engine import kv_transfer, options, programs
from dynamo_tpu.engine.config import PRESETS, tiny_engine
from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

REPO = Path(__file__).resolve().parent.parent
BOXES = ("dynamo_tpu/engine/programs.py", "dynamo_tpu/engine/kv_transfer.py",
         "dynamo_tpu/engine/options.py", "dynamo_tpu/parallel/placement.py")
TINY = ("tiny", "tiny-moe", "tiny-loop", "tiny-axk1", "tiny-lfm2", "tiny-laguna", "tiny-sdar")
# what a preset's cache needs of the tiny engine (tests/test_laguna.py)
ENGINE = {"tiny-laguna": dict(block_size=4, num_kv_blocks=128)}


def imported_modules(path: str) -> set[str]:
    """Every module a file imports, anywhere in it: ``import a.b``,
    ``from a.b import c`` (as ``a.b`` and ``a.b.c``: ``c`` may be a module)."""
    out: set[str] = set()
    for node in ast.walk(ast.parse((REPO / path).read_text())):
        if isinstance(node, ast.Import):
            out |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            out |= {node.module} | {f"{node.module}.{a.name}" for a in node.names}
    return out


@pytest.mark.parametrize("path", BOXES)
def test_no_box_imports_the_step_loop(path):
    mods = imported_modules(path)
    assert "dynamo_tpu.engine.core" not in mods, path   # nor ``Sequence`` from it
    if path.endswith("programs.py"):
        # pure functions of arrays: no allocator, no lock
        assert not {m for m in mods if m.endswith("block_allocator") or m == "threading"}, mods
    # and the loop does import each of them
    assert path[:-3].replace("/", ".") in imported_modules("dynamo_tpu/engine/core.py")


def test_kv_transfer_touches_the_engine_state_its_docstring_lists():
    doc = kv_transfer.__doc__
    listed = doc[doc.index("Engine state"):doc.index("(End of the list.)")]
    stated = set(re.findall(r"``(\w+)``", listed))
    cls = next(n for n in ast.walk(ast.parse((REPO / BOXES[1]).read_text()))
               if isinstance(n, ast.ClassDef) and n.name == "KvTransfer")

    def self_attrs(node, ctx=ast.expr_context):
        return {n.attr for n in ast.walk(node)
                if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name)
                and n.value.id == "self" and isinstance(n.ctx, ctx)}

    methods = {f.name: f for f in cls.body if isinstance(f, ast.FunctionDef)}
    # its own: its methods, its class attributes, and what ``_init_tiers`` sets
    defined = (set(methods) | self_attrs(methods["_init_tiers"], ast.Store)
               | {t.id for n in cls.body if isinstance(n, ast.Assign) for t in n.targets})
    touched = self_attrs(cls)
    foreign = touched - defined
    assert foreign <= stated, f"not in the docstring's list: {sorted(foreign - stated)}"
    assert stated <= foreign, f"listed and not touched: {sorted(stated - foreign)}"
    # the pools and the callbacks are the class's own (``_init_tiers``)
    assert {"host_pool", "disk_pool", "offload", "_tier_aware"} <= defined
    assert issubclass(EngineCore, kv_transfer.KvTransfer)


# what ``resolve`` fills in beside ``enable_prefix_caching`` (None -> True), as
# the parent's four functions in ``core.py`` returned it for the same input
RESOLVED = {"tiny-laguna": dict(num_window_blocks=80, enable_prefix_caching=False),
            "tiny-sdar": dict(megastep_k=3)}
NAMES = ("_prefill_and_sample", None, "_megastep_body", "_megastep_fused_body",
         "_megastep_draft_body", None, None, "gather_feedback", "pad_feedback")


@pytest.mark.parametrize("preset", TINY)
def test_the_programs_keep_their_names_and_resolve_fills_in_what_it_did(preset):
    cfg, eng = PRESETS[preset](), tiny_engine(megastep_k=4, **ENGINE.get(preset, {}))
    model_cfg, engine_cfg = options.resolve(cfg, eng, None, None, None)
    assert model_cfg == cfg
    assert engine_cfg == dataclasses.replace(
        eng, **{"enable_prefix_caching": True, **RESOLVED.get(preset, {})})

    # ``_prefill``, ``_ring``, ``_decode``, ``_fused``, ``_drafted``, ``_prefill_pp``,
    # ``_decode_pp``, ``_feed``, ``_feed_pad``: the profile's module names
    # (chipbench/layer_metrics/*.json, chipbench/trace/); no ring, no pipeline
    built = programs.compile_programs(model_cfg, engine_cfg, None, None, None, 1)
    assert tuple(p and p.__name__ for p in built) == NAMES


@pytest.mark.parametrize("kind, buckets", [("prefill", (24, 64)), ("decode", (3, 4))])
def test_buckets_are_held_to_the_pipelines_one_microbatch_count(kind, buckets):
    # one definition (parallel/pipeline.py) read by ``resolve`` and by ``place``
    from dynamo_tpu.parallel.pipeline import make_pp_mesh, pp_microbatches

    assert pp_microbatches(3) == 3
    eng = tiny_engine(**{"prefill_buckets": (24, 48), f"{kind}_buckets": buckets})
    with pytest.raises(ValueError, match=f"{kind} bucket {buckets[-1]} not a multiple of pp "
                                         "microbatch count 3"):
        options.resolve(dataclasses.replace(PRESETS["tiny"](), num_layers=3, vocab_size=258),
                        eng, None, None, make_pp_mesh(3))


def test_what_others_read_through_core_and_rebind_on_a_live_engine():
    # chipbench/rehearse_v5e.py:137-165, tests/chipbench/test_chipbench_sdar.py:60
    assert core_mod._prefill_and_sample is programs._prefill_and_sample
    assert core_mod._megastep_body is programs._megastep_body
    assert core_mod._program is programs._program
    assert core_mod._resolve_block_megastep is options._resolve_block_megastep
    assert core_mod.pack_lanes is programs.pack_lanes   # tests/test_host_leg.py rebinds it

    # chipbench/rehearse_v5e.py:88-89 rebinds the two on a built engine
    core = EngineCore(PRESETS["tiny"](), tiny_engine(megastep_k=4), seed=0)
    seen = []

    def recording(name, program):
        def stand_in(*args, **kwargs):
            seen.append(name)
            return program(*args, **kwargs)
        return stand_in

    core._prefill = recording("_prefill", core._prefill)
    core._decode = recording("_decode", core._decode)
    seq = core.add_request(PreprocessedRequest(
        model="tiny", token_ids=list(range(1, 12)), request_id="r",
        sampling=SamplingOptions(temperature=0.0), stop=StopConditions(max_tokens=6)))
    for _ in range(50):
        core.step()
        if seq.finish:
            break
    assert seq.generated == 6
    assert seen[0] == "_prefill" and "_decode" in seen
