"""Tier-1 gate: dynalint over the real tree + per-detector fixture tests.

The tree test is the contract the whole suite enforces: ``python -m
tools.dynalint dynamo_tpu/ tests/`` must exit clean, and every in-source
suppression pragma must be registered in the PRAGMA_ALLOWLIST table below
— adding a new pragma without updating the table fails the build, so
grandfathering stays explicit and reviewed.
"""

from __future__ import annotations

import functools
import sys
from collections import Counter
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.dynalint import config as C                     # noqa: E402
from tools.dynalint.linter import lint_file, lint_paths    # noqa: E402

FIXTURES = REPO / "tests" / "fixtures" / "dynalint"


def rules_at(path: Path) -> list[str]:
    return [f.rule for f in lint_file(path, REPO).findings]


@functools.lru_cache(maxsize=1)
def tree_result():
    """Full-tree lint, computed once — three tests consume it."""
    return lint_paths([REPO / "dynamo_tpu", REPO / "tests"], REPO)


# ---------------------------------------------------------------------------
# The suppression tables (explicit, per-file/per-rule).
# ---------------------------------------------------------------------------

# Findings grandfathered WITHOUT an in-source pragma: {(path, rule): count}.
# Empty today — every finding in the tree was either fixed or carries an
# inline pragma with a reason. New entries need a review justifying why an
# inline pragma is not possible.
GRANDFATHERED: dict[tuple[str, str], int] = {}

# Every in-source pragma, pinned: {(path, kind, arg): count}.
PRAGMA_ALLOWLIST: dict[tuple[str, str, str], int] = {
    # EngineCore helpers called only from under _step_lock (step path and
    # the disagg transfer endpoints lock before calling). Grown by the
    # dynacheck holds-lock-unverified sweep (ISSUE 9): every annotation
    # is now CHECKED along call paths, so the whole plan/commit chain
    # carries it explicitly — _step_locked/_step_async/_plan_step/
    # _plan_waves/_maybe_ring_prefill/_run_ring_prefill, the four
    # per-scheduler commit closures, _apply_verify_row, _account_transfer,
    # plus the original _finish/_sweep_expired_holds/transfer endpoints.
    # +1 in ISSUE 12: the universal-megastep fused commit closure
    # (_plan_fused.commit) joins the verified chain.
    # 17 since ISSUE 12, in two files since ISSUE 45: the transfer
    # endpoints' three (_sweep_expired_holds, _touch_hold,
    # _account_transfer) moved with KvTransfer.
    ("dynamo_tpu/engine/core.py", "holds-lock", "_step_lock"): 14,
    ("dynamo_tpu/engine/kv_transfer.py", "holds-lock", "_step_lock"): 3,
    # Intentional syncs inside blocking-host-sync hot paths: the
    # double-buffered landing point (_PendingFetch.land — tokens +
    # batched logprobs, land_aux for the on-device draft round
    # counters, ISSUE 18, and a sparse model's expert counts, ISSUE 32,
    # which land with the tokens), np.asarray over host block-id lists (dispatch
    # assembly + ring prefill), and the host-tier page staging in
    # _stage_page (host buffer, not a device array).
    # 7 in all; _stage_page's is KvTransfer's (ISSUE 45).
    ("dynamo_tpu/engine/core.py", "sync-ok", ""): 6,
    ("dynamo_tpu/engine/kv_transfer.py", "sync-ok", ""): 1,
    # Host-buffer asarray sites cleared by the dynacheck transitive-
    # blocking sweep: packed-page unpacking and pp microbatch planning
    # operate on host arrays only.
    ("dynamo_tpu/engine/kv_quant.py", "sync-ok", ""): 1,
    ("dynamo_tpu/parallel/pipeline.py", "sync-ok", ""): 2,
    # Deliberately deadline-free awaits (unbounded-await rule): server
    # read loops idling between frames, engine-local queues whose
    # producer is in-process, and push-subscription streams. The
    # consumer-facing bounds live elsewhere (ResponseStream's per-token
    # stall deadline, Subscription.get(timeout)).
    ("dynamo_tpu/engine/engine.py", "unbounded-ok", ""): 1,
    ("dynamo_tpu/llm/disagg_pool/cursor.py", "unbounded-ok", ""): 1,
    ("dynamo_tpu/llm/mocker/engine.py", "unbounded-ok", ""): 1,
    ("dynamo_tpu/runtime/dataplane.py", "unbounded-ok", ""): 2,
    ("dynamo_tpu/runtime/store/client.py", "unbounded-ok", ""): 2,
    ("dynamo_tpu/runtime/store/server.py", "unbounded-ok", ""): 2,
    # The netcost fleet view is a best-effort read of the worker
    # monitor: any failure degrades to local pull observations —
    # routing must never break because a metrics view did (ISSUE 14).
    ("dynamo_tpu/llm/kv_router/netcost.py", "allow", "broad-except"): 1,
    # Best-effort teardown in e2e harnesses: the runtime may already be
    # closed by the time __aexit__ re-closes it.
    ("tests/test_disagg.py", "allow", "broad-except"): 1,
    ("tests/test_disagg_pool.py", "allow", "broad-except"): 2,
    ("tests/test_e2e_frontend.py", "allow", "broad-except"): 1,
    ("tests/test_e2e_jax_worker.py", "allow", "broad-except"): 1,
    ("tests/test_grpc_kserve.py", "allow", "broad-except"): 1,
    ("tests/test_openai_surface.py", "allow", "broad-except"): 1,
    ("tests/test_kv_pool.py", "allow", "broad-except"): 1,
    ("tests/test_peer_kv.py", "allow", "broad-except"): 1,
    # The no-op micro-bench intentionally discards the shared NOOP_SPAN.
    ("tests/test_tracing.py", "allow", "unclosed-span"): 1,
}


# ---------------------------------------------------------------------------
# The tier-1 tree gate.
# ---------------------------------------------------------------------------


def test_tree_is_clean():
    res = tree_result()
    budget = dict(GRANDFATHERED)
    leaked = []
    for f in res.findings:
        key = (f.path, f.rule)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
        else:
            leaked.append(f)
    assert not leaked, "dynalint findings:\n" + "\n".join(str(f) for f in leaked)
    unused = {k: v for k, v in budget.items() if v > 0}
    assert not unused, f"stale GRANDFATHERED entries (tighten the table): {unused}"


def test_pragma_inventory_is_pinned():
    res = tree_result()
    counts = Counter((p.path, p.kind, p.arg) for p in res.pragmas)
    assert dict(counts) == PRAGMA_ALLOWLIST, (
        "in-source dynalint pragmas diverge from PRAGMA_ALLOWLIST; "
        f"actual={dict(counts)}"
    )


def test_registry_covers_promised_modules():
    # The GUARDED_BY registry must keep covering the modules the lint was
    # built for (ISSUE 1): engine core, block allocator, kv_router.
    files = set(C.GUARDED_BY)
    assert "dynamo_tpu/engine/core.py" in files
    assert "dynamo_tpu/engine/kv_transfer.py" in files
    assert "dynamo_tpu/engine/block_allocator.py" in files
    assert any(f.startswith("dynamo_tpu/llm/kv_router/") for f in files)
    # held-block bookkeeping and the import's counters: one lock, in the step
    # loop's file and in the file of the endpoints it inherits
    for path, cls in (("dynamo_tpu/engine/core.py", "EngineCore"),
                      ("dynamo_tpu/engine/kv_transfer.py", "KvTransfer")):
        for attr in ("_held", "_held_deadline", "transfer_stats"):
            assert C.GUARDED_BY[path][(cls, attr)] == "_step_lock", (path, attr)


# ---------------------------------------------------------------------------
# Detector fixtures: each rule catches its seeded violations and stays
# quiet on the clean twin.
# ---------------------------------------------------------------------------


def test_fire_and_forget_detector():
    bad = rules_at(FIXTURES / "fire_and_forget_bad.py")
    assert bad == [C.RULE_FIRE_AND_FORGET] * 4, bad
    assert rules_at(FIXTURES / "fire_and_forget_ok.py") == []


def test_blocking_in_async_detector():
    bad = rules_at(FIXTURES / "blocking_async_bad.py")
    assert bad == [C.RULE_BLOCKING_IN_ASYNC] * 4, bad
    assert rules_at(FIXTURES / "blocking_async_ok.py") == []


def test_broad_except_detector():
    bad = rules_at(FIXTURES / "broad_except_bad.py")
    assert bad == [C.RULE_BROAD_EXCEPT] * 4, bad
    assert rules_at(FIXTURES / "broad_except_ok.py") == []


def test_lock_discipline_detector(monkeypatch):
    entries = {
        ("Guarded", "_table"): "_lock",
        ("Guarded", "count"): "_lock",
        (None, "_handle"): "_glock",
    }
    registry = dict(C.GUARDED_BY)
    registry["fixtures/dynalint/lock_discipline_bad.py"] = entries
    registry["fixtures/dynalint/lock_discipline_ok.py"] = entries
    monkeypatch.setattr(C, "GUARDED_BY", registry)
    bad = rules_at(FIXTURES / "lock_discipline_bad.py")
    assert bad == [C.RULE_LOCK_DISCIPLINE] * 6, bad
    assert rules_at(FIXTURES / "lock_discipline_ok.py") == []


def test_jax_pitfall_detector():
    bad = rules_at(FIXTURES / "jax_pitfall_bad.py")
    assert bad == [C.RULE_JAX_PITFALL] * 5, bad
    assert rules_at(FIXTURES / "jax_pitfall_ok.py") == []


def test_unclosed_span_detector():
    bad = rules_at(FIXTURES / "unclosed_span_bad.py")
    assert bad == [C.RULE_UNCLOSED_SPAN] * 4, bad
    assert rules_at(FIXTURES / "unclosed_span_ok.py") == []


def test_blocking_host_sync_detector():
    bad = rules_at(FIXTURES / "host_sync_bad.py")
    assert bad == [C.RULE_HOST_SYNC] * 4, bad
    assert rules_at(FIXTURES / "host_sync_ok.py") == []


def test_unbounded_await_detector():
    bad = rules_at(FIXTURES / "unbounded_await_bad.py")
    assert bad == [C.RULE_UNBOUNDED_AWAIT] * 4, bad
    assert rules_at(FIXTURES / "unbounded_await_ok.py") == []


def test_host_sync_hot_paths_cover_engine_core():
    # The rule was built for the async engine's plan/dispatch side
    # (ISSUE 5); the megastep plan/dispatch path (ISSUE 7) rides the
    # same registry — a blocking sync inside a k-iteration dispatch
    # would serialize k steps of host work with device compute.
    assert "dynamo_tpu/engine/core.py" in C.HOT_STEP_FUNCS
    funcs = C.HOT_STEP_FUNCS["dynamo_tpu/engine/core.py"]
    assert {
        "_dispatch_ragged", "_dispatch_megastep", "_plan_megastep",
        "_plan_step",
    } <= funcs
    # the pipeline's device bodies, in the file the device programs live in
    assert {"_pp_prefill_and_sample", "_pp_decode_chain"} <= C.HOT_STEP_FUNCS[
        "dynamo_tpu/engine/programs.py"]


def test_pragma_spans_cover_multiline_statements():
    # The line-based matcher missed a pragma on the opening line of a
    # wrapped call whenever the flagged node reported a later lineno;
    # pragmas now anchor to the statement's FULL line span (ISSUE 9).
    ok = lint_file(FIXTURES / "pragma_multiline_ok.py", REPO)
    assert ok.findings == [], [str(f) for f in ok.findings]
    assert len(ok.pragmas) == 3
    # ...and the span anchoring neither mutes unpragma'd statements nor
    # lets a pragma bleed beyond its own statement: a pragma inside a
    # function body must not blanket its siblings, a TRAILING pragma on
    # the last line of a multi-line statement must not cover the next
    # sibling statement, and a pragma on a multi-line def/with HEADER
    # line must not cover the first body statement.
    bad = rules_at(FIXTURES / "pragma_multiline_bad.py")
    assert bad == [C.RULE_BLOCKING_IN_ASYNC] * 5, bad


def test_malformed_pragmas_are_findings():
    res = lint_file(FIXTURES / "pragma_malformed.py", REPO)
    rules = [f.rule for f in res.findings]
    assert rules.count("malformed-pragma") == 3, rules
    # The empty-reason pragma must NOT suppress the violation under it.
    assert C.RULE_BROAD_EXCEPT in rules
    assert res.pragmas == []


def test_cli_exits_clean_on_tree():
    from tools.dynalint.__main__ import main

    assert main([str(REPO / "dynamo_tpu"), str(REPO / "tests")]) == 0


def test_cli_rejects_unknown_rule_filter():
    from tools.dynalint.__main__ import main

    assert main(["--rules", "not-a-rule", str(REPO / "tools")]) == 2


# ---------------------------------------------------------------------------
# Regression tests for the satellite fixes that ride with this lint PR.
# ---------------------------------------------------------------------------


def test_pp_int8_constructs():
    # The carve-out this test originally pinned is LIFTED (ISSUE 20):
    # int8 {w, scale} weight pages now shard per pipeline stage and the
    # engine constructs. The still-unsupported combos keep pointed
    # errors — pinned (both directions) by tests/test_pp_megastep.py.
    import jax

    from dynamo_tpu.engine.config import tiny_engine, tiny_model
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.model import init_params, quantize_params
    from dynamo_tpu.parallel.pipeline import make_pp_mesh

    cfg = tiny_model()
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg))
    core = EngineCore(cfg, tiny_engine(), params=params,
                      pp_mesh=make_pp_mesh(2))
    assert core.scheduler_stats()["pp_stages"] == 2


def test_eos_for_fails_fast_on_broken_tokenizer(tmp_path):
    from dynamo_tpu.backends.jax.main import _eos_for
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    assert _eos_for("byte") == (ByteTokenizer.EOS,)
    # Weights-only checkpoint dir still degrades gracefully (byte-level).
    assert _eos_for(str(tmp_path)) == (ByteTokenizer.EOS,)
    # A genuinely broken spec now fails worker startup instead of silently
    # serving without EOS for the process lifetime (ADVICE r5).
    with pytest.raises((OSError, ValueError)):
        _eos_for(str(tmp_path / "missing.gguf"))


def test_plan_microbatches_masks_zero_query_kv():
    import numpy as np

    from dynamo_tpu.parallel.pipeline import plan_microbatches

    # Two sequences, 8 tokens each, split into 2 chunks of 8 rows: each
    # chunk contains exactly one sequence, so the other sequence has zero
    # query rows there and its kv_len must be pinned to the benign 1.
    T = 16
    plan = plan_microbatches(
        tokens=np.arange(T, dtype=np.int32),
        positions=np.arange(T, dtype=np.int32),
        write_pages=np.zeros(T, np.int32),
        write_offs=np.arange(T, dtype=np.int32) % 8,
        kv_lens=np.array([8, 20], np.int32),   # seq1 carries 12 prior kv
        cu_q_lens=np.array([0, 8, 16], np.int32),
        num_seqs=2,
        last_rows=np.array([7, 15], np.int32),
        n_micro=2,
        garbage_block=31,
    )
    assert plan.kv_lens[0, 0] == 8    # seq0 fully in chunk 0
    assert plan.kv_lens[0, 1] == 1    # seq1 absent from chunk 0: masked
    assert plan.kv_lens[1, 0] == 1    # seq0 absent from chunk 1: masked
    assert plan.kv_lens[1, 1] == 20   # seq1 fully through chunk 1
