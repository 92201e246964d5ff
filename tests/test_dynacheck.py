"""Tier-1 gate for dynacheck (ISSUE 9): the tree runs both engines
clean, the suppression inventory is pinned, every interprocedural rule
and every model invariant provably fires on a seeded violation, the
report is byte-deterministic, and the full run fits the CI budget.
"""

from __future__ import annotations

import functools
import sys
import time
from collections import Counter
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.dynacheck import config as C                         # noqa: E402
from tools.dynacheck.__main__ import main, run                  # noqa: E402
from tools.dynacheck import cache as CA                         # noqa: E402
from tools.dynacheck.callgraph import build_project             # noqa: E402
from tools.dynacheck.explore import explore                     # noqa: E402
from tools.dynacheck.interproc import run_all                   # noqa: E402
from tools.dynacheck.models.allocator import AllocatorModel     # noqa: E402
from tools.dynacheck.models.breaker import BreakerModel         # noqa: E402
from tools.dynacheck.models.cursor import (                     # noqa: E402
    CursorModel,
    PPWavefrontModel,
)
from tools.dynacheck.models.keepalive import KeepaliveModel     # noqa: E402
from tools.dynacheck.models.planner import PlannerModel         # noqa: E402
from tools.dynacheck.models.quarantine import QuarantineModel   # noqa: E402
from dynamo_tpu.planner.controller import PlannerController     # noqa: E402

FIXTURES = REPO / "tests" / "fixtures" / "dynacheck"


def fixture_findings(files: list[str], monkeypatch=None, hot=None, guarded=None):
    """Engine A over explicit fixture files (the tree scan excludes the
    fixture dir, so tests hand the files in directly)."""
    if monkeypatch is not None:
        if hot is not None:
            monkeypatch.setattr(C, "HOT_STEP_FUNCS", hot)
        if guarded is not None:
            monkeypatch.setattr(C, "GUARDED_BY", guarded)
    paths = [FIXTURES / f for f in files]
    project = build_project(paths, REPO)
    return run_all(project)


@functools.lru_cache(maxsize=1)
def tree_report():
    """Full-tree dynacheck, computed once — several tests consume it."""
    return run([REPO / "dynamo_tpu"], REPO, engine="all", use_cache=False)


# ---------------------------------------------------------------------------
# The tier-1 tree gate + pinned pragma inventory.
# ---------------------------------------------------------------------------

# Every in-source dynacheck pragma, pinned: {(path, rule): count}. Adding
# a pragma without updating this table fails the build — grandfathering
# stays explicit and reviewed, exactly like dynalint's allowlist.
PRAGMA_ALLOWLIST: dict[tuple[str, str], int] = {
    # The ring-prefill path is deliberately synchronous (sp engines keep
    # the classic loop; the single long prompt IS the step), so its two
    # landings are justified, not moved.
    ("dynamo_tpu/engine/core.py", "transitive-blocking"): 2,
    # import_blocks_direct takes two instances of EngineCore._step_lock
    # under a global id()-ordered acquisition — mutual pulls can never
    # deadlock, which the analysis cannot prove but review did.
    ("dynamo_tpu/engine/kv_transfer.py", "lock-order"): 1,
}


def test_tree_is_clean():
    rep = tree_report()
    assert rep.findings == [], "\n".join(str(f) for f in rep.findings)
    for m in rep.models:
        assert m.ok, "\n".join(str(v) for v in m.violations)


def test_pragma_inventory_is_pinned():
    rep = tree_report()
    counts = Counter((p.path, p.rule) for p in rep.pragmas)
    assert dict(counts) == PRAGMA_ALLOWLIST, (
        "in-source dynacheck pragmas diverge from PRAGMA_ALLOWLIST; "
        f"actual={dict(counts)}"
    )


# Per-model floor on the explored state count: exhaustion with a
# suspiciously small space usually means the action set silently shrank.
# keepalive is a compact boolean protocol — its whole space IS small.
MODEL_FLOORS = {
    "allocator": 100, "cursor": 100, "pp-wavefront": 100, "breaker": 100,
    "quarantine": 100, "keepalive": 5, "planner": 100,
}


def test_models_exhaust_their_state_spaces():
    # The bounded exploration genuinely covers everything reachable: the
    # frontier empties before the depth bound for all six models, so
    # "no violation" means no violation anywhere, not "none within an
    # arbitrary horizon".
    rep = tree_report()
    assert {m.name for m in rep.models} == set(MODEL_FLOORS)
    for m in rep.models:
        assert m.exhausted, f"{m.name}: depth bound hit before exhaustion"
        assert m.states > MODEL_FLOORS[m.name], (
            f"{m.name}: suspiciously small state space ({m.states})"
        )


def test_call_graph_covers_the_engine():
    rep = tree_report()
    assert rep.functions > 500
    assert rep.resolved_edges > 500


# ---------------------------------------------------------------------------
# Engine A fixtures: each rule catches its seeded violation and stays
# quiet on the clean shapes.
# ---------------------------------------------------------------------------


def test_deadlock_cycle_detected():
    findings = fixture_findings(
        ["deadlock_pkg/engine_side.py", "deadlock_pkg/egress_side.py"]
    )
    lock_order = [f for f in findings if f.rule == C.RULE_LOCK_ORDER]
    assert len(lock_order) == 1, [str(f) for f in findings]
    msg = lock_order[0].message
    assert "_alock" in msg and "_block" in msg and "cycle" in msg


def test_a_mixins_lock_is_its_inheritors():
    # KvTransfer takes the step lock EngineCore constructs (ISSUE 45): a
    # base's ``self._lock`` and its inheritor's are one identity.
    findings = fixture_findings(["deadlock_pkg/mixin.py"])
    lock_order = [f for f in findings if f.rule == C.RULE_LOCK_ORDER]
    assert len(lock_order) == 1, [str(f) for f in findings]
    msg = lock_order[0].message
    assert "EngineSide._alock" in msg and "EngineSide._block" in msg and "cycle" in msg
    assert "TransferSide._" not in msg


def test_three_lock_cycle_reported_not_crashed():
    # A cycle of 3+ locks whose edge order differs from the sorted node
    # order: the witness lookup must follow ACTUAL graph edges (a sorted
    # SCC is a set, not an edge sequence).
    findings = fixture_findings(["deadlock_pkg/three_way.py"])
    lock_order = [f for f in findings if f.rule == C.RULE_LOCK_ORDER]
    assert len(lock_order) == 1, [str(f) for f in findings]
    msg = lock_order[0].message
    assert "_xlock" in msg and "_ylock" in msg and "_zlock" in msg


def test_transitive_blocking_detected(monkeypatch):
    hot = {"fixtures/dynacheck/blocking_pkg/hot.py": {"plan_step"}}
    findings = fixture_findings(
        ["blocking_pkg/hot.py", "blocking_pkg/helper.py"],
        monkeypatch, hot=hot,
    )
    trans = [f for f in findings if f.rule == C.RULE_TRANSITIVE_BLOCKING]
    whats = sorted(f.message.split(" is reachable")[0] for f in trans)
    assert whats == ["np.asarray()", "time.sleep()"], [str(f) for f in findings]
    assert all("plan_step" in f.message and "assemble_tables" in f.message
               for f in trans)


def test_coroutine_leaks_detected():
    findings = fixture_findings(["coroleak_pkg/leaky.py"])
    leaks = [f for f in findings if f.rule == C.RULE_CORO_LEAK]
    assert len(leaks) == 2, [str(f) for f in findings]
    assert any("immediately" in f.message for f in leaks)      # dropped
    assert any("'pending'" in f.message for f in leaks)        # bound, unused


def test_cursor_discipline_detected():
    findings = fixture_findings(["cursor_pkg/writer.py"])
    cursor = [f for f in findings if f.rule == C.RULE_CURSOR]
    msgs = " | ".join(f.message for f in cursor)
    assert len(cursor) == 3, [str(f) for f in findings]
    assert "seq.processed" in msgs
    assert "seq.pinned_hashes" in msgs
    assert "blk.refcount" in msgs
    assert "reads_are_fine" not in msgs


def test_holds_lock_annotation_verified():
    findings = fixture_findings(["holdslock_pkg/unheld.py"])
    holds = [f for f in findings if f.rule == C.RULE_HOLDS_LOCK_UNVERIFIED]
    assert len(holds) == 1, [str(f) for f in findings]
    assert "bad_caller" in holds[0].message
    assert "good_caller" not in holds[0].message


def test_registry_drift_detected(monkeypatch):
    guarded = {
        "fixtures/dynacheck/holdslock_pkg/unheld.py": {
            ("Guarded", "table"): "_lock",          # healthy: no finding
            ("Guarded", "ghost_attr"): "_lock",     # never mutated: stale
            ("Guarded", "unlocked"): "_other_lock", # lock doesn't exist
            ("Vanished", "x"): "_lock",             # class doesn't exist
        },
    }
    findings = fixture_findings(
        ["holdslock_pkg/unheld.py"], monkeypatch, guarded=guarded,
    )
    drift = [f for f in findings if f.rule == C.RULE_REGISTRY_DRIFT]
    msgs = " | ".join(f.message for f in drift)
    assert len(drift) == 3, [str(f) for f in findings]
    assert "ghost_attr" in msgs and "Vanished" in msgs
    assert "table" not in msgs.replace("ghost_attr", "")


def test_real_guarded_by_registry_has_no_drift():
    # The hand-maintained registry (PR 1, five refactors ago) now fails
    # CI if an entry rots — this asserts today's registry is sound.
    rep = tree_report()
    assert not [f for f in rep.findings if f.rule == C.RULE_REGISTRY_DRIFT]


def test_wire_contract_detected(monkeypatch):
    monkeypatch.setattr(
        C, "WIRE_SCHEMA_FILE", "fixtures/dynacheck/wire_pkg/wire.py"
    )
    monkeypatch.setattr(
        C, "WIRE_PLANE_FILES",
        {"fixtures/dynacheck/wire_pkg/frames.py": ("alpha", "beta")},
    )
    findings = fixture_findings(["wire_pkg/wire.py", "wire_pkg/frames.py"])
    wirefs = [f for f in findings if f.rule == C.RULE_WIRE_CONTRACT]
    msgs = " | ".join(f.message for f in wirefs)
    assert "A_ORPHAN" in msgs and "produced here but consumed nowhere" in msgs
    assert "A_GHOST" in msgs and "consumed here but produced nowhere" in msgs
    assert "raw string literal 'b'" in msgs        # send-site backslide
    assert "conflicting meaning" in msgs           # cross-plane 't' collision
    assert "B_UNUSED" in msgs                      # registered, unreferenced
    # The healthy produced+consumed pair stays quiet.
    assert "A_BODY is" not in msgs and "A_TYPE is" not in msgs


def test_loop_affinity_detected(monkeypatch):
    monkeypatch.setattr(
        C, "LOOP_AFFINE",
        {"fixtures/dynacheck/affinity_pkg/threads.py": {
            ("Publisher", "_ringbuf"): "fixture ring buffer",
        }},
    )
    findings = fixture_findings(["affinity_pkg/threads.py"])
    aff = [f for f in findings if f.rule == C.RULE_LOOP_AFFINITY]
    assert len(aff) == 1, [str(f) for f in findings]
    msg = aff[0].message
    assert "_flush" in msg and "_drain_blocking" in msg
    # The on-loop write in publish() must stay quiet.
    assert "publish" not in msg


def test_config_knobs_detected(monkeypatch):
    monkeypatch.setattr(
        C, "KNOB_REGISTRY_FILE", "fixtures/dynacheck/knob_pkg/knobs.py"
    )
    monkeypatch.setattr(
        C, "KNOB_DOC_FILE", "tests/fixtures/dynacheck/knob_pkg/README.md"
    )
    findings = fixture_findings(["knob_pkg/knobs.py", "knob_pkg/reader.py"])
    knob = [f for f in findings if f.rule == C.RULE_CONFIG_KNOB]
    msgs = " | ".join(f.message for f in knob)
    assert "'FIX_GHOST' is read here but not registered" in msgs
    assert "'FIX_DIRECT' bypasses the registry" in msgs
    assert "literal default for 'FIX_ALPHA'" in msgs
    assert "dynamically-built name" in msgs
    assert "FIX_DEAD is registered but read nowhere" in msgs
    assert "FIX_SECRET is registered but undocumented" in msgs
    assert "documents FIX_ROT" in msgs
    # Exactly one unresolvable-name finding: the pragma'd read next to it
    # is suppressed.
    assert sum("dynamically-built" in f.message for f in knob) == 1
    # Clean reads (literal, module-constant) stay quiet.
    assert "FIX_BETA" not in msgs


# ---------------------------------------------------------------------------
# Engine B: every model invariant can actually fire. Each buggy variant
# seeds the exact bug class the invariant was written against.
# ---------------------------------------------------------------------------


class _DoubleReleaseModel(AllocatorModel):
    """Re-introduces the PR-3 bug: releasing a sequence's pins twice."""

    name = "allocator-double-release"

    def actions(self, state):
        acts = super().actions(state)
        for s in ("A", "B"):
            if state.started[s] and state.pinned[s]:
                acts.append(
                    (f"double_release_{s}", self._mk(self._double_release, s))
                )
        acts.sort(key=lambda kv: kv[0])
        return acts

    @staticmethod
    def _double_release(state, s):
        st = state.clone()
        pins = list(st.pinned[s])
        st.alloc.release(pins)
        st.alloc.release(pins)   # the double-release
        st.pinned[s] = []
        st.next_idx[s] = 0
        st.started[s] = False
        return st


def test_allocator_model_catches_double_release():
    m = _DoubleReleaseModel()
    m.max_depth = 8
    res = explore(m)
    assert res.violations, "double-release survived the allocator invariants"
    assert any("refcount" in str(v) for v in res.violations)


class _NoBarrierCursorModel(CursorModel):
    """Removes the verify barrier: plans over a data-dependent in-flight
    step, reading an overlay the commit will contradict."""

    name = "cursor-no-barrier"

    def actions(self, state):
        acts = super().actions(state)
        if (
            state.inflight is not None
            and not state.inflight.deterministic
            and state.finished is None
        ):
            acts.append(("plan_over_verify", lambda s: self._step_async(s, 1)))
        acts.sort(key=lambda kv: kv[0])
        return acts


def test_cursor_model_catches_missing_verify_barrier():
    m = _NoBarrierCursorModel()
    m.max_depth = 8
    res = explore(m)
    assert res.violations, "overlay misread survived the cursor invariants"
    assert any("diverged" in str(v) or "drift" in str(v) for v in res.violations)


class _RollbackFreeCursorModel(CursorModel):
    """Commits the optimistic advance instead of the stop-scanned one —
    i.e. deletes the num_computed_tokens rollback."""

    name = "cursor-no-rollback"

    def actions(self, state):
        acts = [(n, fn) for n, fn in super().actions(state)]
        if state.inflight is not None:
            acts.append(("commit_no_rollback", self._commit_no_rollback))
        acts.sort(key=lambda kv: kv[0])
        return acts

    @staticmethod
    def _commit_no_rollback(state):
        from dataclasses import replace
        plan = state.inflight
        if state.finished is not None:
            return replace(state, inflight=None)
        toks = plan.outputs  # NO stop scan: everything lands
        return replace(
            state, inflight=None,
            processed=state.processed + plan.n_steps,
            generated=state.generated + plan.n_steps,
            emitted=state.emitted + toks,
            pending=toks[-1],
        )


def test_cursor_model_catches_missing_rollback():
    m = _RollbackFreeCursorModel()
    m.max_depth = 6
    res = explore(m)
    assert res.violations, "missing rollback survived the cursor invariants"


class _NoRingRollbackCursorModel(CursorModel):
    """Deletes the history-ring rollback for ON-DEVICE drafting: a
    device-draft commit lands the device's full optimistic emission even
    when the host stop scan truncates it — the ring keeps the un-rolled
    tail and the host believes the device's cursor."""

    name = "cursor-no-ring-rollback"

    def actions(self, state):
        acts = [(n, fn) for n, fn in super().actions(state)]
        if state.inflight is not None and state.inflight.kind == "device-draft":
            acts.append(("commit_device_keep_ring", self._commit_keep_ring))
        acts.sort(key=lambda kv: kv[0])
        return acts

    @staticmethod
    def _commit_keep_ring(state):
        from dataclasses import replace
        plan = state.inflight
        if state.finished is not None:
            return replace(state, inflight=None)
        toks = plan.outputs  # NO truncation: the ring's tail all lands
        n = len(toks)
        return replace(
            state, inflight=None,
            processed=state.processed + n,
            generated=state.generated + n,
            emitted=state.emitted + toks,
            pending=toks[-1],
        )


def test_cursor_model_catches_missing_ring_rollback():
    m = _NoRingRollbackCursorModel()
    m.max_depth = 6
    res = explore(m)
    assert res.violations, "missing ring rollback survived the cursor invariants"
    assert any("diverged" in str(v) or "drift" in str(v) for v in res.violations)


class _NoWavefrontBarrierPPModel(PPWavefrontModel):
    """Drops the pp wavefront barrier (ISSUE 20): the stage ring starts
    a microbatch group's iteration t+1 BEFORE iteration t's drain is
    visible, so stage 0 embeds a stale sampled token (and reads a stale
    alive flag) — the exact interleaving the M >= pp wavefront schedule
    makes impossible."""

    name = "pp-wavefront-no-barrier"
    barrier = False


def test_pp_wavefront_model_catches_dropped_barrier():
    m = _NoWavefrontBarrierPPModel()
    m.max_depth = 8
    res = explore(m)
    assert res.violations, "stale-feedback entry survived the pp invariants"
    assert any("diverged" in str(v) for v in res.violations)


class _WedgingBreaker:
    """A breaker whose half-open probe never re-arms: a cancelled probe
    parks the address forever (the exact bug the stale-probe re-arm in
    dataplane.py exists for)."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"

    def __init__(self, threshold, reset_s, clock):
        self.threshold = threshold
        self.reset_s = reset_s
        self._clock = clock
        self.state = self.CLOSED
        self.consecutive_failures = 0
        self.opens_total = 0
        self._opened_at = 0.0
        self._probe_at = 0.0

    def allow(self):
        if self.state == self.CLOSED:
            return True
        now = self._clock()
        if self.state == self.OPEN:
            if now - self._opened_at >= self.reset_s:
                self.state = self.HALF_OPEN
                self._probe_at = now
                return True
            return False
        return False  # half-open NEVER re-arms: the wedge

    def record_success(self):
        self.state = self.CLOSED
        self.consecutive_failures = 0

    def record_failure(self):
        self.consecutive_failures += 1
        if (
            self.state == self.HALF_OPEN
            or self.consecutive_failures >= self.threshold
        ):
            if self.state != self.OPEN:
                self.opens_total += 1
            self.state = self.OPEN
            self._opened_at = self._clock()


def test_breaker_model_catches_cancelled_probe_wedge():
    m = BreakerModel()
    m.breaker_cls = _WedgingBreaker
    m.max_depth = 10
    res = explore(m)
    assert res.violations, "the wedge survived the breaker invariants"
    assert any("wedged" in str(v) for v in res.violations)


class _RearmForeverQuarantineModel(QuarantineModel):
    """A due sweep that re-arms even when the probe says dead: the
    quarantine-forever bug the expiry path exists to prevent."""

    name = "quarantine-rearm-forever"
    sweep_rearms_dead = True


def test_quarantine_model_catches_rearm_forever():
    m = _RearmForeverQuarantineModel()
    m.max_depth = 10
    res = explore(m)
    assert res.violations, "re-arm-forever survived the quarantine invariants"
    assert any("quarantined forever" in str(v) for v in res.violations)


class _NoCancelKeepaliveModel(KeepaliveModel):
    """A reconnect that starts a fresh keepalive task without cancelling
    the old one: the double-beat bug."""

    name = "keepalive-no-cancel"
    cancel_before_restart = False


def test_keepalive_model_catches_double_task():
    res = explore(_NoCancelKeepaliveModel())
    assert res.violations, "double keepalive survived the invariants"
    assert any("tasks=2" in str(v) or "keepalive tasks" in str(v)
               for v in res.violations)


class _FreshIdKeepaliveModel(KeepaliveModel):
    """A resurrection that re-grants WITHOUT ``want=old id``: the server
    hands out a fresh id, orphaning the client's meta and leased-kv
    records."""

    name = "keepalive-fresh-id"
    regrant_with_want = False


def test_keepalive_model_catches_fresh_id_regrant():
    res = explore(_FreshIdKeepaliveModel())
    assert res.violations, "fresh-id re-grant survived the invariants"
    assert any("same_id=False" in str(v) or "different id" in str(v)
               for v in res.violations)


class _NoGuardController(PlannerController):
    """PlannerController._decide with every guard rail deleted: no
    cooldowns, no hysteresis streak."""

    def _decide(self, pool, desired, now, reason):
        if desired > pool.target:
            pool.target = min(desired, pool.target + self.config.max_step_up)
            pool.last_scale_up_t = now
            return self._note(pool, "scale_up", reason)
        if desired < pool.target:
            pool.target = max(desired, pool.target - self.config.max_step_down)
            pool.last_scale_down_t = now
            return self._note(pool, "scale_down", reason)
        return self._note(pool, "hold", reason)


class _NoGuardPlannerModel(PlannerModel):
    name = "planner-no-guards"
    controller_cls = _NoGuardController


def test_planner_model_catches_missing_guard_rails():
    m = _NoGuardPlannerModel()
    m.max_depth = 6
    res = explore(m)
    assert res.violations, "guard-rail removal survived the planner invariants"
    msgs = " | ".join(str(v) for v in res.violations)
    assert "cooldown" in msgs or "below-target cycle" in msgs


# ---------------------------------------------------------------------------
# Determinism + runtime budget + cache + CLI.
# ---------------------------------------------------------------------------


def test_report_is_byte_deterministic():
    a = run([REPO / "dynamo_tpu"], REPO, engine="all", use_cache=False)
    b = run([REPO / "dynamo_tpu"], REPO, engine="all", use_cache=False)
    assert a.render(show_pragmas=True) == b.render(show_pragmas=True)


def test_full_tree_run_fits_ci_budget():
    t0 = time.monotonic()
    run([REPO / "dynamo_tpu"], REPO, engine="all", use_cache=False)
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, f"full-tree dynacheck took {elapsed:.1f}s (budget 60s)"


def test_cache_round_trips(tmp_path):
    rep = tree_report()
    CA.store(tmp_path, "k1", rep.findings, rep.pragmas,
             rep.functions, rep.resolved_edges)
    got = CA.load(tmp_path, "k1")
    assert got is not None
    findings, pragmas, functions, edges = got
    assert findings == rep.findings
    assert pragmas == rep.pragmas
    assert (functions, edges) == (rep.functions, rep.resolved_edges)
    assert CA.load(tmp_path, "other-key") is None


def test_cache_key_tracks_sources(tmp_path):
    f1 = tmp_path / "a.py"
    f1.write_text("x = 1\n")
    k1 = CA.tree_key([f1], tmp_path)
    f1.write_text("x = 2\n")
    k2 = CA.tree_key([f1], tmp_path)
    assert k1 != k2


def test_cli_exits_clean_on_tree():
    assert main([str(REPO / "dynamo_tpu"), "--no-cache"]) == 0


def test_cli_rejects_unknown_rule():
    assert main(["--rules", "not-a-rule", str(REPO / "dynamo_tpu")]) == 2


def test_cli_rejects_missing_path():
    assert main([str(REPO / "no_such_dir_xyz")]) == 2


def test_cache_key_tracks_readme(tmp_path):
    # The config-knob rule reads the README, so a doc edit must miss.
    f1 = tmp_path / "a.py"
    f1.write_text("x = 1\n")
    (tmp_path / "README.md").write_text("docs v1\n")
    k1 = CA.tree_key([f1], tmp_path)
    (tmp_path / "README.md").write_text("docs v2\n")
    k2 = CA.tree_key([f1], tmp_path)
    assert k1 != k2


def test_knobs_md_matches_readme_block():
    # The README's generated block IS the emitter's output (the CI
    # knob-drift gate, exercised in-process).
    from tools.dynacheck.__main__ import KNOBS_BEGIN, KNOBS_END, knobs_markdown

    want = knobs_markdown()
    text = (REPO / "README.md").read_text(encoding="utf-8")
    begin, end = text.find(KNOBS_BEGIN), text.find(KNOBS_END)
    assert begin >= 0 and end > begin, "README lacks the knobs markers"
    assert text[begin:end + len(KNOBS_END)] + "\n" == want


def test_knob_table_covers_every_registered_knob():
    from dynamo_tpu import knobs
    from tools.dynacheck.__main__ import knobs_markdown

    table = knobs_markdown()
    for name in knobs.KNOBS:
        assert f"`{name}`" in table, f"{name} missing from the knob table"


def test_cli_knob_drift_exits_clean():
    assert main(["--knob-drift"]) == 0


def test_malformed_pragma_is_a_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "# dynacheck: allow-unknown-rule(nope)\n"
        "# dynacheck: allow-cursor-discipline()\n"
        "x = 1\n"
    )
    project = build_project([bad], tmp_path)
    findings = run_all(project)
    assert [f.rule for f in findings] == ["malformed-pragma"] * 2
    assert project.pragmas == []
