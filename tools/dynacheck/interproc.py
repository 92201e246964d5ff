"""Engine A rules: interprocedural dataflow over the project call graph.

Five rules dynalint's single-function pass structurally cannot express,
plus the GUARDED_BY registry drift check:

1. ``transitive-blocking`` — a step-loop hot path (HOT_STEP_FUNCS)
   reaches a device->host sync or event-loop blocker through one or more
   call edges. dynalint flags direct sites; this flags the chain.
2. ``lock-order`` — lock-acquisition-order extraction (lexical nesting +
   call edges + holds-lock pragmas) with deadlock-cycle detection.
3. ``holds-lock-unverified`` — a function annotated
   ``# dynalint: holds-lock(X)`` is called from a context that neither
   holds X lexically nor is itself annotated: the annotation is a claim,
   and this rule makes it a checked one.
4. ``coroutine-leak`` — a call to a project-local ``async def`` whose
   coroutine object is neither awaited, handed to a task spawner,
   returned, nor bound to a name that is used again.
5. ``cursor-discipline`` — a write to ``num_computed_tokens`` /
   pinned-hash / refcount protocol state outside the audited
   commit/rollback/release entry points.
6. ``registry-drift`` — a GUARDED_BY entry whose class/attr no longer
   exists, or whose attribute is mutated nowhere under its declared lock.

Findings suppress with ``# dynacheck: allow-<rule>(<reason>)`` anchored
to the enclosing statement's full line span.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from tools.dynacheck import config as C
from tools.dynacheck.callgraph import FuncInfo, LockId, Project


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def run_all(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    for path, line, msg in project.pragma_errors:
        findings.append(Finding(path, line, "malformed-pragma", msg))
    findings.extend(check_transitive_blocking(project))
    findings.extend(check_lock_order(project))
    findings.extend(check_holds_lock(project))
    findings.extend(check_coroutine_leaks(project))
    findings.extend(check_cursor_discipline(project))
    findings.extend(check_registry_drift(project))
    # v2 contract rules live in their own module; imported lazily because
    # contracts.py borrows Finding from here.
    from tools.dynacheck import contracts

    findings.extend(contracts.check_wire_contract(project))
    findings.extend(contracts.check_loop_affinity(project))
    findings.extend(contracts.check_config_knobs(project))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings


# ---------------------------------------------------------------------------
# Rule 1: transitive blocking reachability
# ---------------------------------------------------------------------------


def _hot_roots(project: Project) -> list[FuncInfo]:
    roots: list[FuncInfo] = []
    for suffix, names in C.HOT_STEP_FUNCS.items():
        for info in project.functions.values():
            if info.path.endswith(suffix) and info.name in names:
                roots.append(info)
    roots.sort(key=lambda f: f.key)
    return roots


def check_transitive_blocking(project: Project) -> list[Finding]:
    # One finding per sink site, carrying the shortest chain from the
    # first (sorted) hot root that reaches it — every extra root/chain
    # for the same sink is the same fix.
    best: dict[tuple[str, int], tuple[str, tuple[str, ...]]] = {}
    for root in _hot_roots(project):
        # BFS over call edges; shortest chain per reached function.
        frontier: list[tuple[str, tuple[str, ...]]] = [(root.key, (root.qualname,))]
        visited = {root.key}
        while frontier:
            nxt: list[tuple[str, tuple[str, ...]]] = []
            for key, chain in frontier:
                info = project.functions.get(key)
                if info is None:
                    continue
                if len(chain) > 1:  # depth >= 1: transitive territory
                    for line, what in info.sync_sites:
                        if (info.path, line) in project.sync_ok_lines:
                            continue  # reviewed intentional sync (dynalint)
                        if project.suppressed(
                            C.RULE_TRANSITIVE_BLOCKING, info.path, line
                        ):
                            continue
                        sink = (info.path, line)
                        if sink not in best or len(chain) < len(best[sink][1]):
                            best[sink] = (what, chain)
                for cs in info.calls:
                    for t in sorted(cs.targets):
                        if t in visited:
                            continue
                        tinfo = project.functions.get(t)
                        if tinfo is None:
                            continue
                        # The registered sync primitives are sinks, not
                        # waypoints: CALLING fetch_replicated is the
                        # blocking event (recorded at the call site);
                        # its implementation is not separate news.
                        if tinfo.name in C.HOST_SYNC_FNS:
                            continue
                        visited.add(t)
                        nxt.append((t, chain + (tinfo.qualname,)))
            frontier = nxt
    out: list[Finding] = []
    for (path, line), (what, chain) in sorted(best.items()):
        out.append(Finding(
            path, line, C.RULE_TRANSITIVE_BLOCKING,
            f"{what} is reachable from step-loop hot path "
            f"{chain[0]!r} via {' -> '.join(chain)}: "
            "a blocking sync here serializes planning with "
            "device compute; move the landing to the commit "
            "side or pragma the sink with "
            "`# dynacheck: allow-transitive-blocking(...)`",
        ))
    return out


# ---------------------------------------------------------------------------
# Rule 2: lock-order extraction + deadlock cycles
# ---------------------------------------------------------------------------


def _lock_str(lid: LockId) -> str:
    return f"{lid[0]}.{lid[1]}"


def _locks_inside(project: Project) -> dict[str, set[LockId]]:
    """Fixpoint: locks acquired in each function or any transitive callee."""
    inside: dict[str, set[LockId]] = {
        k: {a.lock for a in f.lock_acquires}
        for k, f in project.functions.items()
    }
    changed = True
    rounds = 0
    while changed and rounds < 50:
        changed = False
        rounds += 1
        for k, f in project.functions.items():
            cur = inside[k]
            before = len(cur)
            for cs in f.calls:
                for t in cs.targets:
                    cur |= inside.get(t, set())
            if len(cur) != before:
                changed = True
    return inside


def _resolve_pragma_lock(project: Project, name: str) -> LockId | None:
    owners = sorted({lid for lid in project.locks if lid[1] == name})
    if len({o[0] for o in owners}) == 1:
        return owners[0]
    return None


def check_lock_order(project: Project) -> list[Finding]:
    inside = _locks_inside(project)
    # edge (src, dst) -> list of witnesses (path, line, description)
    edges: dict[tuple[LockId, LockId], list[tuple[str, int, str]]] = {}

    def add_edge(src: LockId, dst: LockId, path: str, line: int, how: str) -> None:
        if project.suppressed(C.RULE_LOCK_ORDER, path, line):
            return
        edges.setdefault((src, dst), []).append((path, line, how))

    for f in project.functions.values():
        pragma_locks = [
            lid for lid in (
                _resolve_pragma_lock(project, nm) for nm in sorted(f.holds_pragmas)
            ) if lid is not None
        ]
        # Lexical nesting (+ pragma-held context). Two locks of the SAME
        # identity in one with-statement (two instances of one class)
        # produce a self-edge here — a deadlock unless callers impose a
        # global acquisition order.
        for acq in f.lock_acquires:
            for h in acq.held_before:
                add_edge(h, acq.lock, f.path, acq.line, "nested with")
            if not acq.held_before:
                for p in pragma_locks:
                    add_edge(p, acq.lock, f.path, acq.line, "held via holds-lock pragma")
        # Call edges: held here -> acquired inside the callee.
        for cs in f.calls:
            held = list(cs.held_locks)
            if not held and pragma_locks:
                held = pragma_locks
            if not held:
                continue
            for t in cs.targets:
                for m in inside.get(t, ()):
                    for h in held:
                        add_edge(
                            h, m, f.path, cs.line,
                            f"call into {project.functions[t].qualname} "
                            f"which acquires {_lock_str(m)}",
                        )

    # Cycle detection over the lock-order digraph (self-loops included).
    graph: dict[LockId, set[LockId]] = {}
    for (src, dst) in edges:
        graph.setdefault(src, set()).add(dst)
        graph.setdefault(dst, set())
    cycles = _find_cycles(graph)

    out: list[Finding] = []
    for cyc in cycles:
        members = set(cyc)
        # Witness with the ACTUAL edges inside the cycle's node set — the
        # sorted SCC listing is a set, not an edge sequence, so consecutive
        # sorted pairs need not be edges at all.
        cyc_edges = sorted(
            (src, dst) for (src, dst) in edges
            if src in members and dst in members
        )
        witnesses = [w for p in cyc_edges for w in edges[p]]
        if not witnesses:
            continue  # every edge in this SCC was pragma-suppressed
        wit_path, wit_line, _ = min(witnesses)
        detail = "; ".join(
            f"{_lock_str(a)}->{_lock_str(b)} at "
            + ", ".join(f"{p}:{ln} ({how})" for p, ln, how in sorted(edges[(a, b)])[:3])
            for a, b in cyc_edges
        )
        if len(cyc) == 1:
            msg = (
                f"lock {_lock_str(cyc[0])} is acquired while an instance of "
                f"itself is already held ({detail}): two instances of this "
                "lock taken concurrently in opposite orders deadlock; impose "
                "a global acquisition order and pragma the site with "
                "`# dynacheck: allow-lock-order(...)`"
            )
        else:
            names = " , ".join(_lock_str(l) for l in cyc)
            msg = (
                f"inconsistent lock acquisition order: locks {{{names}}} "
                f"form a cycle ({detail}); threads taking these locks in "
                "different orders can deadlock"
            )
        out.append(Finding(wit_path, wit_line, C.RULE_LOCK_ORDER, msg))
    return out


def _find_cycles(graph: dict[LockId, set[LockId]]) -> list[tuple[LockId, ...]]:
    """Elementary cycles, deterministically: self-loops plus one cycle per
    strongly connected component of size > 1 (reported as the sorted SCC —
    a full Johnson enumeration would drown the report in rotations)."""
    cycles: list[tuple[LockId, ...]] = []
    for n in sorted(graph):
        if n in graph.get(n, ()):
            cycles.append((n,))
    for scc in _sccs(graph):
        if len(scc) > 1:
            cycles.append(tuple(sorted(scc)))
    return sorted(cycles)


def _sccs(graph: dict[LockId, set[LockId]]) -> list[list[LockId]]:
    """Tarjan, iterative, deterministic node order."""
    index: dict[LockId, int] = {}
    low: dict[LockId, int] = {}
    on_stack: set[LockId] = set()
    stack: list[LockId] = []
    sccs: list[list[LockId]] = []
    counter = [0]

    for root in sorted(graph):
        if root in index:
            continue
        work: list[tuple[LockId, list[LockId], int]] = [
            (root, sorted(graph.get(root, ())), 0)
        ]
        while work:
            node, succs, i = work.pop()
            if i == 0:
                index[node] = low[node] = counter[0]
                counter[0] += 1
                stack.append(node)
                on_stack.add(node)
            recurse = False
            while i < len(succs):
                s = succs[i]
                i += 1
                if s not in index:
                    work.append((node, succs, i))
                    work.append((s, sorted(graph.get(s, ())), 0))
                    recurse = True
                    break
                if s in on_stack:
                    low[node] = min(low[node], index[s])
            if recurse:
                continue
            if low[node] == index[node]:
                comp: list[LockId] = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                sccs.append(comp)
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
    return sccs


# ---------------------------------------------------------------------------
# Rule 3: holds-lock pragma verification
# ---------------------------------------------------------------------------


def check_holds_lock(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for key in sorted(project.functions):
        f = project.functions[key]
        if not f.holds_pragmas:
            continue
        for lock_name in sorted(f.holds_pragmas):
            for caller_key, cs in sorted(
                project.callers.get(key, []), key=lambda kc: (kc[0], kc[1].line)
            ):
                caller = project.functions.get(caller_key)
                if caller is None:
                    continue
                if any(h[1] == lock_name for h in cs.held_locks):
                    continue  # lexically held at the call
                if lock_name in caller.holds_pragmas:
                    continue  # caller carries (and is checked for) the claim
                if caller.name == "__init__":
                    continue  # construction precedes sharing
                if project.suppressed(
                    C.RULE_HOLDS_LOCK_UNVERIFIED, caller.path, cs.line
                ):
                    continue
                out.append(Finding(
                    caller.path, cs.line, C.RULE_HOLDS_LOCK_UNVERIFIED,
                    f"{caller.qualname} calls {f.qualname} (annotated "
                    f"holds-lock({lock_name})) without holding {lock_name}: "
                    "acquire the lock, annotate the caller with "
                    f"`# dynalint: holds-lock({lock_name})`, or pragma with "
                    "`# dynacheck: allow-holds-lock-unverified(...)`",
                ))
    return out


# ---------------------------------------------------------------------------
# Rule 4: coroutine-leak dataflow
# ---------------------------------------------------------------------------

_OK_USAGE = {"await", "sink", "return", "yield"}


def check_coroutine_leaks(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for key in sorted(project.functions):
        f = project.functions[key]
        for cs in f.calls:
            async_targets = [
                t for t in cs.targets
                if project.functions[t].is_async
                and not project.functions[t].is_generator
            ]
            if not async_targets or cs.usage in _OK_USAGE:
                continue
            if cs.usage == "other":
                continue  # handed onward / stored: ownership moved
            if project.suppressed(C.RULE_CORO_LEAK, f.path, cs.line):
                continue
            tname = project.functions[async_targets[0]].qualname
            if cs.usage == "dropped":
                out.append(Finding(
                    f.path, cs.line, C.RULE_CORO_LEAK,
                    f"coroutine {tname}() is created and immediately "
                    "dropped: the body never runs (Python logs 'never "
                    "awaited' at gc time at best); await it, or hand it "
                    "to a task spawner",
                ))
            elif cs.usage.startswith("bound:"):
                name = cs.usage.split(":", 1)[1]
                if _name_reused_after(f, name, cs.line):
                    continue
                out.append(Finding(
                    f.path, cs.line, C.RULE_CORO_LEAK,
                    f"coroutine {tname}() is bound to {name!r} but the "
                    "name is never used again in this scope: the "
                    "coroutine escapes unawaited and unspawned",
                ))
    return out


def _name_reused_after(f: FuncInfo, name: str, line: int) -> bool:
    if f.node is None:
        return True  # no body available: stay quiet
    for sub in ast.walk(f.node):
        if (
            isinstance(sub, ast.Name)
            and sub.id == name
            and isinstance(sub.ctx, ast.Load)
            and sub.lineno >= line
        ):
            return True
    return False


# ---------------------------------------------------------------------------
# Rule 5: cursor discipline
# ---------------------------------------------------------------------------


def check_cursor_discipline(project: Project) -> list[Finding]:
    out: list[Finding] = []
    for key in sorted(project.functions):
        f = project.functions[key]
        audited: set[str] = set()
        for suffix, quals in C.AUDITED_CURSOR_WRITERS.items():
            if f.path.endswith(suffix):
                audited = quals
                break
        if f.qualname in audited:
            continue
        for w in f.writes:
            if w.attr not in C.CURSOR_ATTRS:
                continue
            if w.receiver in ("<local>", "<global>"):
                continue  # bare-name stores are not protocol-state writes
            if project.suppressed(C.RULE_CURSOR, f.path, w.line):
                continue
            out.append(Finding(
                f.path, w.line, C.RULE_CURSOR,
                f"write to {w.receiver}.{w.attr} ({C.CURSOR_ATTRS[w.attr]}) "
                f"in {f.qualname}, which is not an audited "
                "commit/rollback/release entry point: route the mutation "
                "through the audited writers (tools/dynacheck/config.py "
                "AUDITED_CURSOR_WRITERS) or pragma with "
                "`# dynacheck: allow-cursor-discipline(...)`",
            ))
    return out


# ---------------------------------------------------------------------------
# Rule 6: GUARDED_BY registry drift
# ---------------------------------------------------------------------------


def check_registry_drift(project: Project) -> list[Finding]:
    out: list[Finding] = []
    paths = sorted({f.path for f in project.functions.values()})
    # A registered-but-absent file is drift only on a tree scan — a
    # narrow scan (one fixture file, one module) proves nothing about
    # the registry's other entries.
    tree_scan = any(p.startswith("dynamo_tpu/") for p in paths)
    for suffix in sorted(C.GUARDED_BY):
        matches = [p for p in paths if p.endswith(suffix)]
        if not matches:
            if not suffix.startswith("dynamo_tpu/") or not tree_scan:
                continue
            out.append(Finding(
                suffix, 0, C.RULE_REGISTRY_DRIFT,
                f"GUARDED_BY registers {suffix} but no scanned file "
                "matches it: the module moved or was deleted — update "
                "tools/dynalint/config.py",
            ))
            continue
        path = matches[0]
        file_funcs = [f for f in project.functions.values() if f.path == path]
        for (scope, attr), lock in sorted(
            C.GUARDED_BY[suffix].items(), key=lambda kv: (str(kv[0][0]), kv[0][1])
        ):
            if scope is not None and path not in project.classes.get(scope, set()):
                out.append(Finding(
                    path, 0, C.RULE_REGISTRY_DRIFT,
                    f"GUARDED_BY entry ({scope}, {attr}): class {scope} "
                    f"no longer exists in {path}",
                ))
                continue
            writes = _registry_writes(file_funcs, scope, attr)
            if not writes:
                out.append(Finding(
                    path, 0, C.RULE_REGISTRY_DRIFT,
                    f"GUARDED_BY entry ({scope}, {attr}) guarded by {lock}: "
                    "attribute is mutated nowhere in the file — stale "
                    "entry, tighten the registry",
                ))
                continue
            if lock == C.EXTERNAL:
                continue
            owner = scope and project.lock_owner(scope, lock)
            lock_exists = any(
                lid[1] == lock and (scope is None or lid[0] == owner)
                for lid in project.locks
            )
            if not lock_exists:
                out.append(Finding(
                    path, 0, C.RULE_REGISTRY_DRIFT,
                    f"GUARDED_BY entry ({scope}, {attr}): declared lock "
                    f"{lock} is not constructed anywhere in scope "
                    f"{scope or path}",
                ))
                continue
            guarded_writes = [
                (f, w) for f, w in writes
                if any(h[1] == lock for h in w.held)
                or lock in f.holds_pragmas
            ]
            nontrivial = [
                (f, w) for f, w in writes
                if f.name != "__init__" and f.qualname != "<module>"
            ]
            if nontrivial and not guarded_writes:
                first = min(w.line for _, w in nontrivial)
                out.append(Finding(
                    path, first, C.RULE_REGISTRY_DRIFT,
                    f"GUARDED_BY entry ({scope}, {attr}) declares lock "
                    f"{lock}, but no mutation site holds it (lexically or "
                    "via holds-lock pragma): the attribute migrated to a "
                    "different lock or the discipline is broken — fix the "
                    "registry or the code",
                ))
    return out


def _registry_writes(file_funcs, scope, attr):
    out = []
    for f in file_funcs:
        in_scope = (
            scope is None
            or f.qualname.startswith(f"{scope}.")
        )
        if not in_scope:
            continue
        for w in f.writes:
            if w.attr != attr:
                continue
            if scope is None:
                if w.receiver != "<global>":
                    continue
            else:
                if w.receiver not in ("self", "self(alias)"):
                    continue
            out.append((f, w))
    return out
