"""Project-wide call graph + await graph construction (Engine A's base).

Stdlib ``ast`` only. One parse per file produces, for every function
(including nested defs and methods, dotted qualnames like
``EngineCore._plan_megastep.commit``):

- resolved call sites (callee -> project function), with the set of lock
  identities lexically held at each call,
- lock acquisitions (``with``/``async with`` over known locks), with the
  locks already held when each is taken,
- attribute writes (assign / augassign / del / mutator-method calls),
- per-call usage context for coroutine-leak dataflow (awaited, spawned,
  returned, bound-and-reused, dropped).

Call resolution is deliberately project-native and heuristic — this is a
lint layer, not a type checker. A call resolves when the callee is:
``self.m`` -> method ``m`` of the enclosing class; a typed attribute
(``self.x = ClassName(...)`` in ``__init__`` or an annotated ctor param)
-> that class's method; a local or imported module function; or a method
name defined exactly ONCE across the project (unique-name fallback).
Ambiguous calls stay unresolved and no rule fires through them: the tool
under-approximates rather than spamming.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path

from tools.dynacheck import config as C

# Lock identity: (scope, attr) — scope is the owning class name, or the
# repo-relative module path for module-level locks.
LockId = tuple[str, str]


@dataclass(frozen=True)
class LockAcquire:
    lock: LockId
    line: int
    held_before: tuple[LockId, ...]


@dataclass(frozen=True)
class AttrWrite:
    attr: str
    line: int
    col: int
    kind: str  # "assign" | "augassign" | "del" | "mutate:<method>"
    # Dotted receiver text ("seq", "self", "blk", ...); "<local>" /
    # "<global>" for bare-name stores (registry-drift needs module
    # globals), "self(alias)" for writes through a `st = self.X` alias.
    receiver: str
    held: tuple[LockId, ...] = ()  # locks lexically held at the write


@dataclass
class CallSite:
    line: int
    col: int
    raw: str                     # callee as written ("self.allocator.commit")
    targets: list[str] = field(default_factory=list)  # resolved func keys
    awaited: bool = False
    usage: str = "other"         # await|sink|return|yield|bound:<n>|dropped|other
    held_locks: tuple[LockId, ...] = ()


def _is_generator(node) -> bool:
    stack = list(node.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.Yield, ast.YieldFrom)):
            return True
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return False


@dataclass
class FuncInfo:
    path: str                    # repo-relative posix path
    qualname: str                # dotted nesting: Class.method.nested
    lineno: int
    is_async: bool = False
    is_generator: bool = False
    holds_pragmas: frozenset[str] = frozenset()
    calls: list[CallSite] = field(default_factory=list)
    # Callables handed to thread contexts (to_thread / run_in_executor /
    # submit / Thread(target=...)): resolved like calls; loop-affinity
    # BFS roots.
    spawn_sites: list[CallSite] = field(default_factory=list)
    lock_acquires: list[LockAcquire] = field(default_factory=list)
    writes: list[AttrWrite] = field(default_factory=list)
    # Direct blocking sites inside THIS function's own body (line, what).
    sync_sites: list[tuple[int, str]] = field(default_factory=list)
    # AST def node (coroutine-leak's bound-name reuse scan needs the body).
    node: object = field(default=None, repr=False, compare=False)

    @property
    def key(self) -> str:
        return f"{self.path}::{self.qualname}"

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]


@dataclass
class Project:
    root: Path
    functions: dict[str, FuncInfo] = field(default_factory=dict)
    # class name -> {path of files defining it}
    classes: dict[str, set[str]] = field(default_factory=dict)
    # class name -> its bases' names (a mixin's ``self`` is its inheritor's)
    bases: dict[str, set[str]] = field(default_factory=dict)
    # known locks: (scope, attr) -> defining (path, line)
    locks: dict[LockId, tuple[str, int]] = field(default_factory=dict)
    # callers index (filled by resolve): func key -> [(caller key, CallSite)]
    callers: dict[str, list[tuple[str, CallSite]]] = field(default_factory=dict)
    # parsed module per file (wire/knob rules re-walk these; NOT cached
    # — the cache stores findings only)
    trees: dict[str, ast.Module] = field(default_factory=dict)
    # per-file import map: local name -> dotted target (module or obj)
    imports_by_file: dict[str, dict[str, str]] = field(default_factory=dict)
    # pragma inventory: (path, rule) -> [(line, reason)]
    pragmas: list = field(default_factory=list)
    # pragma errors (malformed) as (path, line, message)
    pragma_errors: list = field(default_factory=list)
    # suppressed (path, statement-span) per rule, for finding filtering:
    # rule -> set of (path, line) covering every line of pragma'd statements
    allow_lines: dict[str, set[tuple[str, int]]] = field(default_factory=dict)
    # dynalint sync-ok pragma lines (path, line): a transitive finding whose
    # blocking site is an intentional, already-reviewed sync is not news.
    sync_ok_lines: set[tuple[str, int]] = field(default_factory=set)

    def lock_owner(self, cls: str, attr: str) -> str:
        """The class whose ``self.<attr>`` lock an instance of ``cls``
        holds: ``cls`` itself, or the one class up or down its line of
        inheritance that constructs it (EngineCore builds the step lock
        its base KvTransfer takes)."""
        if (cls, attr) in self.locks:
            return cls
        line, todo = {cls}, [cls]
        while todo:
            c = todo.pop()
            kin = self.bases.get(c, set()) | {d for d, b in self.bases.items() if c in b}
            todo.extend(kin - line)
            line |= kin
        owners = [c for c in line if (c, attr) in self.locks]
        return owners[0] if len(owners) == 1 else cls

    def suppressed(self, rule: str, path: str, line: int) -> bool:
        return (path, line) in self.allow_lines.get(rule, ())


# ---------------------------------------------------------------------------
# Helpers (shared shapes with dynalint, kept dependency-free of its linter)
# ---------------------------------------------------------------------------


def dotted_name(node: ast.AST) -> str | None:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_sync_site(node: ast.Call) -> str | None:
    """dynalint rule-7 vocabulary: device->host sync calls."""
    func = node.func
    if isinstance(func, ast.Attribute):
        if func.attr in C.HOST_SYNC_METHODS:
            return f".{func.attr}()"
        if func.attr == "asarray" and dotted_name(func.value) in C.HOST_SYNC_ASARRAY_ROOTS:
            return "np.asarray()"
        if func.attr in C.HOST_SYNC_FNS:
            return f"{func.attr}()"
    elif isinstance(func, ast.Name) and func.id in C.HOST_SYNC_FNS:
        return f"{func.id}()"
    d = dotted_name(func)
    if d in C.BLOCKING_CALLS:
        return f"{d}()"
    if d and d.split(".")[0] in C.BLOCKING_ROOTS:
        return f"{d}()"
    return None


_MUTATOR_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault",
    "pop", "popleft", "popitem", "remove", "discard", "clear",
    "appendleft", "rotate", "sort", "reverse",
}

# Parent nodes "transparent" for coroutine usage classification: a call
# inside one of these is classified by the node above it (e.g. the list
# handed to gather(*coros)).
_TRANSPARENT = (ast.List, ast.Tuple, ast.Set, ast.Starred, ast.IfExp, ast.NamedExpr)


class _FileScanner(ast.NodeVisitor):
    """One pass over a module: collects FuncInfos, lock defs, class defs."""

    def __init__(self, path: str, tree: ast.Module, project: Project):
        self.path = path
        self.tree = tree
        self.project = project
        self.module_func = FuncInfo(path=path, qualname="<module>", lineno=0)
        self._class_stack: list[str] = []
        self._func_stack: list[FuncInfo] = []
        self._held: list[LockId] = []
        # Local lock aliases within the current function: name -> LockId.
        self._lock_aliases: list[dict[str, LockId]] = []
        # Local attribute aliases (`st = self.transfer_stats`): name -> attr.
        self._attr_aliases: list[dict[str, str]] = []
        # Per-function `global` declarations.
        self._globals: list[set[str]] = []
        # self.<attr> -> class-name type hints, per enclosing class.
        self.attr_types: dict[tuple[str, str], str] = {}
        # parameter annotations: (qualname, param) -> class name
        self.param_types: dict[tuple[str, str], str] = {}
        # Imports: local name -> dotted target module/obj.
        self.imports: dict[str, str] = {}
        self._parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node

    # -- scope bookkeeping -------------------------------------------------

    def _cur(self) -> FuncInfo:
        return self._func_stack[-1] if self._func_stack else self.module_func

    def _qual(self, name: str) -> str:
        if self._func_stack:
            return f"{self._func_stack[-1].qualname}.{name}"
        if self._class_stack:
            return f"{'.'.join(self._class_stack)}.{name}"
        return name

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.imports[a.asname or a.name.split(".")[0]] = a.name
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module:
            for a in node.names:
                self.imports[a.asname or a.name] = f"{node.module}.{a.name}"
        self.generic_visit(node)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self.project.classes.setdefault(node.name, set()).add(self.path)
        self.generic_visit(node)
        self._class_stack.pop()

    def _enter_func(self, node, is_async: bool) -> None:
        qual = self._qual(node.name)
        info = FuncInfo(
            path=self.path, qualname=qual, lineno=node.lineno, is_async=is_async,
            is_generator=_is_generator(node), node=node,
        )
        self.project.functions[info.key] = info
        self._func_stack.append(info)
        self._lock_aliases.append({})
        self._attr_aliases.append({})
        globals_declared: set[str] = set()
        stack = list(node.body)
        while stack:
            sub = stack.pop()
            if isinstance(sub, ast.Global):
                globals_declared.update(sub.names)
            elif not isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(sub))
        self._globals.append(globals_declared)
        # Annotated params as type hints (def f(self, core: EngineCore)).
        for arg in node.args.posonlyargs + node.args.args + node.args.kwonlyargs:
            ann = arg.annotation
            if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                self.param_types[(qual, arg.arg)] = ann.value.strip("\"'")
            else:
                d = dotted_name(ann) if ann is not None else None
                if d:
                    self.param_types[(qual, arg.arg)] = d.rsplit(".", 1)[-1]

    def _exit_func(self) -> None:
        self._func_stack.pop()
        self._lock_aliases.pop()
        self._attr_aliases.pop()
        self._globals.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._enter_func(node, is_async=False)
        self.generic_visit(node)
        self._exit_func()

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._enter_func(node, is_async=True)
        self.generic_visit(node)
        self._exit_func()

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # Lambdas stay attributed to the enclosing function.
        self.generic_visit(node)

    # -- lock tracking -----------------------------------------------------

    def _lock_id_for(self, expr: ast.expr) -> LockId | None:
        """Resolve a with-item context expression to a lock identity."""
        if isinstance(expr, ast.Name) and self._lock_aliases:
            alias = self._lock_aliases[-1].get(expr.id)
            if alias is not None:
                return alias
        # Subscripted lock maps: self._locks[address] -> (Class, _locks[]).
        if isinstance(expr, ast.Subscript):
            base = self._lock_id_for(expr.value)
            if base is not None:
                return (base[0], base[1] + "[]")
            d = dotted_name(expr.value)
            if d and d.rsplit(".", 1)[-1].lower().endswith("locks"):
                return self._attr_lock(d.rsplit(".", 1)[-1] + "[]", expr)
            return None
        d = dotted_name(expr)
        if d is None:
            return None
        last = d.rsplit(".", 1)[-1]
        lock_like = last.lower().endswith(C.LOCK_NAME_SUFFIXES)
        if d.startswith("self."):
            parts = d.split(".")
            if len(parts) == 2:
                if self._class_stack:
                    lid = (self.project.lock_owner(self._class_stack[-1], parts[1]), parts[1])
                    if lid in self.project.locks or lock_like:
                        return lid
                return None
            # self.a.b (a lock reached through an attribute): identify by
            # the attr name against the registered-lock index below.
            if lock_like:
                return self._attr_lock(last, expr)
            return None
        if "." not in d:
            # Module-level lock (bare name): registered or lock-like.
            lid = (self.path, d)
            if lid in self.project.locks or (
                lock_like and not self._is_local(d)
            ):
                return lid
            return None
        # Foreign receiver (`first._step_lock`): identify by unique attr
        # name across registered locks, so two instances of one class map
        # to ONE identity — exactly what lock-order needs.
        if lock_like:
            return self._attr_lock(last, expr)
        return None

    def _attr_lock(self, attr: str, expr: ast.expr) -> LockId | None:
        owners = [lid for lid in self.project.locks if lid[1] == attr]
        if len({o[0] for o in owners}) == 1:
            return owners[0]
        # Unregistered / ambiguous: scope to this file.
        return (self.path, attr)

    def _is_local(self, name: str) -> bool:
        return bool(self._func_stack)  # conservative: bare names in funcs are locals

    def _visit_with(self, node) -> None:
        added: list[LockId] = []
        for item in node.items:
            lid = self._lock_id_for(item.context_expr)
            if lid is not None:
                self._cur().lock_acquires.append(
                    LockAcquire(lid, item.context_expr.lineno, tuple(self._held))
                )
                self._held.append(lid)
                added.append(lid)
        self.generic_visit(node)
        for _ in added:
            self._held.pop()

    def visit_With(self, node: ast.With) -> None:
        self._visit_with(node)

    def visit_AsyncWith(self, node: ast.AsyncWith) -> None:
        self._visit_with(node)

    # -- assignments: lock defs, aliases, attr types, writes ---------------

    def visit_Assign(self, node: ast.Assign) -> None:
        value = node.value
        vd = dotted_name(value.func) if isinstance(value, ast.Call) else None
        for target in node.targets:
            td = dotted_name(target)
            # Lock constructor assignment -> register a lock identity.
            if vd in C.LOCK_CONSTRUCTORS and td is not None:
                if td.startswith("self.") and self._class_stack:
                    lid = (self._class_stack[-1], td.split(".", 1)[1])
                elif "." not in td and not self._func_stack:
                    lid = (self.path, td)
                else:
                    lid = None
                if lid is not None:
                    self.project.locks[lid] = (self.path, node.lineno)
            # Typed attribute: self.x = ClassName(...) in any method.
            if (
                vd is not None and td is not None and td.startswith("self.")
                and "." not in td[5:] and self._class_stack
                and vd.rsplit(".", 1)[-1] in self.project.classes
            ):
                self.attr_types[(self._class_stack[-1], td[5:])] = vd.rsplit(".", 1)[-1]
            # self.x = param  where param is annotated -> propagate type.
            if (
                isinstance(value, ast.Name) and td is not None
                and td.startswith("self.") and "." not in td[5:]
                and self._class_stack and self._func_stack
            ):
                t = self.param_types.get((self._cur().qualname, value.id))
                if t and t in self.project.classes:
                    self.attr_types[(self._class_stack[-1], td[5:])] = t
            # Local lock alias: lock = self._locks.setdefault(...), etc.
            if isinstance(target, ast.Name) and self._lock_aliases:
                lid = self._alias_lock_rhs(value)
                if lid is not None:
                    self._lock_aliases[-1][target.id] = lid
                # Attribute alias: `st = self.transfer_stats` — writes
                # through `st` are writes to the attribute.
                vdot = dotted_name(value)
                if vdot and vdot.startswith("self.") and "." not in vdot[5:]:
                    self._attr_aliases[-1][target.id] = vdot[5:]
            self._record_write(target, node, "assign")
        self.generic_visit(node)

    def _alias_lock_rhs(self, value: ast.expr) -> LockId | None:
        """`lock = <expr reaching a lock map or lock attr>` alias."""
        if isinstance(value, ast.Call):
            d = dotted_name(value.func)
            if d and d.rsplit(".", 2)[-1] == "setdefault" and ".locks" in f".{d.lower()}":
                recv = d.rsplit(".", 1)[0]
                last = recv.rsplit(".", 1)[-1]
                if recv.startswith("self.") and self._class_stack:
                    return (self._class_stack[-1], last + "[]")
                return (self.path, last + "[]")
            if d in C.LOCK_CONSTRUCTORS:
                return None  # fresh local lock: no shared identity
        if isinstance(value, (ast.Attribute, ast.Subscript)):
            return self._lock_id_for(value)
        return None

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._record_write(node.target, node, "augassign")
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for target in node.targets:
            self._record_write(target, node, "del")
        self.generic_visit(node)

    def _record_write(self, target: ast.expr, site: ast.AST, kind: str) -> None:
        subscripted = False
        while isinstance(target, (ast.Subscript, ast.Starred)):
            subscripted = subscripted or isinstance(target, ast.Subscript)
            target = target.value
        if isinstance(target, ast.Tuple):
            for el in target.elts:
                self._record_write(el, site, kind)
            return
        line = site.lineno
        col = getattr(site, "col_offset", 0)
        held = tuple(self._held)
        if isinstance(target, ast.Attribute):
            recv = dotted_name(target.value) or "<expr>"
            self._cur().writes.append(
                AttrWrite(target.attr, line, col, kind, recv, held)
            )
            return
        if isinstance(target, ast.Name):
            alias = self._attr_aliases[-1].get(target.id) if self._attr_aliases else None
            if alias is not None and (subscripted or kind.startswith("mutate")):
                self._cur().writes.append(
                    AttrWrite(alias, line, col, kind, "self(alias)", held)
                )
                return
            if not self._func_stack or (
                self._globals and target.id in self._globals[-1]
            ):
                recv = "<global>"
            else:
                # A plain local rebinding is not interesting — but a
                # SUBSCRIPT store through a local can alias shared state;
                # registry-drift treats "<local>" writes as weak evidence.
                recv = "<local>"
                if not subscripted and not kind.startswith("mutate"):
                    return
            self._cur().writes.append(
                AttrWrite(target.id, line, col, kind, recv, held)
            )

    # -- calls -------------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        raw = dotted_name(node.func) or (
            f"<expr>.{node.func.attr}" if isinstance(node.func, ast.Attribute) else "<expr>"
        )
        cs = CallSite(
            line=node.lineno, col=node.col_offset, raw=raw,
            held_locks=tuple(self._held),
        )
        cs.usage = self._usage_of(node)
        cs.awaited = cs.usage == "await"
        cur = self._cur()
        cur.calls.append(cs)
        sync = _is_sync_site(node)
        if sync is not None:
            cur.sync_sites.append((node.lineno, sync))
        self._record_spawn(node, cur)
        # Mutator-method writes (x.attr.append(...) mutates x.attr).
        if isinstance(node.func, ast.Attribute) and node.func.attr in _MUTATOR_METHODS:
            base = node.func.value
            while isinstance(base, (ast.Subscript, ast.Starred)):
                base = base.value
            held = tuple(self._held)
            if isinstance(base, ast.Attribute):
                recv = dotted_name(base.value) or "<expr>"
                cur.writes.append(
                    AttrWrite(base.attr, node.lineno, node.col_offset,
                              f"mutate:{node.func.attr}", recv, held)
                )
            elif isinstance(base, ast.Name):
                alias = self._attr_aliases[-1].get(base.id) if self._attr_aliases else None
                if alias is not None:
                    cur.writes.append(
                        AttrWrite(alias, node.lineno, node.col_offset,
                                  f"mutate:{node.func.attr}", "self(alias)", held)
                    )
                elif not self._func_stack or (
                    self._globals and base.id in self._globals[-1]
                ):
                    cur.writes.append(
                        AttrWrite(base.id, node.lineno, node.col_offset,
                                  f"mutate:{node.func.attr}", "<global>", held)
                    )
        self.generic_visit(node)

    def _record_spawn(self, node: ast.Call, cur: FuncInfo) -> None:
        """Callable handed to a thread context becomes a spawn site."""
        name = (
            node.func.attr if isinstance(node.func, ast.Attribute)
            else node.func.id if isinstance(node.func, ast.Name) else None
        )
        if name not in C.THREAD_SPAWNERS:
            return
        target: ast.expr | None = None
        if name == "to_thread" and node.args:
            target = node.args[0]
        elif name == "run_in_executor" and len(node.args) >= 2:
            target = node.args[1]
        elif name == "submit" and node.args:
            target = node.args[0]
        elif name == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    target = kw.value
        if target is None:
            return
        raw = dotted_name(target)
        if raw is None and isinstance(target, ast.Attribute):
            raw = f"<expr>.{target.attr}"
        if raw is None:
            return  # lambda / partial: unresolvable, under-approximate
        cur.spawn_sites.append(CallSite(
            line=node.lineno, col=node.col_offset, raw=raw,
        ))

    def _usage_of(self, node: ast.Call) -> str:
        parent = self._parents.get(node)
        while isinstance(parent, _TRANSPARENT):
            parent = self._parents.get(parent)
        if isinstance(parent, ast.Await):
            return "await"
        if isinstance(parent, ast.Call) and parent is not node:
            d = dotted_name(parent.func)
            last = d.rsplit(".", 1)[-1] if d else (
                parent.func.attr if isinstance(parent.func, ast.Attribute) else None
            )
            if last in C.CORO_SINKS:
                return "sink"
            return "other"  # handed to some call: assume ownership moves
        if isinstance(parent, ast.Return):
            return "return"
        if isinstance(parent, (ast.Yield, ast.YieldFrom)):
            return "yield"
        if isinstance(parent, ast.Assign):
            targets = parent.targets
            if len(targets) == 1 and isinstance(targets[0], ast.Name):
                return f"bound:{targets[0].id}"
            return "other"
        if isinstance(parent, ast.Expr):
            return "dropped"
        return "other"


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


def _build_indexes(scanners: list[_FileScanner], project: Project):
    # (class, method) -> key ; module path -> {func name -> key}
    method_index: dict[tuple[str, str], str] = {}
    methods_by_name: dict[str, list[str]] = {}
    module_funcs: dict[tuple[str, str], str] = {}
    funcs_by_name: dict[str, list[str]] = {}
    for info in project.functions.values():
        parts = info.qualname.split(".")
        if len(parts) == 1:
            module_funcs[(info.path, parts[0])] = info.key
            funcs_by_name.setdefault(parts[0], []).append(info.key)
        elif len(parts) == 2 and parts[0] in project.classes:
            method_index[(parts[0], parts[1])] = info.key
            methods_by_name.setdefault(parts[1], []).append(info.key)
    return method_index, methods_by_name, module_funcs, funcs_by_name


def _module_path(dotted: str, root: Path) -> str | None:
    """dynamo_tpu.engine.core -> dynamo_tpu/engine/core.py if it exists."""
    rel = Path(dotted.replace(".", "/") + ".py")
    if (root / rel).is_file():
        return rel.as_posix()
    rel = Path(dotted.replace(".", "/")) / "__init__.py"
    if (root / rel).is_file():
        return rel.as_posix()
    return None


def resolve_calls(scanners: list[_FileScanner], project: Project) -> None:
    method_index, methods_by_name, module_funcs, funcs_by_name = _build_indexes(
        scanners, project
    )
    attr_types: dict[tuple[str, str], str] = {}
    for sc in scanners:
        attr_types.update(sc.attr_types)

    for sc in scanners:
        for info in [
            f for f in project.functions.values() if f.path == sc.path
        ] + [sc.module_func]:
            enclosing_class = (
                info.qualname.split(".")[0]
                if "." in info.qualname and info.qualname.split(".")[0] in project.classes
                else None
            )
            for cs in info.calls:
                cs.targets = _resolve_one(
                    cs.raw, sc, info, enclosing_class, project, attr_types,
                    method_index, methods_by_name, module_funcs, funcs_by_name,
                )
                for t in cs.targets:
                    project.callers.setdefault(t, []).append((info.key, cs))
            for cs in info.spawn_sites:
                cs.targets = _resolve_one(
                    cs.raw, sc, info, enclosing_class, project, attr_types,
                    method_index, methods_by_name, module_funcs, funcs_by_name,
                )


def _resolve_one(
    raw: str, sc: _FileScanner, info: FuncInfo, enclosing_class: str | None,
    project: Project, attr_types: dict[tuple[str, str], str],
    method_index, methods_by_name, module_funcs, funcs_by_name,
) -> list[str]:
    if raw.startswith("<expr>"):
        last = raw.rsplit(".", 1)[-1]
        return _unique(methods_by_name.get(last, []))
    parts = raw.split(".")
    last = parts[-1]
    # self.m() / self.attr.m() with a typed attr.
    if parts[0] == "self" and enclosing_class is not None:
        if len(parts) == 2:
            key = method_index.get((enclosing_class, last))
            if key:
                return [key]
            return _unique(methods_by_name.get(last, []))
        if len(parts) == 3:
            t = attr_types.get((enclosing_class, parts[1]))
            if t is not None:
                key = method_index.get((t, last))
                if key:
                    return [key]
            return _unique(methods_by_name.get(last, []))
        return []
    # Bare name: local module function, else import, else unique global.
    if len(parts) == 1:
        key = module_funcs.get((sc.path, last))
        if key:
            return [key]
        imp = sc.imports.get(last)
        if imp and "." in imp:
            mod, fname = imp.rsplit(".", 1)
            mpath = _module_path(mod, project.root)
            if mpath:
                key = module_funcs.get((mpath, fname))
                if key:
                    return [key]
        return _unique(funcs_by_name.get(last, []))
    # mod.f() via import alias.
    head = parts[0]
    imp = sc.imports.get(head)
    if imp is not None:
        dotted = imp + "." + ".".join(parts[1:-1]) if len(parts) > 2 else imp
        mpath = _module_path(dotted, project.root)
        if mpath:
            key = module_funcs.get((mpath, last))
            if key:
                return [key]
        # imported class: ClassName.method
        cls = imp.rsplit(".", 1)[-1]
        if cls in project.classes and len(parts) == 2:
            key = method_index.get((cls, last))
            if key:
                return [key]
        return []
    # ClassName.method / param.method via annotation.
    if head in project.classes and len(parts) == 2:
        key = method_index.get((head, last))
        if key:
            return [key]
    t = sc.param_types.get((info.qualname, head))
    if t is not None and len(parts) == 2:
        key = method_index.get((t, last))
        if key:
            return [key]
    # obj.m(): unique method name fallback.
    return _unique(methods_by_name.get(last, []))


def _unique(keys: list[str]) -> list[str]:
    return list(keys) if len(set(keys)) == 1 else []


# ---------------------------------------------------------------------------
# Pragmas (`# dynacheck: allow-<rule>(<reason>)`), anchored to the full
# line span of the enclosing statement — the lesson of the dynalint
# multi-line pragma bug, applied from day one here.
# ---------------------------------------------------------------------------

import re

_ALLOW_RE = re.compile(r"dynacheck:\s*allow-([a-z][a-z0-9-]*)\s*\(\s*([^)]*?)\s*\)")
_KNOB_DYNAMIC_RE = re.compile(r"dynacheck:\s*knob-dynamic\s*\(\s*([^)]*?)\s*\)")
_ANY_PRAGMA_RE = re.compile(r"^#+\s*dynacheck:")
_DYNALINT_HOLDS_RE = re.compile(r"dynalint:\s*holds-lock\s*\(\s*([A-Za-z_][A-Za-z0-9_]*)\s*\)")
_DYNALINT_SYNC_OK_RE = re.compile(r"dynalint:\s*sync-ok\b")


@dataclass(frozen=True)
class Pragma:
    path: str
    line: int
    rule: str
    reason: str


def extract_pragmas(path: str, source: str, tree: ast.Module, project: Project) -> None:
    # Span anchoring and comment classification are SHARED with dynalint:
    # the two tiers must never disagree about which lines a pragma covers.
    from tools.dynalint.linter import comment_tokens, covered_lines, statement_spans

    spans = statement_spans(tree)
    holds_lines: list[tuple[int, str]] = []
    for line, text, standalone in comment_tokens(source):
        covered = covered_lines(spans, line, standalone)
        for m in _DYNALINT_HOLDS_RE.finditer(text):
            holds_lines.append((line, m.group(1)))
        if _DYNALINT_SYNC_OK_RE.search(text):
            project.sync_ok_lines.update((path, ln) for ln in covered)
        if not _ANY_PRAGMA_RE.search(text):
            continue
        matched = False
        for m in _KNOB_DYNAMIC_RE.finditer(text):
            # A declared dynamic env-name escape: suppresses config-knob
            # on the statement, recorded in the pragma inventory under
            # its own rule name.
            reason = m.group(1).strip()
            matched = True
            if not reason:
                project.pragma_errors.append((
                    path, line, "knob-dynamic pragma requires a non-empty reason",
                ))
                continue
            project.pragmas.append(Pragma(path, line, "knob-dynamic", reason))
            bucket = project.allow_lines.setdefault(C.RULE_CONFIG_KNOB, set())
            bucket.update((path, ln) for ln in covered)
        for m in _ALLOW_RE.finditer(text):
            rule, reason = m.group(1), m.group(2).strip()
            matched = True
            if rule not in C.ALL_RULES:
                project.pragma_errors.append((
                    path, line,
                    f"allow pragma names unknown rule {rule!r} "
                    f"(known: {', '.join(C.ALL_RULES)})",
                ))
                continue
            if not reason:
                project.pragma_errors.append((
                    path, line, f"allow-{rule} pragma requires a non-empty reason",
                ))
                continue
            project.pragmas.append(Pragma(path, line, rule, reason))
            # Anchored to the enclosing statement's FULL span (plus the
            # statement below, for a standalone pragma-above comment).
            bucket = project.allow_lines.setdefault(rule, set())
            bucket.update((path, ln) for ln in covered)
        if not matched:
            project.pragma_errors.append((
                path, line,
                "unparseable dynacheck pragma; expected "
                "`dynacheck: allow-<rule>(<reason>)`",
            ))
    # Attach dynalint holds-lock pragmas to defs (Engine A rule 3 input).
    if holds_lines:
        for info in [f for f in project.functions.values() if f.path == path]:
            probes = {info.lineno, info.lineno - 1}
            got = {arg for line, arg in holds_lines if line in probes}
            if got:
                info.holds_pragmas = info.holds_pragmas | got


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _excluded(rel: str) -> bool:
    return any(part in rel for part in C.EXCLUDE_PARTS)


def iter_py_files(paths: list[Path], repo_root: Path) -> list[Path]:
    out: list[Path] = []
    for p in paths:
        if p.is_file() and p.suffix == ".py":
            out.append(p)
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                try:
                    rel = f.resolve().relative_to(repo_root.resolve()).as_posix()
                except ValueError:
                    rel = f.as_posix()
                if not _excluded(rel):
                    out.append(f)
    return out


def build_project(paths: list[Path], repo_root: Path) -> Project:
    project = Project(root=repo_root)
    scanners: list[_FileScanner] = []
    sources: list[tuple[str, str, ast.Module]] = []
    for f in iter_py_files(paths, repo_root):
        try:
            rel = f.resolve().relative_to(repo_root.resolve()).as_posix()
        except ValueError:
            rel = f.as_posix()
        source = f.read_text(encoding="utf-8", errors="replace")
        try:
            tree = ast.parse(source, filename=rel)
        except SyntaxError:
            continue  # dynalint owns syntax-error reporting
        sources.append((rel, source, tree))
    # Pass 1: collect classes + locks first (resolution needs the full
    # class index, and lock-id resolution needs the full lock registry).
    pre = []
    for rel, source, tree in sources:
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                project.classes.setdefault(node.name, set()).add(rel)
                project.bases.setdefault(node.name, set()).update(
                    d.rsplit(".", 1)[-1] for d in map(dotted_name, node.bases) if d)
        pre.append((rel, source, tree))
    for rel, source, tree in pre:
        _collect_locks(rel, tree, project)
    # Pass 2: full scan.
    for rel, source, tree in pre:
        sc = _FileScanner(rel, tree, project)
        sc.visit(tree)
        scanners.append(sc)
        project.trees[rel] = tree
        project.imports_by_file[rel] = sc.imports
        extract_pragmas(rel, source, tree, project)
    resolve_calls(scanners, project)
    return project


def _collect_locks(path: str, tree: ast.Module, project: Project) -> None:
    class_stack: list[str] = []

    def walk(node, in_func: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                class_stack.append(child.name)
                walk(child, in_func)
                class_stack.pop()
                continue
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                walk(child, True)
                continue
            if isinstance(child, ast.Assign) and isinstance(child.value, ast.Call):
                vd = dotted_name(child.value.func)
                if vd in C.LOCK_CONSTRUCTORS:
                    for target in child.targets:
                        td = dotted_name(target)
                        if td is None:
                            continue
                        if td.startswith("self.") and class_stack and "." not in td[5:]:
                            project.locks[(class_stack[-1], td[5:])] = (path, child.lineno)
                        elif "." not in td and not in_func:
                            project.locks[(path, td)] = (path, child.lineno)
            walk(child, in_func)

    walk(tree, False)
