"""Device seconds of the ops under a ``jax.named_scope`` that
``chipbench/trace/phases.py`` does not list among its ``SECTIONS`` (the
list is part of the yardstick and is not this reader's to extend), as % of
all op seconds, inside the program ``module`` where one is given. Read
from the same ``.xplane.pb`` (``phases.load``: plain protobuf, no JAX) with
the same self times, so that a ``while`` does not count its body twice.

An op's scope path is its ``tf_op`` stat
(``jit(_megastep_body)/while/body/while/body/loop_norm/cond/...``); the op
is under ``scope`` where a part of that path is equal to it. Where no op
is (a program without the scope, a trace without ``tf_op``): None.
"""

from __future__ import annotations

from chipbench.manifest import ROOT
from chipbench.trace import phases
from chipbench.trace.reduce import _module_name, _self_times, find_xplane


def share(trace: dict, scope: str, module: str | None = None) -> float | None:
    """``trace`` as ``phases.load`` gives it; programs are told apart as
    ``phases.summarize`` tells them."""
    ops, modules = trace["ops"], trace["modules"]
    starts = [m[1] for m in modules]
    inside = total = 0.0
    for op, own in zip(ops, _self_times(ops)):
        if module is not None and module != (
                _module_name(op[3]) if op[3]
                else phases._enclosing_module(modules, starts, op[1])):
            continue
        total += own
        if scope in op[4].split("/"):
            inside += own
    return 100.0 * inside / total if inside and total else None


def read(ctx, scope: str, module: str | None = None):
    if not ctx.trace:
        return None
    path = find_xplane(ROOT / "chipbench_out" / ctx.cell["name"] / "side-0" / "trace")
    if path is None:
        return None
    return share(phases.load(path), scope, module)
