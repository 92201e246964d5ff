"""Cuts the slice of a traced run that ``test_recorded_v5e_slice`` keeps
(``data/phase_slice.json``, with ``data/phase_slice.expect.json`` the
summary recorded with it). From the repo's root:
``python tests/chipbench/make_phase_slice.py <trace dir> <ms> <slice.json> [skip ms]``."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.trace import phases  # noqa: E402
from chipbench.trace.reduce import find_xplane  # noqa: E402


def slice_of(trace: dict, ms: float, skip_ms: float = 0.0) -> dict:
    """About ``ms`` milliseconds of the trace, from ``skip_ms`` after the
    first device op: small enough to keep among the tests. Only whole
    program executions are kept (a ``while`` cut from its body would
    count the body's time as its own), so the slice runs from the first
    execution that starts inside the span to the last that ends inside."""
    shift = phases.clock_shift(trace)[0]
    a = min(op[1] for op in trace["ops"]) + skip_ms * 1e6
    b = a + ms * 1e6
    whole = [m for m in trace["modules"] if m[1] >= a and m[1] + m[2] <= b]
    if whole:
        a, b = min(m[1] for m in whole), max(m[1] + m[2] for m in whole)
    inside = lambda ev, d=0.0: [e for e in ev if e[1] + d >= a and e[1] + e[2] + d <= b]  # noqa: E731
    runs = {m[3] for m in whole}
    return {
        "ops": [[op[0][:40], *op[1:]] for op in inside(trace["ops"])],
        "modules": whole,
        "phases": [e for e in trace["phases"]
                   if e[1] - shift < b and e[1] + e[2] - shift > a],
        "enqueues": [e for e in trace["enqueues"] if e[1] in runs],
        "completes": [e for e in trace["completes"] if e[1] in runs],
        "window": [a + shift, b + shift], "device": trace.get("device", ""),
    }


if __name__ == "__main__":
    trace = phases.load(find_xplane(Path(sys.argv[1])))
    skip = float(sys.argv[4]) if len(sys.argv) > 4 else 0.0
    Path(sys.argv[3]).write_text(json.dumps(slice_of(trace, float(sys.argv[2]), skip)))
