"""engine/warmup.py: the warm-up's Python call stack lies in one chunk.

CPython keeps frames in 16 KiB chunks it maps and unmaps as calls cross
their ends; tracing and lowering recurse through several, and a hot loop
that sits under a chunk's end pays a map, a page fault and an unmap on
every call it makes (PERF.md, PR 24: the `setup_s` finding)."""

import resource
import sys
import threading

import pytest

from dynamo_tpu.engine.warmup import _on_one_stack_chunk

pytestmark = [pytest.mark.unit]

DEPTH, CALLS = 400, 100


def _probe(depth: int, calls: int) -> None:
    """Recurses ``depth`` frames down and makes ``calls`` calls from each
    level on the way back. Caller and callee have the same frame size,
    so the level under each chunk's end sends every call over it."""
    if depth < 0:
        return
    if depth:
        _probe(depth - 1, calls)
    for _ in range(calls):
        _probe(-1, 0)


def _minor_faults_in_a_thread(fn, *args) -> int:
    faults = []

    def run():
        before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
        fn(*args)
        faults.append(resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before)

    thread = threading.Thread(target=run)
    thread.start()
    thread.join()
    return faults[0]


@pytest.mark.skipif(
    sys.implementation.name != "cpython" or sys.version_info < (3, 11)
    or not hasattr(resource, "RUSAGE_THREAD"),
    reason="CPython 3.11+ frame chunks, counted per thread on Linux",
)
def test_the_warm_ups_stack_is_one_chunk():
    plain = _minor_faults_in_a_thread(_probe, DEPTH, CALLS)
    roomy = _minor_faults_in_a_thread(_on_one_stack_chunk, _probe, DEPTH, CALLS)
    # 400 frames cross at least one chunk's end, and the level under it
    # makes 100 calls over it; in one chunk only new pages fault.
    assert plain >= CALLS, plain
    assert roomy < 64, roomy


def test_the_call_goes_through_unchanged():
    assert _on_one_stack_chunk(lambda a, b: (b, a), 1, 2) == (2, 1)
    with pytest.raises(ZeroDivisionError):
        _on_one_stack_chunk(lambda: 1 / 0)
