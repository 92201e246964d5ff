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


# -- the pipelined loop's feedback programs (ISSUE 25) ---------------------------


def test_serving_compiles_no_feedback_program_after_warm_up():
    """The one-step-ahead loop feeds a step's token buffer from the step
    before it on the device: a megastep gathers inside its own program
    (since PR 40: no gather program of its own) over a source padded per
    output shape to ONE width. Warm-up meets every width and both kinds of
    source, so serving that crosses widths (3 <-> 7 lanes) and follows a
    prefill wave compiles no padding and no megastep again."""
    from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
    from dynamo_tpu.engine.warmup import warm_up
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    # Widths no other test's engine has: the jit caches of one function
    # are shared by every engine in the process. The feedback width is
    # megastep x max(widest decode bucket, prefill_batch): at the default
    # prefill_batch of 8 it is the 32 of every other engine with k = 4, and
    # a worker that ran one of those first had the wave's padding compiled.
    core = EngineCore(
        tiny_model(),
        tiny_engine(megastep_k=4, decode_buckets=(3, 7), max_num_seqs=7,
                    prefill_batch=5),
        seed=0,
    )
    assert core.pipelined

    def programs():
        return (core._feed._cache_size(), core._feed_pad._cache_size(),
                core._decode._cache_size())

    cold = programs()
    phases = warm_up(core)
    assert any(p.startswith("decode B=7") for p in phases)
    warm = programs()
    # no gather of a megastep's own; a padding per output shape (a prefill
    # wave's, and a megastep's per width); a megastep per width, sampled
    # and greedy in one
    assert tuple(w - c for w, c in zip(warm, cold)) == (0, 3, 2)
    fed = core.exec_stats["pipelined_dispatches"]

    def req(i, n_prompt, max_tokens):
        return core.add_request(PreprocessedRequest(
            model="tiny", token_ids=list(range(1 + i, 1 + i + n_prompt)),
            request_id=f"r{i}", sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=max_tokens, ignore_eos=True)))

    # Three lanes (width 3); three more arrive behind a prefill wave while
    # those decode (width 7); the short ones end and the width falls back.
    seqs = [req(i, 9, 1 + 4 * 6) for i in range(3)]
    for _ in range(3):
        core.step()
    seqs += [req(i, 13, 1 + 4 * 2) for i in range(3, 6)]
    for _ in range(400):
        core.step()
        if all(s.finish for s in seqs) and not core.has_work():
            break
    assert all(s.finish == "length" for s in seqs)
    assert core.exec_stats["pipelined_dispatches"] > fed + 6
    assert programs() == warm
