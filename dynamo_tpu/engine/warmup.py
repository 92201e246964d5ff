"""Compile a worker's serving programs before it takes traffic.

The engine compiles lazily: the first prefill wave in each token bucket
and the first decode megastep at each batch width trace every layer of
the model and compile for tens of seconds on a chip. A worker that
registers first and compiles on its first request holds that request's
stream silent for the whole compile; the frontend's stall deadline
(``DYN_DATAPLANE_STALL_TIMEOUT_S``) then declares the worker dead and
replays the stream onto the same, still compiling, worker. So the worker
drives synthetic requests through the normal ``add_request``/``step``
path before ``register_llm``, and the deadline stays what it is.

Covered: every prefill bucket one wave can reach and every decode width
``max_num_seqs`` can reach at the full megastep length, for the one
sampling program ordinary requests select: without a top-k/top-p mask, the
OpenAI default. It serves sampled and greedy requests alike (whether any
lane of a batch draws is a conditional on the device, engine/sampler.py),
so one pass at temperature 1.0 compiles it and a first greedy request
finds nothing left to compile. On the pipelined loop a decode
phase runs two megasteps, so that both kinds of output (a prefill
wave's, a megastep's) have fed a token buffer of that width: a megastep
takes its lanes' inputs as one packed array and gathers the fed tokens
inside its own program, from a source padded per output shape to one
width (``EngineCore._feed_source``; a megastep that nothing feeds is
handed zeros of that shape and runs the same program), so serving may
then cross widths freely. Left to compile on first use,
one short program at a time: shortened megasteps at the end of a
generation budget, masked sampling, logprobs, speculative verify rows and
multimodal prefill. Chunked scheduling runs the same traffic, which
compiles the mixed-step shapes that traffic happens to form.
"""

from __future__ import annotations

import logging
import time

import numpy as np

from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.tracing import startclock
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

log = logging.getLogger("dynamo_tpu.engine.warmup")


def _run(core: EngineCore, prompts: list[list[int]], max_tokens: int, tag: str) -> float:
    """Serve ``prompts`` to the end, every lane drawing (temperature 1.0,
    no mask); returns the seconds the step loop took."""
    seqs = [
        core.add_request(PreprocessedRequest(
            model="warmup",
            token_ids=p,
            request_id=f"warmup-{tag}-{i}",
            sampling=SamplingOptions(temperature=1.0, seed=i),
            stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        ))
        for i, p in enumerate(prompts)
    ]
    t0 = time.perf_counter()
    while any(s.finish is None for s in seqs):
        core.step()
    return time.perf_counter() - t0


def _on_one_stack_chunk(fn, *args):
    """Call ``fn(*args)`` with its whole Python call stack in one piece
    of memory.

    CPython (3.11 and later) keeps Python frames in 16 KiB chunks: it
    maps a new chunk when a call does not fit in the current one and
    unmaps it when that call returns. Tracing and lowering a model
    recurse a few hundred frames deep, and where a chunk's end falls
    just under a hot loop of that recursion (``jaxpr_subcomp``'s loop
    over a layer's equations), every call the loop makes maps, faults in
    and unmaps a chunk: measured on ``_megastep_body`` at 24 layers,
    190,000 to 360,000 minor page faults and 1.0-2.3 s of system time in
    3.5-5.6 s of lowering, against 24 faults and 1.5 s from here. Which
    loop is hit depends on every frame's size from the thread's first
    frame down (so on the thread, and on unrelated edits to the callers),
    not on the program being lowered. A frame that reserves more than
    half a MiB of evaluation stack cannot fit in a 16 KiB chunk, so
    CPython gives it a chunk of 1 MiB of its own, whose remaining half
    holds every frame below it until it returns."""
    return fn(*args)


_on_one_stack_chunk.__code__ = _on_one_stack_chunk.__code__.replace(
    co_stacksize=1 << 16  # slots of 8 bytes: 512 KiB, so a 1 MiB chunk
)


def warm_up(core: EngineCore) -> dict[str, float]:
    """Run the warm-up traffic; returns wall seconds per phase. The
    context's start-up clock (tracing/startclock.py; one of this call's own
    where none runs) keeps the time: a row a program in its ``warmup``
    stage, with the compile events :class:`dynamo_tpu.device.CompileLog`
    hears while the row is open, then ``waves_timed``.

    The allocator's KV-event callbacks are detached for the duration and
    the warm-up blocks are dropped from the prefix cache afterwards, so
    routers never hear of them. Scheduler counters do include the
    warm-up's dispatches.

    When every program is compiled, each prefill bucket's wave runs once
    more and is timed from its first step to its last token, and the
    table (``core.prefill_bucket_ms``, ms per bucket) is installed: from
    then on the waves planner covers the waiting prompt tokens with the
    cheapest set of compiled waves (``EngineCore._plan_prefill_wave``).
    It is installed last and whole. While it is empty the planner takes
    every waiting token, which is what lets a wave here fill its bucket
    and compile it; a table that grew bucket by bucket could price four
    512 waves under the 2,048 wave that was about to be compiled, and
    serving would compile that program on a user's request. The waves are
    timed as they were compiled, at temperature 1.0. An engine that skips
    warm-up (in-process test engines, multi-host workers, whose
    schedulers run in lockstep and so may read no local clock) keeps an
    empty table and plans as before; that is why there is no option."""
    return _on_one_stack_chunk(_warm_up, core)


def _warm_up(core: EngineCore) -> dict[str, float]:
    eng = core.engine
    rng = np.random.RandomState(0)
    vocab = core.cfg.vocab_size
    k = eng.megastep
    lanes = min(eng.max_num_seqs, eng.max_waiting or eng.max_num_seqs)
    # Tokens a decode phase generates: the prefill's, then one megastep,
    # or two where the second is fed from the first on the device.
    gen = 1 + (2 * k if core.pipelined else k)
    # Longest prompt that still leaves room to generate them.
    max_prompt = eng.max_model_len - gen - 1
    bs = eng.block_size
    # A wave that cannot be admitted would wait for blocks forever.
    block_budget = int(eng.num_kv_blocks * 0.9)
    own = startclock.current() is None   # no worker's clock runs: this call's own
    clock = startclock.running()
    clock.mark("warmup")

    def prompts(n: int, length: int) -> list[list[int]]:
        return [rng.randint(1, vocab, size=length).tolist() for _ in range(n)]

    alloc = core.allocator
    saved = alloc.on_stored, alloc.on_removed
    alloc.on_stored = lambda hashes, parent: None
    alloc.on_removed = lambda hashes: None
    try:
        prev = 0
        waves = []  # (bucket, prompts, tokens each) that filled a bucket
        for bucket in eng.prefill_buckets:
            n = min(eng.prefill_batch, lanes)
            length = min(bucket // n, max_prompt)
            if n * length <= prev or n * -(-length // bs) > block_budget:
                break  # one wave cannot fill this bucket (or larger)
            with clock.row(f"prefill T={bucket}"):
                _run(core, prompts(n, length), 1, f"prefill{bucket}")
            waves.append((bucket, n, length))
            prev = bucket
        prev = 0
        for width in eng.decode_buckets:
            n = min(width, lanes)
            length = min(bs, max_prompt)
            if n <= prev or n * -(-(length + gen) // bs) > block_budget:
                break  # max_num_seqs (or the cache) never reaches it
            with clock.row(f"decode B={width} k={k}"):
                _run(core, prompts(n, length), gen, f"decode{width}")
            prev = width
        # Everything is compiled: the host's cost per dispatch counts from
        # here, and each bucket's wave is timed on a fresh set of prompts.
        core.count_host_floor_from_here()
        clock.mark("waves_timed")
        table = {}
        for bucket, n, length in waves:
            seconds = _run(core, prompts(n, length), 1, f"timed-prefill{bucket}")
            table[bucket] = round(1e3 * seconds, 3)
        # handing over: the clean-up below is the first of ``register``
        clock.mark("register")
    finally:
        core.clear_kv_cache()
        alloc.on_stored, alloc.on_removed = saved
        if own:
            # left running, it would be taken for the clock of the next
            # worker this process starts
            clock.close()
    core.prefill_bucket_ms = table
    log.info(
        "prefill wave ms by bucket: %s (host floor so far %.1f ms a dispatch)",
        table, core.host_floor_ms(),
    )
    phases = clock.row_walls()
    phases["prefill waves timed"] = round(clock.seconds("waves_timed"), 2)
    return phases
