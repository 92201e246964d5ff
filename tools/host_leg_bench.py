"""The host's leg of a decode dispatch, reproduced on the CPU.

N lanes of ``tiny`` decode through the pipelined loop, as a 128-lane cell
does on a chip; what is timed is the HOST's work between two enqueues (the
step clock's phases, ms a dispatch), which does not depend on the device:
the planners' walks over the lanes, the assembly of a megastep's inputs,
their transfers, the commit of a landed step. PR 39 found ``plan`` +11 ms a
dispatch at 128 lanes this way (``Sequence.__eq__``, 6,478 calls a plan);
PR 40 measured its three edits with it before they went to the chip.

    python -m tools.host_leg_bench [--lanes 128,32,8] [--dispatches 40]

Per lane count: ms a dispatch by phase, then, from a second short pass
that counts and does not time, the Python-level ``Sequence.__eq__`` calls
and the host-to-device transfers a megastep dispatch makes
(``_put_batch``, ``_to_device`` and the feed index's ``_fed``). The times
are a CPU's: they say how the host's work scales with the lanes, not what a
chip's host takes (PERF.md section 5 has both).
"""

from __future__ import annotations

import argparse
import json
import sys

from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine.core import Sequence
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

HOST_PHASES = ("between_steps", "admit", "plan", "assemble", "h2d", "dispatch", "commit")
PROMPT = 24


def _core(lanes: int, steps: int) -> tuple[EngineCore, list[Sequence]]:
    """``lanes`` sequences of ``tiny``, every one decoding, with room to
    decode for ``steps`` more megasteps and no block pressure."""
    k = 8
    tokens = PROMPT + (steps + 6) * k + 2
    eng = tiny_engine(
        max_num_seqs=lanes, decode_buckets=(lanes,), megastep_k=k,
        prefill_batch=8, max_model_len=-(-tokens // 8) * 8 + 8,
        num_kv_blocks=lanes * (-(-tokens // 8) + 2) + 8,
        enable_prefix_caching=True,
    )
    core = EngineCore(tiny_model(), eng, seed=0)
    seqs = [
        core.add_request(PreprocessedRequest(
            model="tiny", token_ids=[1 + (7 * i + j) % 250 for j in range(PROMPT)],
            request_id=f"lane{i}", sampling=SamplingOptions(temperature=0.7, seed=i),
            stop=StopConditions(max_tokens=tokens - PROMPT - 1, ignore_eos=True)))
        for i in range(lanes)
    ]
    # Past the prefill waves and the first (compiling) megasteps.
    while core.exec_stats["megastep_dispatches"] < 3 or any(
            not s.prefill_done for s in seqs):
        core.step()
    return core, seqs


def _run(core: EngineCore, dispatches: int) -> None:
    until = core.exec_stats["megastep_dispatches"] + dispatches
    while core.exec_stats["megastep_dispatches"] < until:
        core.step()


def timed(lanes: int, dispatches: int) -> dict[str, float]:
    core, _ = _core(lanes, 2 * dispatches)
    _run(core, dispatches // 4)   # settle
    before = core.step_phase_seconds()
    _run(core, dispatches)
    after = core.step_phase_seconds()
    ms = {phase: 1e3 * (seconds - before[(phase, blocks)]) / dispatches
          for (phase, blocks), seconds in after.items() if phase in HOST_PHASES}
    ms["host"] = sum(ms.values())
    return {p: round(v, 3) for p, v in ms.items()}


def counted(lanes: int, dispatches: int = 4) -> dict[str, float]:
    """``Sequence.__eq__`` calls a plan and transfers a megastep dispatch."""
    core, _ = _core(lanes, 2 * dispatches)
    counts = {"eq": 0, "transfers": 0, "in_megastep": 0}
    inner = Sequence.__eq__

    def eq(a, b):
        counts["eq"] += 1
        return inner(a, b)

    def transfer(fn):
        def call(*args, **kw):
            counts["transfers"] += counts["in_megastep"]
            return fn(*args, **kw)
        return call

    dispatch = core._dispatch_megastep

    def megastep(*args, **kw):
        counts["in_megastep"] = 1   # a wave's transfers are not a megastep's
        try:
            return dispatch(*args, **kw)
        finally:
            counts["in_megastep"] = 0

    core._dispatch_megastep = megastep
    for name in ("_put_batch", "_to_device", "_fed"):
        setattr(core, name, transfer(getattr(core, name)))
    own = "__eq__" in vars(Sequence)
    Sequence.__eq__ = eq
    try:
        _run(core, dispatches)
    finally:
        if own:
            Sequence.__eq__ = inner
        else:
            del Sequence.__eq__
    return {"sequence_eq_calls_per_dispatch": counts["eq"] / dispatches,
            "transfers_per_megastep": counts["transfers"] / dispatches}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lanes", default="128,32,8")
    ap.add_argument("--dispatches", type=int, default=40)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    rows = {}
    for lanes in (int(n) for n in args.lanes.split(",")):
        rows[lanes] = {**timed(lanes, args.dispatches), **counted(lanes)}
        if not args.json:
            r = rows[lanes]
            print(f"{lanes:4d} lanes: host {r['host']:7.3f} ms a dispatch = "
                  + " ".join(f"{p} {r[p]:.3f}" for p in HOST_PHASES)
                  + f" | Sequence.__eq__ {r['sequence_eq_calls_per_dispatch']:.0f} a dispatch, "
                  f"{r['transfers_per_megastep']:.0f} transfers a megastep", flush=True)
    if args.json:
        print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
