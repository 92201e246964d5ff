"""A decode megastep with every lane at temperature 0 against one with
every lane drawing, timed on the chip through the engine's own loop.

No cell of the benchmark serves at temperature 0, so none can say what a
GREEDY batch's step costs. Since PR 53 one compiled program a shape serves
both (the sampler's conditional on the device, ``engine/sampler.py``), where
a greedy batch used to have an executable of its own: this tool times the
greedy megastep on either tree (it drives ``EngineCore`` through
``add_request`` / ``step`` and names nothing either lacks), so that the two
can be compared in one chip call:

    python -m tools.sampling_branch_bench [--config qwen2.5-7b-int8]
        [--lanes 32] [--dispatches 60] [--temperatures 0.0,0.7] [--seed 11]

Builds the configuration's engine as a worker does (weights from ``--seed``,
no warm-up: a first pass a temperature compiles what it meets and is
reported apart, ``"compiles": true``), fills ``--lanes`` lanes with one short prompt each, lets
them reach steady decode, and times ``--dispatches`` megasteps on the host's
clock over the pipelined loop, where the device is what the loop waits for.
Per temperature: ms a megastep and ms an inner step by the wall, the step
clock's device seconds a megastep, and (where the tree counts them) the
dispatches by the sampler's branch. Refuses to run without a TPU unless
``--allow-cpu``. Writes ``chiprun_out/sampling_branch_bench/<config>.json``.
"""

from __future__ import annotations

import argparse
import json
import random
import time
from pathlib import Path

PROMPT = 64


def _timed(core, lanes: int, dispatches: int, temperature: float, seed: int) -> dict:
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    k = core.engine.megastep
    settle = 6
    max_tokens = 1 + (dispatches + 2 * settle + 4) * k
    if PROMPT + max_tokens > core.engine.max_model_len:
        raise SystemExit(f"--dispatches {dispatches} needs {PROMPT + max_tokens} positions "
                         f"a lane, max_model_len is {core.engine.max_model_len}")
    rng = random.Random(seed)
    hi = min(core.cfg.vocab_size, 32000)
    seqs = [core.add_request(PreprocessedRequest(
        model="bench", token_ids=[rng.randrange(1, hi) for _ in range(PROMPT)],
        request_id=f"t{temperature}-{i}",
        sampling=SamplingOptions(temperature=temperature, seed=i),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True)))
        for i in range(lanes)]
    stats = core.exec_stats

    def run(n: int) -> None:
        until = stats["megastep_dispatches"] + n
        while stats["megastep_dispatches"] < until:
            core.step()

    while any(not s.prefill_done for s in seqs):
        core.step()
    run(settle)                       # the compiles, and the pipeline full
    before = dict(stats)
    device0 = core.device_account()["device_seconds"].get("megastep", 0.0)
    t0 = time.perf_counter()
    run(dispatches)
    wall = time.perf_counter() - t0
    device = core.device_account()["device_seconds"].get("megastep", 0.0) - device0
    n = stats["megastep_dispatches"] - before["megastep_dispatches"]
    row = {
        "temperature": temperature, "lanes": lanes, "k": k, "megasteps": n,
        "wall_ms_a_megastep": round(1e3 * wall / n, 4),
        "wall_ms_a_step": round(1e3 * wall / n / k, 4),
        "device_ms_a_megastep": round(1e3 * device / n, 4),
        "by_sampling": {kind: stats[f"dispatches_{kind}"] - before[f"dispatches_{kind}"]
                        for kind in ("greedy", "drawn") if f"dispatches_{kind}" in stats},
    }
    while any(s.finish is None for s in seqs):   # leave the lanes free for the next
        core.step()
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen2.5-7b-int8")
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--dispatches", type=int, default=60)
    ap.add_argument("--temperatures", default="0.0,0.7")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--tag", default="")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from chipbench.configs import engine_overrides, load_config, model_fields
    from dynamo_tpu.device import device_info, enable_compile_cache
    from dynamo_tpu.engine import PRESETS, ModelConfig

    enable_compile_cache()
    info = device_info()
    if info["platform"] != "tpu" and not args.allow_cpu:
        raise SystemExit(f"tools/sampling_branch_bench.py: no TPU (platform {info['platform']!r})")
    cfg = load_config(args.config)
    fields = model_fields(cfg)
    PRESETS[args.config] = lambda: ModelConfig(**fields)

    from dynamo_tpu.backends.jax.main import build_engine

    core, _engine = build_engine(args.config, engine_overrides(cfg),
                                 seed=args.seed % (2 ** 31 - 1), quant=cfg["serve"].get("quant"))
    rows = []
    for repeat in range(-1, args.repeats):   # -1: the pass that compiles, kept apart
        for temperature in (float(t) for t in args.temperatures.split(",")):
            row = {"repeat": repeat, "compiles": repeat < 0,
                   **_timed(core, args.lanes, args.dispatches, temperature, args.seed + repeat)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = Path("chiprun_out/sampling_branch_bench")
    out.mkdir(parents=True, exist_ok=True)
    name = f"{args.config}{'-' + args.tag if args.tag else ''}.json"
    (out / name).write_text(json.dumps({"device": info, "rows": rows}, indent=1))
    print(json.dumps({"device": info, "wrote": str(out / name)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
