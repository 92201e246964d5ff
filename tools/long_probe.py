"""A LONG comparison of a benchmark configuration's engine with its plain
reference, through the functions the benchmark's own check uses
(``chipbench.reference.check``: ``run_probe``, ``score_probe``, ``compare``,
``LOGPROB_ATOL``), at a length its fixed probe (96 + 17 tokens,
``chipbench/run.py:PROBE``) never reaches.

Written for ``laguna-s-2.1-ep8-9l-bf16`` (PR 39): a prompt of 2,048 + 600
tokens goes through two chunked waves and 33 decoded tokens, so that the
window's mask, the table that starts at the window's first page, blocks
given back as the window slides, and YaRN at positions past 2,048 are all
inside what is compared. ``--faults window,gate`` then scores the same
served probe against the reference with each named mechanism LEFT OUT on
the reference's side (``chipbench/reference/laguna.py``, ``faults``): those
comparisons must FAIL, which is what says the tolerance still sees the
mechanism. Exit 0 only if the sound comparison passes and every fault fails.

Builds the engine as a worker does (the configuration's ``serve.engine``,
weights from ``--seed``), without warm-up: the programs it meets compile on
first use. Refuses to run without a TPU unless ``--allow-cpu``.

Usage (through the chip tool, from the repo root):
    python -m tools.long_probe [--config laguna-s-2.1-ep8-9l-bf16]
        [--prompt-tokens 2648] [--max-tokens 33] [--seeds 11,2147483659]
        [--faults window,gate]
Writes ``chiprun_out/long_probe/<config>.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import random
import time
from pathlib import Path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="laguna-s-2.1-ep8-9l-bf16")
    ap.add_argument("--prompt-tokens", type=int, default=2648)
    ap.add_argument("--max-tokens", type=int, default=33)
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--faults", default="window,gate")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    from chipbench.configs import engine_overrides, load_config, model_fields
    from chipbench.reference import check
    from dynamo_tpu.device import device_info, enable_compile_cache
    from dynamo_tpu.engine import PRESETS, ModelConfig
    from dynamo_tpu.ops.ragged_attention import traced_calls

    enable_compile_cache()
    info = device_info()
    if info["platform"] != "tpu" and not args.allow_cpu:
        raise SystemExit(f"tools/long_probe.py: no TPU (platform {info['platform']!r})")
    cfg = load_config(args.config)
    fields = model_fields(cfg)
    PRESETS[args.config] = lambda: ModelConfig(**fields)

    from dynamo_tpu.backends.jax.main import build_engine

    faults = [f for f in args.faults.split(",") if f]
    rows, ok = [], True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        core, engine = build_engine(args.config, engine_overrides(cfg),
                                    seed=seed % (2 ** 31 - 1), quant=cfg["serve"].get("quant"))
        rng = random.Random(seed ^ 0x5EED)
        hi = min(cfg["vocab_size"], 32000)
        prompt = [rng.randrange(1, hi) for _ in range(args.prompt_tokens)]
        probe = check.run_probe(core, prompt, args.max_tokens, 5, f"long-{seed}")
        served_s = time.perf_counter() - t0
        stats = core.scheduler_stats()
        for fault in [None, *faults]:
            options = {"faults": (fault,)} if fault else {}
            t1 = time.perf_counter()
            scored = check.score_probe(cfg, core.params, prompt, probe, **options)
            verdict = check.compare([probe], {"sequences": [scored]})
            want = fault is None
            ok &= verdict["ok"] == want
            row = {"seed": seed, "fault": fault, "must_pass": want, **verdict,
                   "atol": check.LOGPROB_ATOL, "scored_s": round(time.perf_counter() - t1, 1)}
            rows.append(row)
            print(json.dumps(row), flush=True)
        rows.append({"seed": seed, "served_s": round(served_s, 1),
                     "prompt_tokens": len(prompt), "generated": len(probe["tokens"]),
                     "prefill_waves": stats["prefill_waves"],
                     "window_blocks_released": stats.get("window_blocks_released"),
                     "window_blocks_in_use": stats.get("window_blocks_in_use"),
                     "attention_traced": {f"{shape}/{impl}": n for (shape, impl), n
                                          in sorted(traced_calls().items())}})
        print(json.dumps(rows[-1]), flush=True)
        core = engine = probe = scored = None   # the next seed's engine needs the device's memory
        gc.collect()
    out = Path("chiprun_out/long_probe")
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.config}.json").write_text(json.dumps(
        {"device": info, "ok": bool(ok), "rows": rows}, indent=1))
    print(json.dumps({"ok": bool(ok), "device": info}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
