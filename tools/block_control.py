"""The controls of a block-diffusion configuration's ``correct``, on the chip:

    chiprun -- python -m tools.block_control --config sdar-30b-a3b-6l-bf16 --seeds 1,2,3

For each seed it builds the engine once, as ``chipbench/reference/control.py``
does, and reads (a) that module's own verdicts (``control.verdicts``: the sound
comparison and its controls, through ``run.reference_check``), and (b) the same
comparison with each fault handed to the ARCHITECTURE'S ``score_probe``
(``chipbench/architectures/sdar_moe.py``), which ``control.py`` does not call:
its mechanism controls go through ``check.score_probe``, the next-token
scoring, which fails a block-diffusion probe whatever is left out. (b) is the
reading that says whether the comparison SEES the in-block mask and the
schedule. One JSON line a verdict; exit 0 only if every sound comparison
passes and every control fails. Never part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="fp8,causal,order")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--down-divisor", type=int, default=0,
                    help="draw the routed experts' down-projection at 1 / N of the fan-in "
                         "scale in place of model._routed_down_divisor's: the sweep that "
                         "settles that rule (PERF.md section 6), never a serving option")
    ap.add_argument("--qk-gain", type=float, default=0.0,
                    help="draw q_norm / k_norm around this in place of model._qk_norm_gain's: "
                         "the sweep that settles THAT rule, never a serving option")
    args = ap.parse_args()

    from chipbench import architectures, run
    from chipbench.configs import engine_overrides, load_config, model_fields
    from chipbench.reference import check, control
    from dynamo_tpu.backends.jax.main import build_engine
    from dynamo_tpu.device import device_info, enable_compile_cache
    from dynamo_tpu.engine import PRESETS, ModelConfig

    if args.down_divisor:
        from dynamo_tpu.engine import model

        model._routed_down_divisor = lambda cfg: args.down_divisor
    if args.qk_gain:
        from dynamo_tpu.engine import model

        model._qk_norm_gain = lambda cfg: args.qk_gain
    enable_compile_cache()
    info = device_info()
    if info["platform"] != "tpu" and not args.allow_cpu:
        raise SystemExit(f"tools.block_control: no TPU (platform {info['platform']!r})")
    cfg = load_config(args.config)
    fields = model_fields(cfg)
    PRESETS[args.config] = lambda: ModelConfig(**fields)
    arch = architectures.of(cfg)
    controls = [c for c in args.controls.split(",") if c]
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        core, engine = build_engine(args.config, engine_overrides(cfg),
                                    seed=seed % (2 ** 31 - 1), quant=cfg["serve"].get("quant"))
        for name, v in ({} if args.down_divisor or args.qk_gain else
                        control.verdicts(core, cfg, seed, controls)).items():
            ok &= v["ok"] == (name == "sound")
            print(json.dumps({"seed": seed, "by": "control.py", "control": name, **v}), flush=True)
        # the same probe, the fault in the architecture's own scoring
        import random

        probe = run.probe_of(cfg)
        rng = random.Random(seed ^ 0x5EED)
        ids = [rng.randrange(1, min(cfg["vocab_size"], 32000))
               for _ in range(probe["prompt_tokens"])]
        served = check.run_probe(core, ids, probe["max_tokens"], probe["top"], "own", extra=True)
        for fault in [None, *controls]:
            scored = arch.score_probe(cfg, core.params, ids, served,
                                      **({"faults": (fault,)} if fault else {}))
            v = check.compare([served], {"sequences": [scored]})
            ok &= v["ok"] == (fault is None)
            print(json.dumps({"seed": seed, "by": "score_probe", "control": fault or "sound",
                              **v}), flush=True)
        print(json.dumps({"seed": seed, "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
        del core, engine
        gc.collect()
    print(json.dumps({"ok": bool(ok), "device": info}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
