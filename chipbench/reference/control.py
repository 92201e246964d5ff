"""The control of ``correct``: comparisons that must come out as NOT correct.

``run.reference_check`` passes where the engine agrees with the plain
reference inside ``check.LOGPROB_ATOL``. That says something only if the
same comparison, at the same probe, fails what it should. Two kinds, both
driven through ``run.reference_check`` and ``check.compare`` with the
worker's side answered in this process by the function the worker calls
(``check.score_request``):

- a PRECISION (``fp8``): the reference, put in the program's place and
  computed in the nearest precision below the one the configuration states
  (:func:`lowered`). It need not decode: along the engine's own prompt and
  tokens it says, at every generated position, which ids it puts first and
  their log-probabilities, and the float32 reference scores those;
- a MECHANISM left out of the reference (``window``, ``gate``: the
  architecture's ``faults``), scored against what the engine served.

The benchmark's runs never come here. On the chip, at a cell's own probe
and weights (``chiprun -- python -m chipbench.reference.control --config
<name>``; the architecture's reference has to know the controls named), it reads
the sound comparison and each control over ``--seeds`` and exits 0 only if
every sound one passes and every control fails; the readings are what
``PERF.md`` sets a limit's two ends from. ``tests/chipbench`` keeps the same
at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from types import SimpleNamespace

from chipbench import architectures
from chipbench.reference import check

PRECISIONS = ("fp8",)


def lowered(cfg: dict, params, prompt: list[int], probe: dict, top: int, precision: str) -> dict:
    """The reference at ``precision`` in the program's place: a probe shaped
    as ``check.run_probe`` shapes one, along the engine's ``probe["tokens"]``.
    ``tokens`` are the ids the lower precision puts first (what ``compare``
    holds against the reference's arg-max); the sequence they were read along
    stays under ``sequence``."""
    import numpy as np

    ids = prompt + probe["tokens"]
    rows = list(range(len(prompt) - 1, len(ids) - 1))
    lp = check.reference_logprobs(cfg, params, ids, rows, faults=(precision,))
    first = np.argsort(-lp, axis=-1)[:, :top]
    return {"tokens": [int(r[0]) for r in first], "sequence": list(probe["tokens"]),
            "top_ids": [[int(t) for t in r] for r in first],
            "top_lps": [[float(lp[j, t]) for t in r] for j, r in enumerate(first)],
            "cached_tokens": probe["cached_tokens"]}


def answer(core, cfg: dict, body: dict, control: str | None, sound: dict | None = None) -> dict:
    """What the worker answers to ``ref.request``, with ``control`` in
    place: None is ``check.score_request`` itself; ``sound`` is that answer
    where the caller has it already (the engine is not asked twice)."""
    got = sound or check.score_request(core, cfg, body)
    if control is None:
        return got
    prompt = list(body["prompt_ids"])
    if control in PRECISIONS:
        served = [lowered(cfg, core.params, prompt, p, body["top"], control)
                  for p in got["served"][:1]] * len(got["served"])
        scored = check.score_probe(cfg, core.params, prompt, {
            "tokens": served[0]["sequence"], "top_ids": served[0]["top_ids"]})
    else:
        served = got["served"]
        scored = check.score_probe(cfg, core.params, prompt, served[0], faults=(control,))
    return {**got, "served": served, "scored": {"sequences": [scored] * len(served)}}


def verdicts(core, cfg: dict, seed: int, controls: list[str]) -> dict:
    """``run.reference_check``'s verdict for the sound comparison (key
    ``"sound"``) and for each control, on the probe a run of ``seed`` sends."""
    from chipbench import run

    cl = SimpleNamespace(workers=[SimpleNamespace(name="in-process")], children=None)
    kept: dict = {}
    out = {}
    for control in [None, *controls]:
        def side_call(worker, kind, body, children, timeout, control=control):
            kept.setdefault("sound", check.score_request(core, cfg, body))
            return json.loads(json.dumps(answer(core, cfg, body, control, kept["sound"])))

        real, run.side_call = run.side_call, side_call
        try:
            out[control or "sound"] = run.reference_check(cl, cfg, seed)
        finally:
            run.side_call = real
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="name under chipbench/configs")
    ap.add_argument("--seeds", default="3000000019")
    ap.add_argument("--controls", default="fp8,window")
    ap.add_argument("--out", default="chiprun_out/control")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    import gc

    from chipbench.configs import engine_overrides, load_config, model_fields
    from dynamo_tpu.device import device_info, enable_compile_cache
    from dynamo_tpu.engine import PRESETS, ModelConfig

    enable_compile_cache()
    info = device_info()
    if info["platform"] != "tpu" and not args.allow_cpu:
        raise SystemExit(f"chipbench.reference.control: no TPU (platform {info['platform']!r})")
    cfg = load_config(args.config)
    fields = model_fields(cfg)
    PRESETS[args.config] = lambda: ModelConfig(**fields)
    architectures.of(cfg)       # an unknown model_type fails here, before the engine is built

    from dynamo_tpu.backends.jax.main import build_engine

    controls = [c for c in args.controls.split(",") if c]
    rows, ok = [], True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        # weights from the seed as the worker draws them (worker_entry.main)
        core, engine = build_engine(args.config, engine_overrides(cfg), seed=seed % (2 ** 31 - 1),
                                    quant=cfg["serve"].get("quant"))
        built_s = time.perf_counter() - t0
        for name, v in verdicts(core, cfg, seed, controls).items():
            must_pass = name == "sound"
            ok &= v["ok"] == must_pass
            rows.append({"seed": seed, "control": name, "must_pass": must_pass,
                         "limit": check.LOGPROB_ATOL, **v})
            print(json.dumps(rows[-1]), flush=True)
        print(json.dumps({"seed": seed, "built_s": round(built_s, 1),
                          "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
        del core, engine
        gc.collect()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.config}.json").write_text(json.dumps(
        {"device": info, "ok": bool(ok), "rows": rows}, indent=1))
    print(json.dumps({"ok": bool(ok), "device": info}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
