"""Start one JAX worker for a benchmark configuration.

The program's own CLI (``python -m dynamo_tpu.backends.jax``) takes a
``--preset`` from a fixed list. This entry registers the cell's
configuration file as a preset and then calls the same
``run_jax_worker(..., warm_up=True)`` the CLI calls: same scheduler,
cache, warm-up and registration as production, no file of the program
edited.

Two things only the process that holds the chip can do are served from a
side thread, driven by files in ``--side-dir`` that the parent writes:

- ``trace.request`` ``{"seconds": s}``: run ``jax.profiler`` for that
  long, write the trace under ``<side-dir>/trace`` and ``trace.done``;
- ``ref.request`` ``{"prompt_ids", "max_tokens", "top"}``: send the probe
  through this worker's engine, run the plain reference
  (chipbench/reference) on this worker's own weights, and write both to
  ``ref.done`` (see :func:`chipbench.reference.check.score_request`).
"""

from __future__ import annotations

import argparse
import json
import logging
import threading
import time
import traceback
from pathlib import Path

log = logging.getLogger("chipbench.worker_entry")


def _serve_side_requests(side: Path, core_out: list, cfg: dict, stop: threading.Event) -> None:
    """Poll for request files; each is answered once and removed."""
    while not stop.wait(0.05):
        for kind in ("trace", "ref"):
            req = side / f"{kind}.request"
            if not req.exists():
                continue
            try:
                body = json.loads(req.read_text())
                req.unlink()
                if kind == "trace":
                    result = _trace(side / "trace", float(body["seconds"]))
                else:
                    from chipbench.reference.check import score_request

                    result = score_request(core_out[0], cfg, body)
            except Exception:  # noqa: BLE001 — reported to the parent, which fails the run
                result = {"error": traceback.format_exc()[-2000:]}
            tmp = side / f"{kind}.tmp"
            tmp.write_text(json.dumps(result))
            tmp.rename(side / f"{kind}.done")


def _trace(out_dir: Path, seconds: float) -> dict:
    import jax

    jax.profiler.start_trace(str(out_dir))
    t0 = time.time()
    try:
        time.sleep(seconds)
    finally:
        t1 = time.time()
        jax.profiler.stop_trace()   # writes the trace: seconds, not traced
    return {"started_unix": t0, "stopped_unix": t1}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True, help="name under chipbench/configs")
    ap.add_argument("--model-name", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--side-dir", required=True)
    args = ap.parse_args()

    from chipbench.configs import engine_overrides, load_config, model_fields

    cfg = load_config(args.config)

    from dynamo_tpu.device import enable_compile_cache
    from dynamo_tpu.engine import PRESETS, ModelConfig

    fields = model_fields(cfg)
    PRESETS[args.config] = lambda: ModelConfig(**fields)
    cache_dir = enable_compile_cache()

    from dynamo_tpu.backends.jax.main import run_jax_worker
    from dynamo_tpu.runtime import DistributedRuntime, dynamo_worker

    side = Path(args.side_dir)
    side.mkdir(parents=True, exist_ok=True)
    core_out: list = []
    stop = threading.Event()
    # The engine seeds jax.random.PRNGKey, which takes 32 bits; the
    # driver's seeds are larger.
    seed = args.seed % (2 ** 31 - 1)

    @dynamo_worker()
    async def entry(runtime: DistributedRuntime) -> None:
        log.info("persistent compile cache: %s", cache_dir)
        thread = threading.Thread(
            target=_serve_side_requests, args=(side, core_out, cfg, stop),
            name="chipbench-side", daemon=True)
        thread.start()
        try:
            await run_jax_worker(
                runtime, model_name=args.model_name, preset=args.config,
                engine_overrides=engine_overrides(cfg), seed=seed,
                quant=cfg["serve"].get("quant"), core_out=core_out,
                warm_up=True,
            )
        finally:
            stop.set()
            thread.join(10)

    entry()


if __name__ == "__main__":
    main()
