"""SLA profiler: sweep the real engine on one chip and emit the planner's
performance profile.

The offline half of the reference's SLA planning flow
(`/root/reference/benchmarks/profiler/profile_sla.py:52` +
`utils/profile_prefill.py`/`profile_decode.py`): measure

- prefill: TTFT vs input sequence length (one request at a time), and
- decode: inter-token latency vs concurrency at fixed context,

then write exactly the dict `planner.perf_interpolation.from_profile`
loads, so `Planner` plans from measured numbers instead of fixtures.

Usage:
    python benchmarks/profile_sla.py --preset llama3-1b --out profile.json
    python benchmarks/profile_sla.py --preset tiny --quick   # CPU smoke
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _drain_one(core, seq):
    """Run until `seq` finishes; returns (ttft_s, per-token itl list)."""
    t0 = time.perf_counter()
    first = None
    stamps: list[tuple[float, int]] = []
    while seq.finish is None:
        for s, out in core.step():
            if s is seq and out.token_ids:
                now = time.perf_counter()
                if first is None:
                    first = now - t0
                stamps.append((now - t0, len(out.token_ids)))
    return first, stamps


def profile_prefill(make_core, isl_grid: list[int], reps: int = 2) -> dict:
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    core = make_core(max(isl_grid))
    rng = np.random.RandomState(0)
    vocab = core.cfg.vocab_size
    ttfts: list[float] = []
    for i, isl in enumerate(isl_grid):
        best = float("inf")
        for r in range(reps + 1):  # first rep warms the bucket's compile
            seq = core.add_request(
                PreprocessedRequest(
                    model="profile",
                    token_ids=rng.randint(1, vocab, size=isl).tolist(),
                    request_id=f"pf-{isl}-{r}",
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=1, ignore_eos=True),
                )
            )
            ttft, _ = _drain_one(core, seq)
            if r > 0:
                best = min(best, ttft)
        ttfts.append(round(best, 5))
    return {"isl": list(map(float, isl_grid)), "ttft_s": ttfts}


def profile_decode(
    make_core, concurrency_grid: list[int], ctx: int = 128, osl: int = 32
) -> dict:
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    rng = np.random.RandomState(1)
    itls: list[float] = []
    for conc in concurrency_grid:
        core = make_core(ctx, batch=conc)
        vocab = core.cfg.vocab_size

        def req(i, n_out):
            return PreprocessedRequest(
                model="profile",
                token_ids=rng.randint(1, vocab, size=ctx).tolist(),
                request_id=f"dc-{conc}-{i}",
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=n_out, ignore_eos=True),
            )

        # Warm the compile path (megastep = resolved fused-decode length).
        w = core.add_request(req("w", core.engine.megastep))
        _drain_one(core, w)

        seqs = [core.add_request(req(i, osl)) for i in range(conc)]
        first: dict[str, float] = {}
        last: dict[str, float] = {}
        counts: dict[str, int] = {}
        done = 0
        t0 = time.perf_counter()
        while done < len(seqs):
            for s, out in core.step():
                now = time.perf_counter() - t0
                rid = s.request_id
                first.setdefault(rid, now)
                last[rid] = now
                counts[rid] = counts.get(rid, 0) + len(out.token_ids)
                if out.finish_reason:
                    done += 1
        per_tok = [
            (last[r] - first[r]) / (counts[r] - 1)
            for r in first
            if counts[r] > 1
        ]
        itls.append(round(float(np.median(per_tok)), 5))
        del core
    return {"concurrency": list(map(float, concurrency_grid)), "itl_s": itls}


def main() -> None:
    ap = argparse.ArgumentParser(description="dynamo-tpu SLA profiler")
    ap.add_argument("--preset", default="llama3-1b")
    ap.add_argument("--out", default="profile.json")
    ap.add_argument("--quick", action="store_true", help="small grids (CI/CPU)")
    ap.add_argument("--isl-grid", type=int, nargs="*", default=None)
    ap.add_argument("--concurrency-grid", type=int, nargs="*", default=None)
    args = ap.parse_args()

    from dynamo_tpu.engine.config import PRESETS, EngineConfig
    from dynamo_tpu.engine.core import EngineCore

    cfg = PRESETS[args.preset]()
    tiny = cfg.hidden_size <= 256
    if args.quick or tiny:
        isl_grid = args.isl_grid or [16, 32, 64]
        conc_grid = args.concurrency_grid or [1, 4]
        ctx, osl = 32, 8
    else:
        isl_grid = args.isl_grid or [128, 512, 2048]
        conc_grid = args.concurrency_grid or [1, 8, 32, 64]
        ctx, osl = 128, 32

    def make_core(max_len: int, batch: int = 8) -> EngineCore:
        bs = 8 if tiny else 32
        bucket = max(64, 1 << (max_len - 1).bit_length())
        blocks = max(64, (batch + 2) * -(-(max_len + osl) // bs))
        eng = EngineConfig(
            num_kv_blocks=blocks,
            block_size=bs,
            max_num_seqs=max(batch, 8),
            max_model_len=bucket + 2 * osl + bs,
            prefill_buckets=(bucket,),
            prefill_batch=min(16, max(batch, 8)),
            decode_buckets=(max(batch, 8),),
            megastep_k=min(32, osl),
        )
        return EngineCore(cfg, eng, seed=0)

    profile = {
        "meta": {"preset": args.preset, "ctx": ctx, "osl": osl},
        "prefill": profile_prefill(make_core, isl_grid),
        "decode": profile_decode(make_core, conc_grid, ctx=ctx, osl=osl),
    }
    with open(args.out, "w") as f:
        json.dump(profile, f, indent=1)
    print(json.dumps(profile))


if __name__ == "__main__":
    main()
