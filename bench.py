"""Serving benchmark on the flagship single-chip model — north-star
metrics per BASELINE.md: tokens/sec/chip + p50 TTFT/TPOT per config.

Drives EngineCore (the real jitted engine: bucketed ragged prefill,
batched paged-attention decode chains with fused sampling) through
synthetic workloads shaped after the reference's harness
(`/root/reference/benchmarks/llm/perf.sh:18-27`: ISL/OSL presets and a
concurrency sweep scaled to one chip).

Prints one JSON line per secondary config, then the PRIMARY line last
(the driver records the final line):

    {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., "configs": [...]}

``vs_baseline`` is measured throughput over an HBM-bandwidth roofline for
the decode phase (decode is bandwidth-bound: every step streams the full
weights plus the batch's live KV), so 1.0 means saturating the chip's
memory system — the honest ceiling for autoregressive decode. The peak
comes from ``dynamo_tpu.device.DEVICE_PEAKS`` for the ``device_kind`` JAX
reports; ``main()`` refuses to run without a TPU, names the device in
every line, and exits non-zero when any phase failed.

Engine shapes (one 2048-token prefill bucket packing a whole admission
wave, decode chains of up to 128 steps) were chosen against an earlier
runtime's per-dispatch cost and have not been re-tuned; cells, traces and
the ledger are ROADMAP S0's.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field

import numpy as np

QUICK = bool(os.environ.get("BENCH_QUICK"))

# Per-dispatch host-overhead profiles for the mocker A/Bs (microseconds
# of base_iter_us): a priced 58 ms dispatch — the figure the fusion
# features were first argued against — and the mocker's default 0.5 ms.
SLOW_DISPATCH = "dispatch-58ms"
DISPATCH_COST_PROFILES_US = {SLOW_DISPATCH: 58000.0, "dispatch-0.5ms": 500.0}


@dataclass
class Config:
    name: str
    batch: int
    isl: int
    osl: int
    engine_kw: dict = field(default_factory=dict)
    primary: bool = False
    model: str | None = None   # preset override (default: flagship 1b)
    quant: bool = False        # int8 weight-only quantization
    pp: int = 1                # pipeline-parallel stages (needs pp devices)
    # Measured repetitions: the headline (value / vs_baseline) is the
    # MEDIAN of N reps — an honest order statistic; *_best fields carry
    # best-of-N alongside. The run-to-run spread on today's machine is
    # not measured yet (ROADMAP S0d).
    reps: int = 3


CONFIGS = [
    # PRIMARY — the north-star model size (BASELINE.md: tokens/sec/chip +
    # TTFT/TPOT at 8B): llama3-8b served on ONE 16 GB chip via int8
    # weight-only quantization (bf16 params alone are 16.06 GB).
    Config("8b-int8", batch=16, isl=128, osl=64, model="llama3-8b", quant=True,
           engine_kw=dict(num_kv_blocks=256, prefill_batch=16),
           primary=True, reps=2),
    # Flagship-1b saturation throughput (reference perf.sh shape scaled
    # to one chip; round 1-3 comparison config).
    Config("saturated", batch=32, isl=128, osl=128),
    # Same shape, int8: max absolute tokens/sec (6.05 vs 7.35 ms/step
    # bf16, PERF.md).
    Config("saturated-int8", batch=32, isl=128, osl=128, quant=True),
    # Low-concurrency latency.
    Config("low-conc", batch=8, isl=128, osl=128),
    # Long-prefill, TTFT-heavy (reference default ISL is 3000).
    Config("long-prefill", batch=8, isl=2048, osl=64,
           engine_kw=dict(max_model_len=4096, num_kv_blocks=1024)),
    # Scheduling A/B vs "saturated": same shape through the chunked
    # token-budget scheduler (mixed prefill+decode steps). Compare TTFT
    # p50/p99 + queue_wait against the waves twin above.
    Config("saturated-chunked", batch=32, isl=128, osl=128,
           engine_kw=dict(scheduling="chunked", prefill_chunk=128,
                          max_num_batched_tokens=512,
                          prefill_buckets=(128, 256, 512))),
    # Scheduling A/B vs "long-prefill": 2048-token prompts streamed in
    # 512-token chunks instead of monopolizing whole waves.
    Config("long-prefill-chunked", batch=8, isl=2048, osl=64,
           engine_kw=dict(max_model_len=4096, num_kv_blocks=1024,
                          scheduling="chunked", prefill_chunk=512,
                          max_num_batched_tokens=2048,
                          prefill_buckets=(512, 1024, 2048))),
    # Megastep A/B on the real engine (ISSUE 7): same decode-heavy shape,
    # one dispatch per token (k=1) vs 8 fused iterations per dispatch.
    # run_config's default decode_chain=min(128, osl) already fuses, so
    # the k=1 twin is the one that surfaces the raw per-dispatch
    # overhead; compare TPOT p50 + dispatches/token.
    Config("1b-megastep-k1", batch=16, isl=128, osl=64,
           engine_kw=dict(megastep_k=1)),
    Config("1b-megastep-k8", batch=16, isl=128, osl=64,
           engine_kw=dict(megastep_k=8)),
    # Quantized-KV A/B on the REAL engine (ISSUE 8): the primary shape
    # with int8 KV pages at DOUBLED blocks + batch (the halved page
    # frees the HBM) vs the bf16-KV primary above. Compare decode tok/s
    # + TPOT; the CPU-runnable capacity/virtual-clock A/B is
    # run_kvquant_ab.
    Config("8b-int8-kvint8", batch=32, isl=128, osl=64, model="llama3-8b",
           quant=True,
           engine_kw=dict(num_kv_blocks=512, prefill_batch=16,
                          kv_dtype="int8"),
           reps=2),
    # 70B-class pp composition (ISSUE 20) — the second half of the
    # BASELINE.md metric (tokens/sec/chip + TTFT/TPOT at 8B **and 70B**):
    # int8 weights + int8 KV pages sharded over a 4-stage pipe with
    # FUSED pp megasteps (the decode chain wavefronts inside one device
    # program; stage hops ride lax.ppermute in the scan). This is the
    # named real-engine path; one chip cannot host it (70B-int8 needs
    # ~4x 16 GB stages), so the CI-runnable numbers come from the
    # mocker-profiled run_pp_megastep_ab below, which are mocker
    # virtual-clock figures, not measurements.
    Config("llama3-70b-int8-kvint8-pp", batch=16, isl=128, osl=64,
           model="llama3-70b", quant=True, pp=4,
           engine_kw=dict(num_kv_blocks=512, prefill_batch=16,
                          kv_dtype="int8", megastep_k=8),
           reps=2),
]


def run_config(cfg_model, c: Config, hbm_gbps: float) -> dict:
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    kw = dict(
        num_kv_blocks=768,
        block_size=32,
        max_num_seqs=c.batch,
        max_model_len=512,
        prefill_buckets=(2048,),
        prefill_batch=16,
        decode_buckets=(c.batch,),
        decode_chain=min(128, c.osl),
    )
    kw.update(c.engine_kw)
    kw["prefill_buckets"] = tuple(
        b for b in kw["prefill_buckets"] if b <= kw["max_model_len"]
    ) or (kw["max_model_len"],)
    eng = EngineConfig(**kw)
    params = None
    if c.quant:
        import jax

        from dynamo_tpu.engine.model import init_params_quantized

        params = init_params_quantized(jax.random.PRNGKey(0), cfg_model)
    mesh_kw = {}
    if c.pp > 1:
        from dynamo_tpu.parallel.pipeline import make_pp_mesh

        mesh_kw["pp_mesh"] = make_pp_mesh(c.pp)
    core = EngineCore(cfg_model, eng, params=params, seed=0, **mesh_kw)
    rng = np.random.RandomState(0)

    def req(i: int, n_out: int) -> PreprocessedRequest:
        return PreprocessedRequest(
            model="bench",
            token_ids=rng.randint(1, cfg_model.vocab_size, size=c.isl).tolist(),
            request_id=f"bench-{i}",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=n_out, ignore_eos=True),
        )

    def drain(n_expected: int):
        """Run to completion; per-request first/last token timestamps."""
        finished = 0
        tokens = 0
        first: dict[str, float] = {}
        last: dict[str, float] = {}
        counts: dict[str, int] = {}
        t0 = time.perf_counter()
        while finished < n_expected:
            for seq, out in core.step():
                now = time.perf_counter()
                tokens += len(out.token_ids)
                rid = seq.request_id
                counts[rid] = counts.get(rid, 0) + len(out.token_ids)
                first.setdefault(rid, now - t0)
                last[rid] = now - t0
                if out.finish_reason:
                    finished += 1
        elapsed = time.perf_counter() - t0
        tpots = [
            (last[r] - first[r]) / (counts[r] - 1) for r in first if counts[r] > 1
        ]
        return tokens, elapsed, first, tpots

    # Warmup: compile the prefill bucket + decode megastep programs
    # (eng.megastep = resolved --megastep-k, falling back to decode_chain).
    core.add_request(req(99990, eng.megastep))
    core.add_request(req(99991, eng.megastep))
    drain(2)

    # Queue-wait attribution (admit -> first chunk dispatched) comes from
    # the engine's sched_admit stat spans; filter by wall-clock so warmup
    # and other configs' spans are excluded.
    from dynamo_tpu import tracing

    collector = tracing.get_collector()
    t_reps_start = time.time()

    # Decode roofline: per step, weights + live KV of the batch stream
    # from HBM. Mean context during decode = ISL + OSL/2.
    kv_bytes_per_tok = (
        cfg_model.num_layers * cfg_model.num_kv_heads * cfg_model.head_dim * 2 * 2
    )
    mean_ctx = c.isl + c.osl / 2
    pbytes = (
        cfg_model.quantized_param_bytes() if c.quant else cfg_model.param_bytes()
    )
    step_bytes = pbytes + c.batch * mean_ctx * kv_bytes_per_tok
    roofline = c.batch / (step_bytes / (hbm_gbps * 1e9))

    reps = []
    for rep in range(max(1, c.reps)):
        for i in range(c.batch):
            core.add_request(req(rep * 100000 + i, c.osl))
        tokens, elapsed, first, tpots = drain(c.batch)
        # vs_baseline compares the DECODE phase against the decode
        # roofline (the roofline models decode HBM traffic only): decode
        # window = end of the last prefill (every request's first token
        # is prefill-sampled) to the last token.
        decode_time = max(elapsed - max(first.values()), 1e-9)
        decode_tok_s = (tokens - len(first)) / decode_time
        ttfts = sorted(first.values())
        tp = sorted(tpots)
        reps.append({
            "value": tokens / elapsed,
            "decode_tok_s": decode_tok_s,
            "vs_baseline": decode_tok_s / roofline,
            "ttft_p50": ttfts[len(ttfts) // 2],
            "ttft_p99": ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))],
            "tpot_p50": tp[len(tp) // 2] if tp else None,
            "tpot_p99": tp[min(len(tp) - 1, int(0.99 * len(tp)))] if tp else None,
        })
    queue_waits = sorted(
        s.duration_s for s in collector.stats()
        if s.name == "sched_admit" and s.start_s >= t_reps_start
    )
    del core

    # Median rep (by end-to-end throughput; lower-middle for even N so
    # the headline never benefits from the rounding) + best rep.
    ordered = sorted(reps, key=lambda r: r["value"])
    med = ordered[(len(ordered) - 1) // 2]
    best = ordered[-1]
    return {
        "metric": (
            f"{cfg_model.name}{'-int8' if c.quant else ''} agg tokens/sec/chip "
            f"({c.name}: B={c.batch}, {c.isl}/{c.osl})"
        ),
        "value": round(med["value"], 1),
        "unit": "tokens/sec (median of %d reps; *_best = best rep)" % len(reps),
        "vs_baseline": round(med["vs_baseline"], 4),
        "value_best": round(best["value"], 1),
        "vs_baseline_best": round(best["vs_baseline"], 4),
        "decode_tok_s": round(med["decode_tok_s"], 1),
        "decode_tok_s_best": round(best["decode_tok_s"], 1),
        "ttft_p50_ms": round(med["ttft_p50"] * 1e3, 1),
        "ttft_p99_ms": round(med["ttft_p99"] * 1e3, 1),
        "tpot_p50_ms": (
            round(med["tpot_p50"] * 1e3, 2) if med["tpot_p50"] is not None else None
        ),
        "tpot_p99_ms": (
            round(med["tpot_p99"] * 1e3, 2) if med["tpot_p99"] is not None else None
        ),
        # Queue-wait attribution: admit -> first prefill chunk dispatched,
        # sourced from the scheduler's sched_admit spans (all reps pooled).
        # Under waves this is the "arrivals queue behind whole waves"
        # component of TTFT; chunked scheduling attacks exactly this term.
        "queue_wait_ms": (
            {
                "p50": round(queue_waits[len(queue_waits) // 2] * 1e3, 1),
                "p99": round(
                    queue_waits[min(len(queue_waits) - 1,
                                    int(0.99 * len(queue_waits)))] * 1e3, 1,
                ),
                "n": len(queue_waits),
            }
            if queue_waits else None
        ),
        # Metric derivation, per config: vs_baseline = decode_tok_s /
        # roofline_tok_s, where roofline = B / (weights + live-KV bytes
        # per step / the device's published HBM bandwidth).
        "derivation": {
            "roofline_tok_s": round(roofline, 1),
            "step_gb": round(step_bytes / 1e9, 3),
            "param_gb": round(pbytes / 1e9, 3),
            "kv_gb_per_step": round(c.batch * mean_ctx * kv_bytes_per_tok / 1e9, 3),
            "hbm_gbps": hbm_gbps,
            "decode_window": "last prefill-sampled token -> last token",
        },
    }


def run_disagg_ab(model) -> dict:
    """Aggregated-vs-disaggregated A/B sharing the one chip: a prefill
    core and a decode core move KV via the v2 descriptor transfer,
    mirroring the P/D worker flow in backends/jax/main.py. Reports TTFT,
    total-latency ratio (median AND best of N reps), a per-phase
    breakdown (prefill/export/wire/import/decode), and the device-direct
    transfer variant (import_blocks_direct — the within-slice ICI path).

    STEADY-STATE by construction: every device program in the timed
    windows (both prefill buckets, the decode chain, the transfer
    gather/scatter at full transfer width) is compiled and warmed with a
    DISTINCT prompt before timing starts — jit compiles are excluded and
    each rep uses fresh prompt content so no rep rides the prefix cache.
    (BASELINE.md disagg A/B; reference architecture.md:75 says disagg
    should be FASTER — parity on one shared chip is the honest target,
    since both sides of this A/B contend for the same MXU.)"""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    ISL, OSL = 2048, 8
    REPS = 3
    # The small prefill bucket keeps the decode core's 1-token
    # continuation prefill (64 cached blocks + 1 token) off the full
    # 2048-token program.
    kw = dict(
        num_kv_blocks=768, block_size=32, max_num_seqs=8, max_model_len=4096,
        prefill_buckets=(128, 2048), prefill_batch=8, decode_buckets=(8,),
        decode_chain=8,
    )
    rng = np.random.RandomState(0)

    def fresh_prompt():
        return rng.randint(1, model.vocab_size, size=ISL).tolist()

    def req(tokens, rid, n_out, hold=False):
        return PreprocessedRequest(
            model="bench", token_ids=list(tokens), request_id=rid,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=n_out, ignore_eos=True),
            kv_transfer_params={"do_remote_decode": True} if hold else None,
        )

    def run_until_done(core, seq):
        toks, first_t = [], None
        t0 = time.perf_counter()
        while seq.finish is None:
            for s, out in core.step():
                if s is seq:
                    if first_t is None:
                        first_t = time.perf_counter() - t0
                    toks.extend(out.token_ids)
        return toks, first_t, time.perf_counter() - t0

    # Aggregated baseline core (warm both buckets + the decode chain).
    agg = EngineCore(model, EngineConfig(**kw), seed=0)
    warm = agg.add_request(req(fresh_prompt()[:64], "w", 8))
    run_until_done(agg, warm)
    w2 = agg.add_request(req(fresh_prompt(), "w2", 8))
    run_until_done(agg, w2)

    # Disagg cores. Warm the full transfer path on a distinct prompt:
    # held 2048-token prefill, descriptor export, the chunked gathers and
    # import scatters at the exact widths the timed reps replay, and the
    # device-direct copy program.
    CHUNK = 16
    p_core = EngineCore(model, EngineConfig(**kw), seed=0)
    d_core = EngineCore(model, EngineConfig(**kw), seed=0, params=p_core.params)
    for core in (p_core, d_core):
        w = core.add_request(req(fresh_prompt()[:64], "w", 8))
        run_until_done(core, w)
    pw = p_core.add_request(req(fresh_prompt(), "wxfer", 1, hold=True))
    run_until_done(p_core, pw)
    descs = p_core.export_descriptors("wxfer")
    for s in range(0, len(descs), CHUNK):
        pages = p_core.read_held_pages("wxfer", s, CHUNK)
        d_core.import_blocks(
            [dict(descs[s + j], kv=kv) for j, kv in enumerate(pages)]
        )
    p_core.release_held("wxfer")
    pw2 = p_core.add_request(req(fresh_prompt(), "wdirect", 1, hold=True))
    run_until_done(p_core, pw2)
    d_core.import_blocks_direct(p_core, "wdirect")
    p_core.release_held("wdirect")

    def wire_transfer(rid: str, descs: list[dict]) -> int:
        """Pipelined host-staged transfer: a producer thread stages
        chunks out of the prefill cache while the main thread imports
        the previous chunk into the decode cache (the worker flow's
        stream, backends/jax/main.py kv_transfer, runs the same
        producer/consumer shape across the data plane). Returns bytes
        moved one way."""
        import queue as _queue
        import threading as _threading

        q: _queue.Queue = _queue.Queue(maxsize=2)
        failure: list[BaseException] = []

        def producer():
            try:
                for s in range(0, len(descs), CHUNK):
                    q.put((s, p_core.read_held_pages(rid, s, CHUNK)))
            except BaseException as e:  # noqa: BLE001 — re-raised by consumer
                failure.append(e)
            finally:
                q.put(None)

        t = _threading.Thread(target=producer, daemon=True)
        t.start()
        moved = 0
        while (item := q.get()) is not None:
            s, pages = item
            moved += sum(len(p) for p in pages)
            d_core.import_blocks(
                [dict(descs[s + j], kv=kv) for j, kv in enumerate(pages)]
            )
        t.join()
        if failure:
            # A truncated transfer must not masquerade as a fast one.
            raise failure[0]
        # Land the uploads now so the phase attribution is honest (the
        # scatter's device work is otherwise lazily paid by decode).
        import jax as _jax

        _jax.block_until_ready(d_core.cache)
        return moved

    wire_ratios, direct_ratios, phase_rows = [], [], []
    ttft_aggs, ttft_disaggs = [], []
    wire_bytes = wire_secs = 0.0
    for rep in range(REPS):
        # Device-direct path FIRST (this is the primary: the within-slice
        # ICI analogue of NIXL's device-to-device RDMA — the reference
        # transfer never stages through host memory either).
        prompt = fresh_prompt()
        seq = agg.add_request(req(prompt, f"agg{rep}", OSL))
        agg_toks, agg_ttft, agg_total = run_until_done(agg, seq)
        ttft_aggs.append(agg_ttft)

        t0 = time.perf_counter()
        rid = f"pfd{rep}"
        pseq = p_core.add_request(req(prompt, rid, 1, hold=True))
        tok1, ttft_d, _ = run_until_done(p_core, pseq)
        d_core.import_blocks_direct(p_core, rid)
        p_core.release_held(rid)
        dseq = d_core.add_request(req(prompt + tok1, f"decd{rep}", OSL - 1))
        d_toks, _, _ = run_until_done(d_core, dseq)
        direct_total = time.perf_counter() - t0
        assert tok1 + d_toks == agg_toks, "disagg output diverged from aggregated"
        direct_ratios.append(direct_total / agg_total)
        ttft_disaggs.append(ttft_d)

        # Host-staged wire path (the cross-host DCN flow; fresh prompt so
        # it cannot ride the direct rep's cache).
        prompt2 = fresh_prompt()
        seq = agg.add_request(req(prompt2, f"agg2{rep}", OSL))
        agg_toks2, _, agg_total2 = run_until_done(agg, seq)
        t0 = time.perf_counter()
        rid = f"pf{rep}"
        pseq = p_core.add_request(req(prompt2, rid, 1, hold=True))
        tok1, _, _ = run_until_done(p_core, pseq)
        t1 = time.perf_counter()
        descs = p_core.export_descriptors(rid)
        t2 = time.perf_counter()
        moved = wire_transfer(rid, descs)
        p_core.release_held(rid)
        t3 = time.perf_counter()
        dseq = d_core.add_request(req(prompt2 + tok1, f"dec{rep}", OSL - 1))
        d_toks, _, _ = run_until_done(d_core, dseq)
        t4 = time.perf_counter()
        assert tok1 + d_toks == agg_toks2, "wire disagg diverged from aggregated"
        wire_ratios.append((t4 - t0) / agg_total2)
        wire_bytes += moved
        wire_secs += t3 - t2
        phase_rows.append({
            "prefill": t1 - t0, "export": t2 - t1, "transfer": t3 - t2,
            "decode": t4 - t3,
        })

    assert d_core.transfer_stats["dropped_blocks"] == 0, (
        "transfer dropped blocks: %s" % d_core.transfer_stats
    )
    del p_core, d_core, agg

    wire_ratios.sort()
    direct_ratios.sort()
    med = direct_ratios[len(direct_ratios) // 2]
    med_phases = {
        k: round(
            sorted(r[k] for r in phase_rows)[len(phase_rows) // 2] * 1e3, 1
        )
        for k in phase_rows[0]
    }
    ttft_agg = sorted(ttft_aggs)[len(ttft_aggs) // 2]
    ttft_d = sorted(ttft_disaggs)[len(ttft_disaggs) // 2]
    return {
        "metric": f"{model.name} disagg-vs-agg total latency ratio ({ISL}/{OSL})",
        "value": round(med, 3),
        "unit": "x (1.0 = parity; median of %d steady-state reps, "
                "device-direct transfer)" % REPS,
        "vs_baseline": round(1.0 / med, 4),
        "direct_ratio_best": round(direct_ratios[0], 3),
        "wire_ratio_median": round(wire_ratios[len(wire_ratios) // 2], 3),
        "wire_phases_ms": med_phases,
        "wire_mb_per_s": round(wire_bytes / max(wire_secs, 1e-9) / 1e6, 1),
        "ttft_agg_ms": round(ttft_agg * 1e3, 1),
        "ttft_disagg_ms": round(ttft_d * 1e3, 1),
        "ttft_ratio": round(ttft_d / ttft_agg, 3),
        "note": (
            "steady-state: prefill/decode/transfer programs warmed on "
            "distinct prompts before timing (compiles excluded). Primary = "
            "device-direct (one-program cache-to-cache copy; the NIXL "
            "device-to-device analogue for co-located P/D). wire_* = the "
            "host-staged DCN path, pipelined producer/consumer, bounded "
            "by this machine's host<->device rate (wire_mb_per_s)"
        ),
    }


def run_overload_ab() -> dict:
    """Overload robustness A/B on the mocker's VIRTUAL clock (ISSUE 10):
    two tenants, a 4x burst, fairness (per-tenant DRR admission) on vs
    off. A heavy tenant floods 40 short-completion requests at t=0 with
    a 30 ms deadline each; a light tenant arrives steadily. Reported per
    scenario: the light tenant's TTFT p50/p99 (vs its unloaded run),
    SLO attainment (light TTFT within 2x unloaded p99), goodput
    (client-visible tokens per virtual second), and the typed shed rate
    (deadline expirations — every one a clean error frame, never a
    partial stream). ASSERTED, not just reported: fairness holds the
    light tenant's TTFT p99 within 2x of unloaded while FIFO does not,
    and zero broken streams in every scenario (the seed of ROADMAP item
    3's mocker fleet harness)."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    HEAVY_N, LIGHT_N = 40, 8
    HEAVY_ISL, LIGHT_ISL = 32, 32
    HEAVY_OSL, LIGHT_OSL = 1, 4
    HEAVY_DEADLINE_S = 0.030
    LIGHT_STEP_S = 0.02

    def seq(rid, isl, osl, tenant, fill, deadline=None):
        prompt = [fill] * isl
        s = _Seq(
            request_id=rid, prompt=prompt, max_tokens=osl,
            out=asyncio.Queue(),
            seq=TokenBlockSequence(prompt, 8),
            prompt_hashes=compute_seq_hashes(prompt, 8),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
            tenant_id=tenant,
        )
        s.deadline_epoch = deadline
        return s

    def run(fair: bool, heavy_n: int) -> dict:
        args = MockEngineArgs(
            num_kv_blocks=4096, block_size=8, max_num_seqs=2,
            max_num_batched_tokens=128, enable_prefix_caching=False,
            fair_scheduling=fair, fair_quantum=32,
        )
        eng = MockTpuEngine(args)
        vt_box = [0.0]
        eng.clock = lambda: vt_box[0]  # deadlines on the virtual clock
        heavy = [
            seq(f"h{i}", HEAVY_ISL, HEAVY_OSL, "heavy", 1 + (i % 7),
                deadline=HEAVY_DEADLINE_S)
            for i in range(heavy_n)
        ]
        light = [
            seq(f"l{i}", LIGHT_ISL, LIGHT_OSL, "light", 9)
            for i in range(LIGHT_N)
        ]
        pending = [(LIGHT_STEP_S * i, s) for i, s in enumerate(light)]
        for s in heavy:
            eng._waiting.append(s)
        submit_vt = {s.request_id: 0.0 for s in heavy}
        live = list(heavy)
        first: dict[str, float] = {}
        frames: dict[str, list] = {s.request_id: [] for s in heavy + light}
        while vt_box[0] < 120.0 and (
            pending
            or any(s in eng._waiting or s in eng._running for s in live)
        ):
            while pending and pending[0][0] <= vt_box[0]:
                t, s = pending.pop(0)
                submit_vt[s.request_id] = vt_box[0]
                eng._waiting.append(s)
                live.append(s)
            eng._admit()
            p, d = eng._step()
            vt_box[0] += (
                args.base_iter_us
                + p * args.prefill_us_per_token
                + d * args.decode_us_per_seq
            ) / 1e6
            for s in live:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    frames[s.request_id].append(item)
                    if item.get("token_ids"):
                        first.setdefault(s.request_id, vt_box[0])
        # Zero-broken-streams audit: every request either completed its
        # full budget or received EXACTLY one typed shed frame with no
        # tokens before or after.
        completed = shed = broken = tokens_out = 0
        for s in live:
            fr = frames[s.request_id]
            toks = sum(len(f.get("token_ids", [])) for f in fr)
            finishes = [f.get("finish_reason") for f in fr if f.get("finish_reason")]
            if finishes and finishes[-1] == "error":
                ok = (
                    toks == 0
                    and len([f for f in fr if f.get("finish_reason")]) == 1
                    and fr[-1].get("meta", {}).get("shed") == "deadline"
                )
                shed += 1
                broken += 0 if ok else 1
            elif finishes and toks == s.max_tokens:
                completed += 1
                tokens_out += toks
            else:
                broken += 1
        ttfts = sorted(
            first[s.request_id] - submit_vt[s.request_id]
            for s in light
            if s.request_id in first
        )
        assert len(ttfts) == LIGHT_N, "light tenant requests lost"
        return {
            "light_ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1e3, 2),
            "light_ttft_p99_ms": round(ttfts[-1] * 1e3, 2),
            "completed": completed,
            "shed_typed": shed,
            "broken_streams": broken,
            "shed_rate": round(shed / len(live), 3),
            "goodput_tok_s": round(tokens_out / max(vt_box[0], 1e-9), 1),
        }

    unloaded = run(fair=False, heavy_n=0)
    fifo = run(fair=False, heavy_n=HEAVY_N)
    fair = run(fair=True, heavy_n=HEAVY_N)
    slo_ms = 2.0 * unloaded["light_ttft_p99_ms"]
    rows = [
        dict(unloaded, config="light-only (unloaded)"),
        dict(fifo, config="burst+fifo"),
        dict(fair, config="burst+fair-drr"),
    ]
    for r in rows:
        r["slo_ok"] = r["light_ttft_p99_ms"] <= slo_ms
    assert fair["light_ttft_p99_ms"] <= slo_ms, (
        f"fair DRR missed the SLO: light p99 {fair['light_ttft_p99_ms']} ms "
        f"vs bound {slo_ms} ms"
    )
    assert fifo["light_ttft_p99_ms"] > slo_ms, (
        "FIFO unexpectedly held the SLO — the burst is not saturating"
    )
    assert all(r["broken_streams"] == 0 for r in rows), rows
    return {
        "metric": (
            f"mocker overload A/B: light-tenant TTFT p99 under a "
            f"{HEAVY_N}-request heavy burst (2 slots; virtual clock)"
        ),
        "value": round(
            fair["light_ttft_p99_ms"] / fifo["light_ttft_p99_ms"], 4
        ),
        "unit": "x fair-vs-fifo light p99 (lower is better)",
        "vs_baseline": round(
            fifo["light_ttft_p99_ms"] / fair["light_ttft_p99_ms"], 2
        ),
        "slo_bound_ms": slo_ms,
        "rows": rows,
        "note": (
            "heavy tenant: 40 short-completion requests at t=0 with a "
            "30 ms deadline (expired-in-queue requests shed with ONE "
            "typed error frame — audited per stream); light tenant: 8 "
            "steady arrivals. fair-drr holds light p99 within 2x "
            "unloaded (asserted); FIFO does not (asserted); zero broken "
            "streams in every scenario (asserted)"
        ),
    }


def run_peer_pool_ab() -> dict:
    """Cluster KV pool A/B on the mocker's VIRTUAL clock (ISSUE 11): a
    multi-worker fleet serving a shared-system-prompt workload, peer
    pull on vs off. One worker prefills the 2048-token shared prefix
    cold; every OTHER worker's first request either recomputes it (no
    pool) or imports the 64 shared blocks from the peer at the priced
    dataplane cost (kv_pull_us_per_block x the int8 byte ratio — the
    packed buffer IS the wire format) and prefills only its unique tail.
    Reported: cross-worker TTFT (first shared-prefix request on a
    not-yet-warm worker) pool vs cold, the pull cost itself, and a
    bit-identical stream audit. ASSERTED: pooled cross-worker TTFT is
    < 0.5x cold prefill — the 'most prefill becomes a network copy'
    claim at the heart of ROADMAP item 1."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    WORKERS = 4
    BS = 32
    SHARED_TOKENS = 2048          # 64 shared-prefix blocks
    TAIL_TOKENS = 32
    OSL = 8
    PULL_US_PER_BLOCK = 60.0      # dataplane copy cost per bf16 block

    def mk_engine() -> MockTpuEngine:
        return MockTpuEngine(
            MockEngineArgs(
                num_kv_blocks=4096, block_size=BS, max_num_seqs=4,
                max_num_batched_tokens=8192,
                kv_dtype="int8",           # pulls move the packed buffer
                kv_pull_us_per_block=PULL_US_PER_BLOCK,
            )
        )

    shared = [7] * SHARED_TOKENS

    def mk_seq(rid: str, tail_fill: int) -> _Seq:
        prompt = shared + [tail_fill] * TAIL_TOKENS
        return _Seq(
            request_id=rid, prompt=prompt, max_tokens=OSL,
            out=asyncio.Queue(),
            seq=TokenBlockSequence(prompt, BS),
            prompt_hashes=compute_seq_hashes(prompt, BS),
            stop=StopConditions(max_tokens=OSL, ignore_eos=True),
        )

    def serve_one(eng: MockTpuEngine, seq: _Seq) -> tuple[float, list, float]:
        """Drive the engine's admit/step loop on a virtual clock until the
        request finishes; returns (TTFT, stream frames, total vt)."""
        args = eng.args
        vt = 0.0
        ttft = None
        frames: list = []
        eng._waiting.append(seq)
        for _ in range(10_000):
            eng._admit()
            p, d = eng._step()
            vt += (
                args.base_iter_us
                + p * args.prefill_us_per_token
                + d * args.decode_us_per_seq
            ) / 1e6
            done = False
            while not seq.out.empty():
                item = seq.out.get_nowait()
                if not isinstance(item, dict):
                    done = True
                    continue
                frames.append(item)
                if ttft is None and item.get("token_ids"):
                    ttft = vt
                if item.get("finish_reason"):
                    done = True
            if done:
                break
        assert ttft is not None, f"request {seq.request_id} never produced a token"
        return ttft, frames, vt

    shared_hashes = compute_seq_hashes(shared, BS)
    parents = [shared_hashes[i - 1] if i else None for i in range(len(shared_hashes))]

    def run(pool: bool) -> dict:
        # Worker 0 always prefills the shared prefix cold (someone must);
        # workers 1..W-1 are the cross-worker cohort under measurement.
        engines = [mk_engine() for _ in range(WORKERS)]
        seed_ttft, seed_frames, _ = serve_one(engines[0], mk_seq("seed", 101))
        ttfts: list[float] = []
        pull_cost = 0.0
        streams: list = []
        for w in range(1, WORKERS):
            eng = engines[w]
            vt_pull = 0.0
            if pool:
                imported, cost_s = eng.import_peer_blocks(shared_hashes, parents)
                assert imported == len(shared_hashes), "pool import fell short"
                eng.peer_stats.pulls_attempted += 1
                eng.peer_stats.pulls_succeeded += 1
                vt_pull = cost_s
                pull_cost = cost_s
            ttft, frames, _ = serve_one(eng, mk_seq(f"x{w}", 101))
            ttfts.append(vt_pull + ttft)
            streams.append([t for f in frames for t in f.get("token_ids", [])])
        return {
            "seed_ttft_ms": round(seed_ttft * 1e3, 3),
            "xworker_ttft_ms_mean": round(sum(ttfts) / len(ttfts) * 1e3, 3),
            "xworker_ttft_ms_max": round(max(ttfts) * 1e3, 3),
            "pull_cost_ms": round(pull_cost * 1e3, 3),
            "streams": streams,
            "seed_stream": [
                t for f in seed_frames for t in f.get("token_ids", [])
            ],
        }

    cold = run(pool=False)
    pooled = run(pool=True)
    # Bit-identical audit: the pool changes WHERE the prefix comes from,
    # never which tokens stream.
    assert pooled["streams"] == cold["streams"], "peer pull changed a stream"
    assert pooled["seed_stream"] == cold["seed_stream"]
    ratio = pooled["xworker_ttft_ms_mean"] / cold["xworker_ttft_ms_mean"]
    assert ratio < 0.5, (
        f"cluster pool missed the bar: cross-worker TTFT with pool is "
        f"{ratio:.3f}x cold prefill (bound 0.5x)"
    )
    for r in (cold, pooled):
        r.pop("streams")
        r.pop("seed_stream")
    return {
        "metric": (
            f"mocker cluster-KV-pool A/B: cross-worker shared-prefix TTFT "
            f"({WORKERS}-worker fleet, {SHARED_TOKENS}-token shared prompt, "
            f"virtual clock)"
        ),
        "value": round(ratio, 4),
        "unit": "x pool-vs-cold cross-worker TTFT (lower is better)",
        "vs_baseline": round(1.0 / ratio, 2),
        "rows": [
            dict(cold, config="cold (no pool: every worker re-prefills)"),
            dict(pooled, config="pool (peer pull at "
                                f"{PULL_US_PER_BLOCK}us/block x int8 ratio)"),
        ],
        "note": (
            "shared 2048-token system prompt (64 blocks), 32-token unique "
            "tails; worker 0 seeds cold, workers 1..3 either recompute the "
            "shared prefix or import it from the peer at the priced "
            "dataplane cost (int8 packed buffer, ~0.52x bf16 bytes). "
            "Streams audited bit-identical pool vs cold; ratio asserted "
            "< 0.5x — cross-worker prefill became a network copy"
        ),
    }


def run_fleet_obs_ab() -> dict:
    """Fleet-observability overhead A/B on the mocker's VIRTUAL clock
    (ISSUE 13): the identical B=16 decode workload with metric-snapshot
    publishing OFF vs ON — the ON arm runs the REAL pipeline (snapshot
    publisher -> store wire -> fleet aggregator -> SLO attribution)
    interleaved with the step loop. The publish path is an asyncio task
    reading host stats dicts, so it adds ZERO priced step work: streams
    are bit-identical and the virtual-clock TPOT ratio is asserted
    <= 1.02 (the < 2% acceptance bar — met by construction, verified by
    measurement). The wall-clock cost of one snapshot build+publish is
    reported alongside so the host-side price is visible too. The rows
    grow per-tenant SLO-ATTAINMENT columns sourced from the aggregator's
    stitched budget breakdown — the embryo of the ROADMAP item 2 fleet
    benchmark."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.obs.aggregator import FleetAggregator
    from dynamo_tpu.obs.slo import SloTargets
    from dynamo_tpu.obs.snapshot import SnapshotPublisher
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    B, ISL, OSL = 16, 128, 64
    PUBLISH_EVERY = 32  # iterations between snapshot ticks in the ON arm

    async def run(publish: bool) -> dict:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
                tenant_id="gold" if j % 2 else "bronze",
            )
            seqs.append(s)
            eng._waiting.append(s)

        store = rt = agg_rt = agg = pub = None
        finished_records: list[dict] = []

        def drain_records() -> list[dict]:
            out = list(finished_records)
            finished_records.clear()
            return out

        if publish:
            store = StoreServer()
            await store.start()
            rt = await DistributedRuntime.create(store.address)
            agg_rt = await DistributedRuntime.create(store.address)
            agg = FleetAggregator(
                agg_rt.store, namespace="bench-obs", stale_after_s=600.0,
                slo_targets=SloTargets(ttft_s=0.2, tpot_s=0.05),
            )
            await agg.start()
            # interval_s is irrelevant here: the drive loop ticks the
            # publisher manually so snapshot cadence is deterministic in
            # ITERATIONS, not wall time.
            pub = SnapshotPublisher(
                rt.store, "bench-obs", worker_id=1, component="backend",
                interval_s=3600.0,
            )
            pub.collectors = {
                "scheduler": eng.scheduler_stats,
                "spec": eng.spec_decode_stats,
                "kv_cache": eng.kv_cache_stats,
            }
            pub.tenant_source = eng.fair_queue_stats
            pub.request_source = drain_records
        vt = 0.0
        it = 0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        streams: dict[str, list] = {s.request_id: [] for s in seqs}
        done: set[str] = set()
        t_wall0 = time.perf_counter()
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()
            vt += eng.iter_time_s(p, d)
            it += 1
            for s in seqs:
                rid = s.request_id
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    toks = item.get("token_ids", [])
                    streams[rid].extend(toks)
                    if toks:
                        if rid in first:
                            gaps.extend([(vt - prev[rid]) / len(toks)] * len(toks))
                        first.setdefault(rid, vt)
                        prev[rid] = vt
                    if item.get("finish_reason") and rid not in done:
                        done.add(rid)
                        # Worker-side SLO record on VIRTUAL timestamps
                        # (everything submitted at vt=0): the same shape
                        # PhaseScanner emits from live trace spans.
                        finished_records.append({
                            "rid": rid, "tenant": s.tenant_id,
                            "t": vt, "tokens": len(streams[rid]),
                            "phases": {
                                "sched_admit": 0.0,
                                "prefill": first.get(rid, vt),
                                "decode": prev.get(rid, vt) - first.get(rid, vt),
                            },
                        })
            if publish and it % PUBLISH_EVERY == 0:
                pub.publish_nowait()
                for _ in range(4):  # let drain + aggregator ingest run
                    await asyncio.sleep(0)
        wall_s = time.perf_counter() - t_wall0
        gaps.sort()
        out = {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 4),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 4
            ),
            "ttft_mean_ms": round(sum(first.values()) / len(first) * 1e3, 3),
            "iterations": it,
            "wall_s": round(wall_s, 3),
            "streams": streams,
        }
        if publish:
            # Final tick carries the last finished-request records, then
            # the wall-clock price of ONE build+publish, measured on the
            # real wire.
            pub.publish_nowait()
            assert await pub.flush(10.0), "snapshot publisher failed to flush"
            t0 = time.perf_counter()
            pub.publish_nowait()
            assert await pub.flush(10.0)
            out["snapshot_publish_us"] = round(
                (time.perf_counter() - t0) * 1e6, 1
            )
            for _ in range(200):
                if 1 in agg.latest and agg.latest[1].seq >= pub._seq:
                    break
                await asyncio.sleep(0.01)
            assert 1 in agg.latest, "aggregator never saw the worker"
            assert pub.snapshots_published_total >= 2
            assert pub.snapshots_dropped_total == 0
            agg.slo.sweep(time.monotonic() + 60.0)  # finalize worker-only
            slo = agg.slo.summary()
            assert set(slo["tenants"]) == {"gold", "bronze"}, slo
            out["snapshots_published"] = pub.snapshots_published_total
            # The SLO-attainment columns: per-tenant attainment + tails
            # from the aggregator's stitched budget breakdown.
            out["slo"] = {
                t: {
                    "requests": row["requests"],
                    "ttft_p50_ms": row["ttft_p50_ms"],
                    "ttft_p99_ms": row["ttft_p99_ms"],
                    "tpot_p50_ms": row["tpot_p50_ms"],
                    "tpot_p99_ms": row["tpot_p99_ms"],
                    "ttft_attainment": row["ttft_attainment"],
                    "tpot_attainment": row["tpot_attainment"],
                }
                for t, row in slo["tenants"].items()
            }
            await pub.stop()
            await agg.stop()
            await rt.shutdown()
            await agg_rt.shutdown()
            await store.stop()
        return out

    off = asyncio.run(run(publish=False))
    on = asyncio.run(run(publish=True))
    # Bit-identical streams: publishing changes what is OBSERVED, never
    # what streams.
    assert on.pop("streams") == off.pop("streams"), (
        "snapshot publishing changed a token stream"
    )
    ratio = on["tpot_p50_ms"] / off["tpot_p50_ms"]
    assert ratio <= 1.02, (
        f"publishing cost {ratio:.4f}x TPOT on the virtual clock (bar "
        f"1.02x): priced step work leaked into the publish path"
    )
    slo = on.pop("slo")
    rows = [
        dict(off, config="obs-off"),
        dict(on, config=f"obs-on (snapshot every {PUBLISH_EVERY} iters, "
                        "real store wire + aggregator + SLO attribution)"),
    ]
    return {
        "metric": (
            f"mocker fleet-observability A/B decode TPOT p50 ratio "
            f"(B={B}, {ISL}/{OSL}, snapshot publishing on vs off, "
            f"virtual clock)"
        ),
        "value": round(ratio, 4),
        "unit": "x vs obs-off (1.0 = publishing adds zero priced step work)",
        "vs_baseline": round(1.0 / ratio, 4),
        "rows": rows,
        "slo_attainment": slo,
        "note": (
            "ON arm runs the real pipeline: SnapshotPublisher -> store "
            "pub/sub -> FleetAggregator -> SLO attribution, interleaved "
            "with the step loop. Streams bit-identical on vs off "
            "(asserted), TPOT ratio <= 1.02 (asserted; the publish path "
            "is an asyncio task reading host stats dicts — no host "
            "sync, no step-lock hold, nothing on plan/dispatch). "
            "snapshot_publish_us is the measured wall cost of one "
            "build+publish on the wire. slo_attainment columns come "
            "from the aggregator's stitched per-request TTFT/TPOT "
            "budget breakdown — the embryo of the ROADMAP item 2 "
            "fleet benchmark"
        ),
    }


def run_fleet_ab() -> dict:
    """THE fleet-scale headline (ISSUE 14, ROADMAP item 2): closed-loop
    SLA autoscaling + network-aware routing, proven on the mocker fleet
    harness at a virtual "millions of users" scale.

    Part 1 — autoscaling: a 3-tenant diurnal workload (4x peak/trough
    swing, 60 s agent bursts, ~130k-user populations, shared prefixes)
    over 1.5 diurnal periods. The planner run goes first and discovers
    its own capacity trajectory; the static baseline then gets the
    planner's MEAN replica count — the equal-budget comparison. ASSERTED:
    planner-on holds TTFT attainment >= 0.95 where the same budget held
    static falls below 0.8, zero broken streams either way, and the
    budgets really are within 15%.

    Part 2 — network-aware routing: a fixed 4-worker fleet where one
    peer is slow (25 ms/block pulls), 3x-slower hardware, and loaded
    with 6 rps of out-of-band traffic — yet holds the hottest shared
    prefix. ASSERTED: measured-cost routing shifts decode placement AND
    peer-prefix pulls off the bad peer (>= 4x fewer of each), cohort
    TTFT p99 beats overlap-only, and streams are byte-identical with
    routing-aware on or off."""
    from dynamo_tpu.fleet.harness import run_fleet_ab as fleet_ab
    from dynamo_tpu.fleet.harness import run_routing_ab

    ab = fleet_ab(duration_s=360.0, seed=0)
    planner, static = ab["planner"], ab["static"]
    budget = ab["static_budget_replicas"]
    assert planner.broken_streams == 0 and static.broken_streams == 0, (
        planner.broken_streams,
        static.broken_streams,
    )
    assert planner.attainment_ttft >= 0.95, (
        f"planner-on missed the bar: TTFT attainment "
        f"{planner.attainment_ttft} < 0.95"
    )
    assert static.attainment_ttft < 0.8, (
        f"static baseline unexpectedly held: TTFT attainment "
        f"{static.attainment_ttft} >= 0.8 at {budget} replicas — the "
        f"diurnal swing is not saturating"
    )
    assert planner.mean_replicas <= budget * 1.15, (
        f"budgets diverged: planner mean {planner.mean_replicas} vs "
        f"static {budget} — not an equal-budget comparison"
    )

    rt = run_routing_ab()
    base, aware = rt["overlap_only"], rt["network_aware"]
    assert aware.streams == base.streams, (
        "network-aware routing changed a stream"
    )
    slow = 0
    assert aware.placements.get(slow, 0) * 4 <= base.placements.get(slow, 1), (
        f"placement did not shift: {base.placements} -> {aware.placements}"
    )
    assert aware.pulls_by_source.get(slow, 0) * 4 <= base.pulls_by_source.get(
        slow, 1
    ), f"pulls did not shift: {base.pulls_by_source} -> {aware.pulls_by_source}"
    assert aware.ttft_p99_ms < base.ttft_p99_ms, (
        base.ttft_p99_ms,
        aware.ttft_p99_ms,
    )

    def row(rep, config):
        d = rep.summary()
        d.pop("decisions", None)
        d.pop("placements", None)
        d.pop("pulls_by_source", None)
        d["config"] = config
        return d

    return {
        "metric": (
            "mocker fleet A/B: TTFT SLO attainment under a 4x diurnal "
            "multi-tenant swing, closed-loop planner vs equal-budget "
            "static pool (virtual clock)"
        ),
        "value": planner.attainment_ttft,
        "unit": "TTFT attainment, planner-on (static equal-budget below)",
        "vs_baseline": round(
            planner.attainment_ttft / max(static.attainment_ttft, 1e-9), 2
        ),
        "static_budget_replicas": budget,
        "rows": [
            row(planner, f"planner-on (mean {planner.mean_replicas} replicas, "
                         f"peak {planner.peak_replicas})"),
            row(static, f"static pool ({budget} replicas, equal budget)"),
        ],
        "planner_decisions": planner.decisions,
        "routing_ab": {
            "slow_peer_placements": {
                "overlap_only": base.placements.get(slow, 0),
                "network_aware": aware.placements.get(slow, 0),
            },
            "slow_peer_pull_blocks": {
                "overlap_only": base.pulls_by_source.get(slow, 0),
                "network_aware": aware.pulls_by_source.get(slow, 0),
            },
            "cohort_ttft_p99_ms": {
                "overlap_only": base.ttft_p99_ms,
                "network_aware": aware.ttft_p99_ms,
            },
            "ttft_p99_ratio": round(
                aware.ttft_p99_ms / max(base.ttft_p99_ms, 1e-9), 4
            ),
            "streams_bit_identical": True,
        },
        "note": (
            "autoscaling: 3 tenants (diurnal consumer+enterprise, bursty "
            "agents), ~13k requests over 360 virtual s, 1.5 diurnal "
            "periods; planner run first, static frozen at the planner's "
            "mean replicas (equal budget, asserted within 15%). Planner "
            "holds attainment >= 0.95 via AR-rate planning + "
            "backlog-proportional reactive pressure + hysteresis; "
            "scale-down is always a graceful drain (zero broken streams "
            "asserted both arms). routing_ab: one slow (25 ms/block), "
            "3x-slower, 6 rps-loaded peer holding the hottest prefix — "
            "measured per-peer cost (PeerPullStats EWMA -> "
            "ForwardPassMetrics.net) + reported queue depth shift "
            "placement and pulls >= 4x off it (asserted) and cut cohort "
            "TTFT p99 (asserted); streams byte-identical aware on/off "
            "(asserted)"
        ),
    }


def run_spec_ab() -> dict:
    """Speculative-decoding A/B on the mocker's VIRTUAL clock (ISSUE 4):
    spec off vs n-gram verify at swept acceptance rates, decode-heavy
    workload (B=16, 128/64). Deterministic — the mocker's cost model
    prices draft tokens like prefill tokens, so the numbers carry the
    verify overhead, not just the win. Columns: measured acceptance rate,
    TPOT p50/p99, decode-window tokens/sec, and the TPOT-p50 ratio vs
    spec off. The REAL engine's verify path shares the scheduler and the
    ragged assembler with these steps; its parity is pinned by
    tests/test_spec_decode.py, while this A/B pins the TIMING claim
    (TPOT improves at acceptance >= 0.5)."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    B, ISL, OSL, K = 16, 128, 64, 4

    def run(rate: float | None) -> dict:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
            **(
                dict(spec_decode="ngram", spec_k=K, spec_acceptance_rate=rate)
                if rate is not None
                else {}
            ),
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            s.spec_k = K if rate is not None else 0
            seqs.append(s)
            eng._waiting.append(s)
        vt = 0.0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()
            vt += (
                args.base_iter_us
                + p * args.prefill_us_per_token
                + d * args.decode_us_per_seq
            ) / 1e6
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    n = len(item.get("token_ids", []))
                    if not n:
                        continue
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / n] * n)
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        decode_s = vt - max(first.values())
        st = eng.spec_decode_stats()
        return {
            "target_acceptance": rate,
            "acceptance_rate": round(st["acceptance_rate"], 3),
            "mean_accepted_len": round(st["mean_accepted_len"], 2),
            "wasted_tokens": st["wasted_tokens"],
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 3
            ),
            "decode_tok_s": round(B * (OSL - 1) / max(decode_s, 1e-9), 1),
        }

    off = run(None)
    rows = [dict(off, config="spec-off")]
    for rate in (0.5, 0.7, 0.9):
        r = run(rate)
        r["config"] = f"spec-ngram@{rate}"
        r["tpot_p50_vs_off"] = round(r["tpot_p50_ms"] / off["tpot_p50_ms"], 3)
        rows.append(r)
    best = min(rows[1:], key=lambda r: r["tpot_p50_ms"])
    return {
        "metric": (
            f"mocker spec-decode A/B decode TPOT p50 ratio "
            f"(B={B}, {ISL}/{OSL}, k={K}, virtual clock)"
        ),
        "value": best["tpot_p50_vs_off"],
        "unit": "x vs spec-off (lower is better; deterministic mocker clock)",
        "vs_baseline": round(1.0 / best["tpot_p50_vs_off"], 4),
        "rows": rows,
        "note": (
            "acceptance-rate sweep; draft tokens priced like prefill "
            "tokens so ratios include verify overhead. Real-engine "
            "output parity (greedy + seeded sampling) is pinned by "
            "tests/test_spec_decode.py"
        ),
    }


def run_device_draft_ab() -> dict:
    """On-device n-gram drafting A/B on the mocker's VIRTUAL clock
    (ISSUE 18): host-drafted speculation vs device-resident ring
    drafting at EQUAL spec_k, under the universal megastep. The host
    drafter pays one dispatch per draft->verify->accept round; the
    device drafter runs up to megastep_k-1 rounds BETWEEN inner
    iterations of one dispatch, so the per-dispatch overhead amortizes
    over every round. Two cost profiles (a priced 58 ms dispatch
    overhead and 0.5 ms) x acceptance {0.5, 0.9}; device
    draft rounds are priced on the clock (DYN_SPEC_DRAFT_ROUND_US) and
    drafted tokens like prefill tokens, so ratios carry the drafting
    cost, not just the win. Streams are asserted bit-identical across
    spec-off / host-draft / device-draft inside every cell; the REAL
    engine's parity matrix is pinned by tests/test_spec_decode.py."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    B, ISL, OSL, K, MEGA = 16, 128, 64, 4, 8
    PROFILES = DISPATCH_COST_PROFILES_US

    def run(base_us: float, rate: float | None,
            device: bool) -> tuple[dict, dict]:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
            base_iter_us=base_us, megastep_k=MEGA,
            **(
                dict(spec_decode="ngram", spec_k=K,
                     spec_acceptance_rate=rate, spec_device_draft=device)
                if rate is not None
                else {}
            ),
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            s.spec_k = K if rate is not None else 0
            s.spec_device = device if rate is not None else False
            seqs.append(s)
            eng._waiting.append(s)
        vt = 0.0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
        dispatches = 0
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()
            dispatches += 1
            vt += eng.iter_time_s(
                p, d, eng._last_kv_blocks_read, eng._last_device_rounds
            )
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    toks = item.get("token_ids", [])
                    if not toks:
                        continue
                    streams[s.request_id].extend(toks)
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / len(toks)] * len(toks))
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        decode_s = vt - max(first.values())
        st = eng.spec_decode_stats()
        return {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 3
            ),
            "decode_tok_s": round(B * (OSL - 1) / max(decode_s, 1e-9), 1),
            "acceptance_rate": round(st["acceptance_rate"], 3),
            "device_rounds": st["device_rounds"],
            "device_hits": st["device_hits"],
            "dispatches_per_accepted_token": round(
                st["dispatches_per_accepted_token"], 4
            ),
            "dispatches": dispatches,
        }, streams

    rows = []
    headline = None
    for profile, base_us in PROFILES.items():
        base_row, base_streams = run(base_us, None, False)
        rows.append(dict(base_row, config=f"{profile}-spec-off"))
        for rate in (0.5, 0.9):
            host_row, host_streams = run(base_us, rate, False)
            dev_row, dev_streams = run(base_us, rate, True)
            assert host_streams == base_streams, (
                f"{profile}@{rate}: host-draft stream diverged from spec-off"
            )
            assert dev_streams == base_streams, (
                f"{profile}@{rate}: device-draft stream diverged from spec-off"
            )
            ratio = round(dev_row["tpot_p50_ms"] / host_row["tpot_p50_ms"], 3)
            rows.append(dict(host_row, config=f"{profile}-host@{rate}"))
            rows.append(dict(dev_row, config=f"{profile}-device@{rate}",
                             tpot_p50_vs_host=ratio))
            if profile == SLOW_DISPATCH and rate == 0.9:
                headline = ratio
    return {
        "metric": (
            f"mocker on-device-draft A/B decode TPOT p50 ratio "
            f"({SLOW_DISPATCH} profile, acceptance 0.9, B={B}, {ISL}/{OSL}, "
            f"k={K}, megastep_k={MEGA}, device vs host drafting, "
            "virtual clock)"
        ),
        "value": headline,
        "unit": "x vs host-drafted spec (lower is better; deterministic "
                "mocker clock)",
        "vs_baseline": round(1.0 / headline, 4),
        "rows": rows,
        "note": (
            "device drafting runs up to megastep_k-1 draft->verify->"
            "accept rounds inside ONE dispatch (ring match priced at "
            "DYN_SPEC_DRAFT_ROUND_US per round, drafted tokens like "
            "prefill tokens); the host drafter pays a dispatch per "
            "round. Streams asserted bit-identical spec-off/host/device "
            "in every cell; real-engine bit-identity pinned by "
            "tests/test_spec_decode.py"
        ),
    }


def run_async_ab() -> dict:
    """Async pipelined-execution A/B on the mocker's VIRTUAL clock
    (ISSUE 5): async-exec off vs on across decode batch widths, with
    host-gap columns. The mocker's cost model splits each iteration into
    fixed per-dispatch HOST overhead (base_iter_us — plan assembly,
    sampled-token fetch, bookkeeping, detokenization) and DEVICE compute;
    the one-step-ahead loop overlaps them (iteration = max instead of
    sum), so TPOT improves most where the fixed overhead dominates —
    small decode batches — and the uncovered host gap drops to
    max(0, host - device). Token streams are bit-identical on vs off;
    the REAL engine's plan/dispatch/commit split shares this contract,
    pinned by tests/test_async_exec.py."""
    import asyncio

    from dynamo_tpu import tracing
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    ISL, OSL = 128, 64
    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()

    def run(async_exec: bool, B: int) -> dict:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
            async_exec=async_exec,
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            seqs.append(s)
            eng._waiting.append(s)
        vt = 0.0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        t_run_start = time.time()
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()
            vt += eng.iter_time_s(p, d)
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    n = len(item.get("token_ids", []))
                    if not n:
                        continue
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / n] * n)
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        # Host-gap column sourced from the SAME host_gap stat spans the
        # engine records (iter_time_s) — no re-derived twin of the
        # overlap model that could silently diverge from it.
        host_gaps = sorted(
            s.duration_s for s in collector.stats()
            if s.name == "host_gap" and s.start_s >= t_run_start
        ) or [0.0]
        decode_s = vt - max(first.values())
        return {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 3
            ),
            "host_gap_p50_ms": round(
                host_gaps[len(host_gaps) // 2] * 1e3, 3
            ),
            "decode_tok_s": round(B * (OSL - 1) / max(decode_s, 1e-9), 1),
        }

    rows = []
    headline = None
    for B in (4, 16, 64):
        off = run(False, B)
        on = run(True, B)
        ratio = round(on["tpot_p50_ms"] / off["tpot_p50_ms"], 3)
        rows.append({
            "config": f"B={B}",
            "off": off,
            "on": on,
            "tpot_p50_on_vs_off": ratio,
        })
        if B == 4:
            headline = ratio
    return {
        "metric": (
            f"mocker async-exec A/B decode TPOT p50 ratio "
            f"(B=4, {ISL}/{OSL}, virtual clock; sweep B=4/16/64)"
        ),
        "value": headline,
        "unit": "x vs async-off (lower is better; deterministic mocker clock)",
        "vs_baseline": round(1.0 / headline, 4),
        "rows": rows,
        "note": (
            "host_gap_p50_ms = per-dispatch host overhead the device "
            "waits on (async-off: the full base_iter_us; async-on: the "
            "remainder after overlapping with device compute). Real-"
            "engine parity + pipelining invariants are pinned by "
            "tests/test_async_exec.py"
        ),
    }


def run_megastep_ab() -> dict:
    """Decode-megastep A/B on the mocker's VIRTUAL clock (ISSUE 7): TPOT
    vs k ∈ {1, 4, 8, 16} fused decode iterations per dispatch, decode-
    heavy workload (B=16, 128/64). Two cost profiles: "dispatch-58ms"
    prices the fixed per-dispatch host overhead at 58 ms (the regime the
    megastep was first argued for; device decode is ~0.1
    ms/lane-iteration), "dispatch-0.5ms" keeps the mocker's default
    overhead as a low-overhead sanity check. One megastep pays
    the overhead once per k device iterations, so TPOT approaches
    (host/k + device)/1 — the ratio column is the amortization. Streams
    are asserted bit-identical across k inside the run; the REAL
    engine's parity is pinned by tests/test_megastep.py."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    B, ISL, OSL = 16, 128, 64
    PROFILES = DISPATCH_COST_PROFILES_US

    def run(base_us: float, k: int) -> tuple[dict, dict]:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
            base_iter_us=base_us, megastep_k=k,
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            seqs.append(s)
            eng._waiting.append(s)
        vt = 0.0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()  # d = decode LANE-ITERATIONS (k per lane)
            vt += (
                args.base_iter_us
                + p * args.prefill_us_per_token
                + d * args.decode_us_per_seq
            ) / 1e6
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    toks = item.get("token_ids", [])
                    if not toks:
                        continue
                    streams[s.request_id].extend(toks)
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / len(toks)] * len(toks))
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        decode_s = vt - max(first.values())
        st = eng.scheduler_stats()
        return {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 3
            ),
            "decode_tok_s": round(B * (OSL - 1) / max(decode_s, 1e-9), 1),
            "dispatches_per_token": round(st["dispatches_per_token"], 4),
            "megastep_dispatches": st["megastep_dispatches"],
        }, streams

    rows = []
    headline = None
    for profile, base_us in PROFILES.items():
        base_row, base_streams = run(base_us, 1)
        rows.append(dict(base_row, config=f"{profile}-k1", tpot_p50_vs_k1=1.0))
        for k in (4, 8, 16):
            r, streams = run(base_us, k)
            assert streams == base_streams, (
                f"megastep k={k} stream diverged from k=1"
            )
            r["config"] = f"{profile}-k{k}"
            r["tpot_p50_vs_k1"] = round(
                r["tpot_p50_ms"] / base_row["tpot_p50_ms"], 3
            )
            rows.append(r)
            if profile == SLOW_DISPATCH and k == 8:
                headline = r["tpot_p50_vs_k1"]
    return {
        "metric": (
            f"mocker megastep A/B decode TPOT p50 ratio "
            f"({SLOW_DISPATCH} cost profile, B={B}, {ISL}/{OSL}, k=8 vs 1, "
            "virtual clock; sweep k=1/4/8/16 x 58 ms / 0.5 ms dispatch)"
        ),
        "value": headline,
        "unit": "x vs k=1 (lower is better; deterministic mocker clock)",
        "vs_baseline": round(1.0 / headline, 4),
        "rows": rows,
        "note": (
            "the slow profile prices the dispatch overhead at 58 ms; one "
            "megastep pays it once per k device "
            "iterations. Streams asserted bit-identical across k; "
            "real-engine parity (greedy + seeded + logprobs, EOS inside "
            "a megastep, async composition) pinned by "
            "tests/test_megastep.py"
        ),
    }


def run_megastep_mixed_ab() -> dict:
    """UNIVERSAL-megastep A/B under MIXED traffic (ISSUE 12), on the
    mocker's VIRTUAL clock: chunked scheduling + spec decode with
    staggered arrivals, so prefill chunks, decode rows, and verify rows
    share iterations — the production shape the decode-only
    run_megastep_ab cannot see (its fusion rate overstates mixed
    traffic, where the first cut forced k=1). k ∈ {1, 8} across the
    58 ms and 0.5 ms dispatch-cost profiles. With the carve-outs lifted, EVERY iteration with
    decode work fuses: verify rows resolve accept/reject inside the
    priced dispatch and emit (1 + accepted) + (k - 1) tokens, prefill
    chunks ride along — one base_iter_us per k-ish tokens per lane
    instead of per verify row. Streams asserted bit-identical across k;
    the 58 ms ratio is the ISSUE 12 acceptance bar (<= 0.5x). The REAL
    engine's fused parity (greedy + seeded + logprobs, chunked + waves,
    async, rejection rollback) is pinned by tests/test_megastep.py."""
    import asyncio

    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    B, ISL, OSL = 16, 256, 64
    PROFILES = DISPATCH_COST_PROFILES_US

    def run(base_us: float, k: int) -> tuple[dict, dict]:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
            scheduling="chunked", prefill_chunk=64,
            base_iter_us=base_us, megastep_k=k,
            spec_decode="ngram", spec_k=4, spec_acceptance_rate=0.6,
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            s.spec_k = args.spec_k
            seqs.append(s)
        # Staggered arrivals: 4 lanes seed the batch, one more every 2
        # iterations — the 256-token prompts chunk at 64 tokens, so
        # late arrivals' prefill chunks share iterations with earlier
        # lanes' fused decode/verify rows for most of the run (the
        # mixed-traffic regime the A/B exists to price).
        arrivals = {j: 0 if j < 4 else (j - 3) * 2 for j in range(B)}
        vt = 0.0
        it = 0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
        pending = list(seqs)
        while pending or any(
            s in eng._running or s in eng._waiting for s in seqs
        ):
            while pending and arrivals[int(pending[0].request_id[1:])] <= it:
                eng._waiting.append(pending.pop(0))
            eng._admit()
            p, d = eng._step()  # d = decode LANE-ITERATIONS (k per lane)
            it += 1
            vt += (
                args.base_iter_us
                + p * args.prefill_us_per_token
                + d * args.decode_us_per_seq
            ) / 1e6
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    toks = item.get("token_ids", [])
                    if not toks:
                        continue
                    streams[s.request_id].extend(toks)
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / len(toks)] * len(toks))
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        st = eng.scheduler_stats()
        sp = eng.spec_decode_stats()
        return {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 3
            ),
            "dispatches_per_token": round(st["dispatches_per_token"], 4),
            "megastep_dispatches": st["megastep_dispatches"],
            "fused_mixed_dispatches": st["fused_mixed_dispatches"],
            "mixed_steps": st["mixed_steps"],
            "spec_acceptance": round(sp["acceptance_rate"], 3),
        }, streams

    rows = []
    headline = None
    for profile, base_us in PROFILES.items():
        base_row, base_streams = run(base_us, 1)
        rows.append(dict(base_row, config=f"{profile}-k1", tpot_p50_vs_k1=1.0))
        r, streams = run(base_us, 8)
        assert streams == base_streams, (
            f"mixed megastep k=8 stream diverged from k=1 ({profile})"
        )
        assert r["fused_mixed_dispatches"] > 0, (
            "mixed traffic produced no fused dispatches — the ISSUE 12 "
            "carve-out lift is not engaged"
        )
        assert base_row["fused_mixed_dispatches"] == 0
        r["config"] = f"{profile}-k8"
        r["tpot_p50_vs_k1"] = round(
            r["tpot_p50_ms"] / base_row["tpot_p50_ms"], 3
        )
        rows.append(r)
        if profile == SLOW_DISPATCH:
            headline = r["tpot_p50_vs_k1"]
            assert headline <= 0.5, (
                f"mixed-traffic megastep missed the acceptance bar: "
                f"{headline} > 0.5x vs k=1"
            )
    return {
        "metric": (
            f"mocker UNIVERSAL-megastep mixed-traffic A/B decode TPOT p50 "
            f"ratio ({SLOW_DISPATCH} profile, chunked + spec, staggered arrivals, "
            f"B={B}, {ISL}/{OSL}, k=8 vs 1, virtual clock)"
        ),
        "value": headline,
        "unit": "x vs k=1 (lower is better; deterministic mocker clock)",
        "vs_baseline": round(1.0 / headline, 4),
        "rows": rows,
        "note": (
            "ISSUE 12: chunked + spec traffic where the first cut forced "
            "k=1 — verify rows now resolve accept/reject inside the fused "
            "dispatch ((1 + accepted) + (k - 1) tokens per lane per "
            "base_iter_us) and prefill chunks ride the same priced "
            "iteration. Streams asserted bit-identical across k; "
            "real-engine fused parity (greedy + seeded + logprobs, "
            "chunked + waves, async composition, on-device rejection "
            "rollback) pinned by tests/test_megastep.py; decode-only "
            "numbers tracked separately by run_megastep_ab"
        ),
    }


def run_pp_megastep_ab() -> dict:
    """Fused pp megastep A/B (ISSUE 20) on the mocker's VIRTUAL clock:
    decode TPOT with pp=4 stages, k=8 fused wavefront iterations per
    dispatch vs the host-rollback pp baseline (k=1 — every token pays
    its own dispatch overhead AND its own fill/drain bubble). Stage
    traffic is priced at DYN_PP_HOP_US per ppermute hop: a dispatch
    fusing k iterations crosses k*pp + pp-1 stage boundaries (k
    wavefront rounds over pp microbatch groups plus the bubble), so the
    fused program pays the bubble + base_iter_us once per k tokens
    instead of per token. Profiles as in run_megastep_ab: a 58 ms and a
    0.5 ms dispatch overhead. Acceptance bar (ISSUE 20): at 58 ms,
    pp=4 k=8 TPOT p50 <= 0.5x the k=1
    pp baseline. Streams are asserted bit-identical across pp on/off AND
    fused on/off in the same run; the REAL engine's pp parity (greedy +
    seeded, waves + chunked, async, EOS mid-megastep, block pressure) is
    pinned by tests/test_pp_megastep.py. These are mocker-profiled
    numbers — the real-engine 70B path is the llama3-70b-int8-kvint8-pp
    CONFIG, which needs a 4-stage TPU pipe."""
    import asyncio

    from dynamo_tpu import knobs
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    B, ISL, OSL = 16, 128, 64
    PROFILES = DISPATCH_COST_PROFILES_US
    hop_us = knobs.get_float("DYN_PP_HOP_US")

    def run(base_us: float, pp: int, k: int) -> tuple[dict, dict]:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=2048, enable_prefix_caching=False,
            base_iter_us=base_us, megastep_k=k, pp=pp,
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            seqs.append(s)
            eng._waiting.append(s)
        vt = 0.0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()  # d = decode LANE-ITERATIONS (k per lane)
            vt += (
                args.base_iter_us
                + p * args.prefill_us_per_token
                + d * args.decode_us_per_seq
                + eng._last_pp_rounds * hop_us
            ) / 1e6
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    toks = item.get("token_ids", [])
                    if not toks:
                        continue
                    streams[s.request_id].extend(toks)
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / len(toks)] * len(toks))
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        decode_s = vt - max(first.values())
        st = eng.scheduler_stats()
        return {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "tpot_p99_ms": round(
                gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))] * 1e3, 3
            ),
            "decode_tok_s": round(B * (OSL - 1) / max(decode_s, 1e-9), 1),
            "dispatches_per_token": round(st["dispatches_per_token"], 4),
            "pp_fused_dispatches": st["pp_fused_dispatches"],
            "pp_forced_single": st["pp_forced_single"],
            "pp_pipe_occupancy": round(st["pp_pipe_occupancy"], 4),
        }, streams

    rows = []
    headline = None
    for profile, base_us in PROFILES.items():
        # pp=1 twins first: fused on/off without a pipe — the reference
        # stream every pp variant must match bit-for-bit.
        ref_row, ref_streams = run(base_us, 1, 1)
        rows.append(dict(ref_row, config=f"{profile}-pp1-k1"))
        r_fused1, s_fused1 = run(base_us, 1, 8)
        assert s_fused1 == ref_streams, "pp=1 fused stream diverged"
        rows.append(dict(r_fused1, config=f"{profile}-pp1-k8"))
        # Host-rollback pp baseline: every token pays dispatch + bubble.
        base_row, base_streams = run(base_us, 4, 1)
        assert base_streams == ref_streams, (
            "pp=4 k=1 stream diverged from pp=1"
        )
        assert base_row["pp_forced_single"] > 0
        rows.append(dict(base_row, config=f"{profile}-pp4-k1",
                         tpot_p50_vs_k1=1.0))
        # Fused pp megasteps: k wavefront iterations per priced dispatch.
        r, streams = run(base_us, 4, 8)
        assert streams == ref_streams, (
            "fused pp megastep stream diverged from pp=1"
        )
        assert r["pp_fused_dispatches"] > 0 and r["pp_forced_single"] == 0
        r["config"] = f"{profile}-pp4-k8"
        r["tpot_p50_vs_k1"] = round(
            r["tpot_p50_ms"] / base_row["tpot_p50_ms"], 3
        )
        rows.append(r)
        if profile == SLOW_DISPATCH:
            headline = r["tpot_p50_vs_k1"]
            assert headline <= 0.5, (
                f"fused pp megastep missed the acceptance bar: "
                f"{headline} > 0.5x vs host-rollback pp"
            )
    return {
        "metric": (
            f"mocker fused-pp-megastep A/B decode TPOT p50 ratio "
            f"({SLOW_DISPATCH} profile, pp=4, B={B}, {ISL}/{OSL}, k=8 vs host-rollback "
            "k=1, virtual clock; DYN_PP_HOP_US per stage hop)"
        ),
        "value": headline,
        "unit": "x vs pp k=1 (lower is better; deterministic mocker clock)",
        "vs_baseline": round(1.0 / headline, 4),
        "rows": rows,
        "note": (
            "ISSUE 20: one fused pp dispatch wavefronts k=8 iterations "
            "over 4 stages (k*pp + pp-1 priced hops + one base_iter_us) "
            "vs the host-rollback pipe paying dispatch + fill/drain "
            "bubble per token. Streams asserted bit-identical across "
            "pp on/off AND fused on/off; real-engine pp parity pinned by "
            "tests/test_pp_megastep.py. Mocker-profiled — the real 70B "
            "path is the llama3-70b-int8-kvint8-pp CONFIG (needs a "
            "4-stage pipe)"
        ),
    }


def run_kvquant_ab() -> dict:
    """Quantized-KV A/B (ISSUE 8), CPU-runnable. Three parts:

    1. CAPACITY — resident KV blocks at a fixed HBM budget for the
       llama3-8b geometry (the primary bench shape): int8 pages + f32
       scales vs bf16 pages. Pure arithmetic from the real page layout
       (engine/kv_quant.kv_page_bytes); the acceptance bar is >= 1.8x.
    2. DECODE TPOT on the mocker's VIRTUAL clock with the KV-read term
       priced (decode attention is DMA-latency-bound, PERF.md): bf16 at
       B=16 vs int8 at B=16 (pure traffic win) and int8 at B=32 (the
       capacity-enabled doubled batch). Streams asserted bit-identical
       bf16-vs-int8 at equal B.
    3. KERNEL A/B — int8-page vs bf16-page decode attention through the
       XLA dequant-on-gather reference, labelled with the backend (CPU
       gather timings do not transfer to TPU DMA behavior; the Pallas
       int8-page variant does not compile for TPU).
    """
    import asyncio

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.kv_quant import (
        kv_byte_ratio,
        kv_page_bytes,
        quantize_kv,
    )
    from dynamo_tpu.llm.mocker.engine import MockEngineArgs, MockTpuEngine, _Seq
    from dynamo_tpu.llm.protocols.common import StopConditions
    from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

    # -- 1. capacity at a fixed HBM budget (llama3-8b geometry) ------------
    bf16_block = kv_page_bytes(32, 32, 8, 128, "bf16")
    int8_block = kv_page_bytes(32, 32, 8, 128, "int8")
    kv_budget = 6 << 30  # ~16 GB chip minus ~8.5 GB int8-8b weights+slack
    blocks_bf16 = kv_budget // bf16_block
    blocks_int8 = kv_budget // int8_block
    capacity_ratio = blocks_int8 / blocks_bf16

    # -- 2. mocker virtual-clock decode A/B --------------------------------
    ISL, OSL = 128, 64
    BASE_US = 500.0

    def run(kv_dtype: str, B: int) -> tuple[dict, dict]:
        args = MockEngineArgs(
            num_kv_blocks=8192, block_size=32, max_num_seqs=B,
            max_num_batched_tokens=4096, enable_prefix_caching=False,
            base_iter_us=BASE_US,
            # Device decode split: ~0.02 ms/lane non-KV compute plus a
            # KV-read term that dominates at context (DMA-bound model):
            # 4-5 resident blocks/lane x 20 us at ISL=128.
            decode_us_per_seq=20.0,
            kv_read_us_per_block=20.0,
            kv_dtype=kv_dtype,
        )
        eng = MockTpuEngine(args)
        seqs = []
        for j in range(B):
            prompt = [1 + (j % 7)] * ISL
            s = _Seq(
                request_id=f"s{j}", prompt=prompt, max_tokens=OSL,
                out=asyncio.Queue(),
                seq=TokenBlockSequence(prompt, args.block_size),
                prompt_hashes=compute_seq_hashes(prompt, args.block_size),
                stop=StopConditions(max_tokens=OSL, ignore_eos=True),
            )
            seqs.append(s)
            eng._waiting.append(s)
        vt = 0.0
        first: dict[str, float] = {}
        prev: dict[str, float] = {}
        gaps: list[float] = []
        streams: dict[str, list[int]] = {s.request_id: [] for s in seqs}
        while any(s in eng._running or s in eng._waiting for s in seqs):
            eng._admit()
            p, d = eng._step()
            vt += eng.iter_time_s(p, d, eng._last_kv_blocks_read)
            for s in seqs:
                while not s.out.empty():
                    item = s.out.get_nowait()
                    if not isinstance(item, dict):
                        continue
                    toks = item.get("token_ids", [])
                    if not toks:
                        continue
                    streams[s.request_id].extend(toks)
                    rid = s.request_id
                    if rid in first:
                        gaps.extend([(vt - prev[rid]) / len(toks)] * len(toks))
                    first.setdefault(rid, vt)
                    prev[rid] = vt
        gaps.sort()
        decode_s = vt - max(first.values())
        return {
            "tpot_p50_ms": round(gaps[len(gaps) // 2] * 1e3, 3),
            "decode_tok_s": round(B * (OSL - 1) / max(decode_s, 1e-9), 1),
        }, streams

    bf16_row, bf16_streams = run("bf16", 16)
    i8_row, i8_streams = run("int8", 16)
    assert {k: v[: OSL] for k, v in i8_streams.items()} == bf16_streams, (
        "int8 mocker stream diverged from bf16"
    )
    i8x2_row, _ = run("int8", 32)
    rows = [
        dict(bf16_row, config="bf16-B16", resident_blocks_at_budget=blocks_bf16),
        dict(
            i8_row, config="int8-B16",
            tpot_p50_vs_bf16=round(i8_row["tpot_p50_ms"] / bf16_row["tpot_p50_ms"], 3),
        ),
        dict(
            i8x2_row, config="int8-B32-doubled-batch",
            resident_blocks_at_budget=blocks_int8,
            tok_s_vs_bf16=round(i8x2_row["decode_tok_s"] / bf16_row["decode_tok_s"], 3),
        ),
    ]

    # -- 3. int8-page vs bf16-page decode attention kernel A/B -------------
    from dynamo_tpu.ops import paged_attention as pa

    B, n_kv, group, d, bs, blocks = 16, 8, 4, 128, 32, 8
    total = (B * blocks + 1) * bs
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, n_kv * group, d), jnp.float32)
    k_f = jax.random.normal(ks[1], (n_kv, total, d), jnp.bfloat16)
    v_f = jax.random.normal(ks[2], (n_kv, total, d), jnp.bfloat16)
    k_i8, k_sc = quantize_kv(k_f)
    v_i8, v_sc = quantize_kv(v_f)
    tables = jnp.asarray(
        np.arange(B * blocks, dtype=np.int32).reshape(B, blocks)
    )
    seq_lens = jnp.asarray(np.full(B, blocks * bs - 5, np.int32))

    # The Pallas int8-page variant does not compile for TPU
    # (pa.INT8_PAGES_ON_TPU), so this A/B times the XLA dequant-on-gather
    # path everywhere and says so in its label.
    impl, label = pa.paged_attention_reference, "xla-reference-" + jax.default_backend()

    f_bf = jax.jit(lambda: impl(
        q, k_f, v_f, tables, seq_lens, block_size=bs
    ))
    f_i8 = jax.jit(lambda: impl(
        q, k_i8, v_i8, tables, seq_lens, block_size=bs,
        k_scale=k_sc, v_scale=v_sc,
    ))

    def bench_fn(f, reps=20):
        f()  # compile
        jax.block_until_ready(f())
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2] * 1e3

    t_bf = bench_fn(f_bf)
    t_i8 = bench_fn(f_i8)
    kernel_ab = {
        "impl": label,
        "bf16_page_ms": round(t_bf, 3),
        "int8_page_ms": round(t_i8, 3),
        "int8_vs_bf16": round(t_i8 / t_bf, 3),
        "note": (
            "xla-reference timings measure the dequant-on-gather math "
            "only and do NOT transfer to a page-DMA kernel"
        ),
    }

    return {
        "metric": (
            f"kv-quant A/B: resident KV blocks at a fixed {kv_budget >> 30} GiB "
            f"budget (llama3-8b geometry, int8 vs bf16 pages) + mocker "
            f"decode TPOT with the KV-read term priced ({ISL}/{OSL})"
        ),
        "value": round(capacity_ratio, 3),
        "unit": "x resident blocks vs bf16 (>= 1.8 required; scales included)",
        "vs_baseline": round(capacity_ratio, 4),
        "bytes_per_block": {"bf16": bf16_block, "int8": int8_block,
                            "ratio": round(kv_byte_ratio("int8", 128), 6)},
        "resident_blocks": {"bf16": int(blocks_bf16), "int8": int(blocks_int8)},
        "rows": rows,
        "kernel_ab": kernel_ab,
        "note": (
            "mocker virtual clock (deterministic, CPU-runnable): int8 "
            "prices 0.516x KV bytes per decode lane-iteration; the B=32 "
            "row is the capacity-enabled doubled batch the freed HBM "
            "buys. Streams asserted bit-identical bf16-vs-int8 at equal "
            "B; real-engine quality guard + byte-stability pinned by "
            "tests/test_kv_quant.py"
        ),
    }


def main() -> int:
    """Chip configs, then (unless BENCH_QUICK) the on-chip disagg A/B and
    the mocker A/Bs. Every line names the device; a phase that raises is
    reported and the rest still run, but the exit code says so."""
    import gc
    import traceback

    from dynamo_tpu.device import (
        device_info,
        device_peaks,
        enable_compile_cache,
    )
    from dynamo_tpu.engine.config import PRESETS, llama3_1b

    device = device_info()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX reports {device}. There is no "
            "CPU mode: a CPU run says nothing about the chip."
        )
    peaks = device_peaks(device["kind"])  # raises on an unknown chip
    enable_compile_cache()

    model = llama3_1b()
    configs = [c for c in CONFIGS if c.primary] if QUICK else CONFIGS
    phases = [
        (c.name, lambda c=c: run_config(
            PRESETS[c.model]() if c.model else model, c, peaks.hbm_gbps))
        for c in configs
    ]
    if not QUICK:
        phases.append(("disagg-ab", lambda: run_disagg_ab(model)))
        phases += [
            (fn.__name__, fn) for fn in (
                run_spec_ab, run_device_draft_ab, run_async_ab,
                run_megastep_ab, run_megastep_mixed_ab, run_pp_megastep_ab,
                run_kvquant_ab, run_overload_ab, run_peer_pool_ab,
                run_fleet_obs_ab, run_fleet_ab,
            )
        ]
    primary_name = next(c.name for c in configs if c.primary)

    results, failed, primary = [], [], None
    for name, phase in phases:
        try:
            r = dict(phase(), device=device)
        except Exception:  # noqa: BLE001 — one phase must not lose the rest
            traceback.print_exc()
            failed.append(name)
            continue
        results.append(r)
        if name == primary_name:
            primary = r
        # Every phase prints as soon as it is measured (the primary prints
        # AGAIN, with the full list, as the final line) — a driver-side
        # timeout mid-run still leaves complete JSON lines.
        print(json.dumps(r), flush=True)
        gc.collect()  # drop the phase's device buffers before the next
    summary = dict(
        primary or {"metric": "primary config failed", "value": None},
        device=device, failed_phases=failed,
        configs=[r for r in results if r is not primary],
    )
    print(json.dumps(summary), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
