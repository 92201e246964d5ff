"""Per-worker system status server: /health, /live, /metrics, /traces.

Capability parity: reference `lib/runtime/src/system_status_server.rs:31-712`
(axum server per process; per-endpoint health states; uptime gauge;
Prometheus text). Enabled through `DYN_SYSTEM_ENABLED` / `DYN_SYSTEM_PORT`
(`config.rs` DYN_SYSTEM_* prefix).

``/traces`` serves the process-local tracing ring buffer
(dynamo_tpu/tracing) as JSON: recent traces with per-phase waterfalls.
Spans recorded in *other* processes of the same deployment share trace
ids (traceparent propagation over the dataplane), so an operator stitches
a full request by querying each process's ``/traces`` for one trace id —
or, in single-process/frontends, reads the whole waterfall in one place.
"""

from __future__ import annotations

import logging
import time
from typing import Callable

from aiohttp import web

from dynamo_tpu import tracing
from dynamo_tpu.runtime.metrics import MetricsRegistry

log = logging.getLogger("dynamo_tpu.status")

# Scheduler gauge export: stats-dict key -> (metric name, doc). Shared by
# the real engine and the mocker (both expose scheduler_stats() dicts with
# these keys), so every worker's /metrics carries the same series.
SCHEDULER_GAUGES: dict[str, tuple[str, str]] = {
    "waiting": (
        "scheduler_waiting_seqs",
        "Sequences queued for admission (inbox + waiting)",
    ),
    "running": (
        "scheduler_running_seqs",
        "Sequences admitted and running",
    ),
    "preemptions": (
        "scheduler_preemptions_total",
        "Sequences preempted (released + re-queued) since start",
    ),
    "decode_stalls": (
        "scheduler_decode_stalls_total",
        "Decode iterations skipped waiting on a free block (mocker's "
        "preemption-lite; always 0 on the real engine, which preempts)",
    ),
    "last_step_batched_tokens": (
        "scheduler_last_step_batched_tokens",
        "Tokens batched into the most recent mixed step",
    ),
    "last_step_budget_utilization": (
        "scheduler_token_budget_utilization",
        "Most recent mixed step's batched tokens / max_num_batched_tokens",
    ),
    "chunked_prefills_in_flight": (
        "scheduler_chunked_prefills_in_flight",
        "Sequences mid-prefill (first chunk run, prompt not finished)",
    ),
    "chunked_scheduling": (
        "scheduler_chunked_enabled",
        "1 when the chunked token-budget scheduler is active",
    ),
    "token_budget": (
        "scheduler_token_budget",
        "Resolved per-step batched-token budget",
    ),
    "async_exec": (
        "scheduler_async_exec",
        "1 when the engine serves with the one-step-ahead (pipelined) "
        "loop: the loop it chose, not the option it was given",
    ),
    # Decode megastep (PERF.md r9): the dispatch-amortization evidence.
    "megastep_k": (
        "scheduler_megastep_k",
        "Resolved decode-megastep length (inner iterations per dispatch)",
    ),
    "megastep_dispatches": (
        "scheduler_megastep_dispatches_total",
        "Device dispatches that fused k > 1 decode iterations",
    ),
    "single_step_dispatches": (
        "scheduler_single_step_dispatches_total",
        "Single-iteration device dispatches (prefill waves, k == 1 "
        "mixed steps / verify rows / decode)",
    ),
    # Universal megastep (ISSUE 12): the lifted-carve-out evidence.
    "fused_mixed_dispatches": (
        "scheduler_fused_mixed_dispatches_total",
        "Universal-megastep dispatches that fused a ragged mixed/verify "
        "first iteration (prefill chunks / spec verify rows) with "
        "scanned decode continuation",
    ),
    "megastep_forced_single": (
        "scheduler_megastep_forced_single_total",
        "Megastep batches forced back to k=1 because a lane's stop "
        "watch overflowed the device's slots — the ONE documented "
        "un-fused path; anything non-zero without >8-stop-id requests "
        "is a bug",
    ),
    "dispatches_per_token": (
        "engine_dispatches_per_token",
        "Device dispatches / committed (client-visible) tokens since "
        "start — < 1.0 means multi-token dispatches are amortizing the "
        "fixed per-dispatch overhead",
    ),
    # Pipeline parallelism (ISSUE 20): fused pp megasteps on the fast path.
    "pp_stages": (
        "scheduler_pp_stages",
        "Pipeline-parallel stages this engine runs (1 = pp off)",
    ),
    "pp_pipe_occupancy": (
        "scheduler_pp_pipe_occupancy",
        "Steady-state pipe occupancy k*M / (k*M + pp - 1) for the "
        "resolved megastep length and microbatch count (1.0 when pp off)",
    ),
    "pp_fused_dispatches": (
        "scheduler_pp_fused_dispatches_total",
        "Fused pp megastep dispatches (k > 1 decode iterations wavefront-"
        "interleaved across the pipe in one device program)",
    ),
    "pp_forced_single": (
        "scheduler_pp_forced_single_total",
        "pp decode dispatches forced back to k=1 (stop-watch overflow — "
        "same documented un-fused path as megastep_forced_single)",
    ),
    # Overload robustness (ISSUE 10): bounded-queue + deadline shedding
    # and the fair-scheduler switch, on BOTH backends.
    "queue_limit": (
        "scheduler_queue_limit",
        "Bounded admission-queue ceiling (0 = unbounded); at the limit "
        "new requests get the typed retryable shed error",
    ),
    "shed_total": (
        "scheduler_requests_shed_total",
        "Requests refused at add_request because the bounded queue was "
        "full (each became a retry-elsewhere error, never a broken stream)",
    ),
    "deadline_expired_total": (
        "scheduler_deadline_expired_total",
        "Queued requests expired past their deadline (typed retryable "
        "error frame; admitted requests always run to completion)",
    ),
    "fair_enabled": (
        "scheduler_fair_enabled",
        "1 when per-tenant deficit-round-robin admission is active",
    ),
    # Looped stacks (ISSUE 27): what the loop costs the cache.
    "kv_cache_layers": (
        "engine_kv_cache_layers",
        "Planes of K/V a token holds: weight layers x passes over them "
        "(ut_steps); the weight layers of a single-pass model",
    ),
    "kv_bytes_per_token": (
        "engine_kv_bytes_per_token",
        "Bytes of K/V cache one token holds over all planes, at the "
        "cache's dtype (int8: scales included; a latent cache: its "
        "[ckv | kr] rows)",
    ),
    "state_bytes_per_block": (
        "engine_state_bytes_per_block",
        "Bytes of convolution state one block holds over all conv layers "
        "(their newest conv_L_cache - 1 rows, whatever the block size); 0 "
        "for a model without conv layers",
    ),
    "state_bytes_per_sequence": (
        "engine_state_bytes_per_sequence",
        "Bytes of recurrent state one sequence holds over all linear-attention "
        "or mamba layers, whatever its context (the float32 state and the convolution's "
        "newest rows, in a slab indexed by lane slot); 0 for a model without "
        "such layers",
    ),
    "experts_held": (
        "engine_experts_held",
        "Routed experts of each sparse layer this worker holds (its share "
        "of the router's width); 0 for a model without a stated share",
    ),
    # Window and full attention layers mixed (ISSUE 39): the window pool.
    "window_bytes_per_sequence": (
        "engine_window_bytes_per_sequence",
        "Bytes of K/V the window layers hold for one decoding sequence, "
        "whatever its context (sliding_window / block_size + 1 blocks in "
        "each); 0 for a model without window layers",
    ),
    "window_blocks_in_use": (
        "engine_window_blocks_in_use",
        "Blocks of the window pool that sequences hold now: those a later "
        "query of theirs may still see",
    ),
    "window_blocks": (
        "engine_window_blocks",
        "Blocks of the window pool (0: the model has no window layers)",
    ),
    # Generation by diffusion over blocks (ISSUE 42).
    "block_length": (
        "engine_block_length",
        "Places a block-diffusion model generates at a time, each seeing "
        "its block both ways (0: one next token a step)",
    ),
    "denoising_steps": (
        "engine_denoising_steps",
        "Denoising passes a block is served with; one more, over the clean "
        "block, writes its K/V (0: not a block-diffusion model)",
    ),
}


def bind_scheduler_gauges(
    status: "SystemStatusServer | None", scheduler_stats: Callable[[], dict]
) -> None:
    """Export a worker's scheduler gauges on its status-server /metrics,
    evaluated at scrape time (prometheus set_function — no polling task).
    No-op when the status server is disabled."""
    if status is None:
        return
    scoped = status.metrics.scoped(service="engine")
    for key, (name, doc) in SCHEDULER_GAUGES.items():
        scoped.gauge(name, doc).set_function(
            lambda k=key: float(scheduler_stats().get(k, 0) or 0)
        )


# Engine counter export: stats-dict key -> (metric name, doc). Keys match
# EngineCore.scheduler_stats() (its exec_stats part). Unlike the gauges
# above these are typed ``counter``: a reader takes the difference of two
# scrapes and divides one by another (dispatches, tokens, occupancy).
ENGINE_COUNTERS: dict[str, tuple[str, str]] = {
    "dispatches": (
        "engine_dispatches",
        "Device dispatches of step programs since start",
    ),
    "pipelined_dispatches": (
        "engine_pipelined_dispatches",
        "Dispatches enqueued while another step was in flight (the "
        "one-step-ahead loop engaging)",
    ),
    "drains": (
        "engine_pipeline_drains",
        "In-flight steps committed early so a plan could preempt under "
        "block pressure",
    ),
    "committed_tokens": (
        "engine_committed_tokens",
        "Tokens committed to client streams since start",
    ),
    "decode_tokens_committed": (
        "engine_decode_tokens_committed",
        "Tokens decode iterations committed to client streams: all but "
        "each stream's first. The denominator of engine_lane_seconds",
    ),
    "decode_live_lanes": (
        "engine_decode_live_lanes",
        "Live lanes summed over decode dispatches",
    ),
    "decode_padded_lanes": (
        "engine_decode_padded_lanes",
        "Padded batch width summed over decode dispatches",
    ),
    "megastep_useful_lane_iters": (
        "engine_megastep_useful_lane_iters",
        "Lane-iterations of decode megasteps that gave a client a token",
    ),
    "megastep_issued_lane_iters": (
        "engine_megastep_issued_lane_iters",
        "Lane-iterations decode megasteps issued (live lanes x k)",
    ),
    "ragged_real_tokens": (
        "engine_ragged_real_tokens",
        "Real tokens summed over ragged (prefill wave / mixed) dispatches",
    ),
    "ragged_bucket_tokens": (
        "engine_ragged_bucket_tokens",
        "Bucket (padded) tokens summed over ragged dispatches",
    ),
    "prefill_cut_waves": (
        "engine_prefill_cut_waves",
        "Prefill waves the planner ended before the waiting prompt tokens "
        "did, other than at the largest bucket: the rest rides a later, "
        "smaller wave instead of padding this one to the next bucket",
    ),
    "layer_passes": (
        "engine_layer_passes",
        "Passes over the layer stack, per live lane and fused iteration "
        "(a prefill wave: per sequence): dispatched lanes x k x ut_steps",
    ),
    "window_blocks_released": (
        "engine_window_blocks_released",
        "Window-pool blocks that slid wholly out of every later query's "
        "window and were given back while their sequence went on",
    ),
    "state_replayed_tokens": (
        "engine_state_replayed_tokens",
        "Tokens a sequence of a model with linear-attention or mamba layers had run when "
        "it was preempted: no block holds their state, so it runs them again "
        "from position 0 into a fresh lane slot",
    ),
    "blocks_committed": (
        "engine_blocks_committed",
        "Blocks of a block-diffusion model of which a client was sent at "
        "least one place (a block cut by a stop counts)",
    ),
    "block_rows": (
        "engine_block_rows",
        "Live rows of a block-diffusion model's passes: block_length a live "
        "lane a pass, and as many again where a pending block's clean rows "
        "rode the pass",
    ),
    "head_rows": (
        "engine_block_head_rows",
        "Of those rows, the ones that went through the head and the "
        "sampler: a pass's places that could still be hidden, none of the "
        "clean rows",
    ),
    "block_clean_folded": (
        "engine_block_clean_folded",
        "Kept blocks whose clean rows (their final K/V) rode the first pass "
        "of the lane's next block and moved the lane's cursor over them; "
        "over engine_blocks_committed: the share of blocks that cost no "
        "pass of their own",
    ),
    "block_pending_dropped": (
        "engine_block_pending_dropped",
        "Revealed blocks whose clean rows never ran: the lane ended, was "
        "cancelled or was preempted first (a request's last block)",
    ),
    "block_places_discarded": (
        "engine_block_places_discarded",
        "Places a block-diffusion model generated and no client was sent: "
        "those after a cut, and those of blocks run past a stop that only "
        "the host's scan saw",
    ),
}

# Counters with ONE label: (name, doc, label, {label value: stats key}).
# A block-diffusion model's, then every model's.
LABELLED_COUNTERS: tuple[tuple[str, str, str, dict[str, str]], ...] = (
    ("engine_denoise_forwards",
     "Passes a live lane of a block-diffusion model ran, counted once a "
     "lane a pass. Every pass is a denoising pass: a block's clean rows "
     "ride the next block's first (engine_block_clean_folded)",
     "pass", {"denoise": "denoise_forwards"}),
    ("engine_places_revealed",
     "Places a denoising pass revealed, by the rule: every hidden place "
     "over the confidence threshold, or the step's quota of the surest",
     "by", {"threshold": "places_revealed_threshold", "quota": "places_revealed_quota"}),
    ("engine_dispatches_by_sampling",
     "Device dispatches by the branch the sampler's conditional takes in "
     "them (engine/sampler.py): greedy, every lane of the batch at "
     "temperature 0, the arg-max alone; drawn, some lane draws. One "
     "compiled program serves both",
     "kind", {"greedy": "dispatches_greedy", "drawn": "dispatches_drawn"}),
)


# The step clock's account of the device: key of ``StepClock.account()`` ->
# (name, doc, labels after ``service``). Counters, from the same readings as
# the phases.
DEVICE_ACCOUNT_COUNTERS: dict[str, tuple[str, str, tuple[str, ...]]] = {
    "device_seconds": (
        "engine_device_seconds",
        "Estimated device-busy seconds by the kind of dispatch (prefill, "
        "megastep, decode, mixed): from the later of its enqueue and its "
        "predecessor's finish to its landing, where the blocking fetch "
        "waited; a late landing (the output was ready before the host came "
        "for it) counts up to the upper end of its bracketed finish",
        ("kind",),
    ),
    "late_landings": (
        "engine_late_landings",
        "Dispatches whose output was ready before the host fetched it, by "
        "kind: the device may have had nothing queued after them",
        ("kind",),
    ),
    "starved_seconds": (
        "engine_device_starved_seconds",
        "Seconds between a dispatch's finish and the next enqueue while the "
        "engine had work (no_work is not starvation). bound: lower / upper, "
        "equal where the landing waited (a late landing's finish is "
        "bracketed by two polls); phase: the step-clock phase they lay "
        "under; after: the kind of the dispatch whose end began them",
        ("bound", "phase", "after"),
    ),
    "lane_seconds": (
        "engine_lane_seconds",
        "Seconds of decode-ready lanes (running, prefill done) by state: "
        "decode (inside a dispatch that carried the lane), behind_prefill (a "
        "wave that did not carry it held the device), behind_host (the "
        "device starved, upper bound, while it was runnable). Over "
        "engine_decode_tokens_committed: what a token cost, by cause",
        ("state",),
    ),
}


# What a sparse model's layers counted of their router's choices, in the
# order EngineCore.scheduler_stats()["expert_stats"][phase] holds them
# (model._shared_sparse_mlp); phase: the program that counted ("decode": a
# megastep's iterations, "prefill": a wave). All zero for a dense model.
EXPERT_COUNTERS: tuple[tuple[str, str], ...] = (
    ("engine_experts_touched",
     "Held experts that at least one live token was routed to, summed "
     "over sparse layers and steps"),
    ("engine_expert_steps",
     "Sparse layers x steps counted: experts_touched / expert_steps is "
     "the held experts touched a layer a step"),
    ("engine_expert_pairs_held",
     "(token, expert) pairs the router chose that fell on held experts "
     "(all computed: the layer drops none)"),
    ("engine_expert_pairs_routed",
     "(token, expert) pairs the router chose over its whole width: live "
     "tokens x experts per token x sparse layers"),
    ("engine_expert_rows_computed",
     "Rows the expert products ran on: every held expert on every row of "
     "a decode step; in a prefill wave the rows of the tiles that hold a "
     "chosen pair. Over expert_pairs_held: how far the work follows the "
     "load (1 = only the chosen pairs)"),
)


class _EngineCounters:
    """Scrape-time collector for the engine's cumulative counters: the
    step clock's seconds per phase, :data:`ENGINE_COUNTERS`, and the
    labelled series (prefill waves and their measured ms by bucket,
    attention calls traced by shape and implementation) and, where the
    engine offers it, the step clock's account of the device
    (``account``: ``EngineCore.device_account``)."""

    def __init__(self, phase_seconds: Callable[[], dict], stats: Callable[[], dict],
                 account: Callable[[], dict] | None = None):
        self._phase_seconds = phase_seconds
        self._stats = stats
        self._account = account

    def collect(self):
        from prometheus_client.core import CounterMetricFamily, GaugeMetricFamily

        phases = CounterMetricFamily(
            "dynamo_engine_step_phase_seconds",
            "Engine-loop wall time by step phase; the phases partition it. "
            "blocks: what held the engine thread (host work, a wait for "
            "the device, or no work)",
            labels=["service", "phase", "blocks"],
        )
        for (phase, blocks), seconds in self._phase_seconds().items():
            phases.add_metric(["engine", phase, blocks], seconds)
        yield phases
        if self._account is not None:
            yield from self._device_account(self._account())
        stats = self._stats()
        for key, (name, doc) in ENGINE_COUNTERS.items():
            family = CounterMetricFamily(f"dynamo_{name}", doc, labels=["service"])
            family.add_metric(["engine"], float(stats.get(key, 0) or 0))
            yield family
        for name, doc, label, keys in LABELLED_COUNTERS:
            family = CounterMetricFamily(f"dynamo_{name}", doc, labels=["service", label])
            for value, key in keys.items():
                family.add_metric(["engine", value], float(stats.get(key, 0) or 0))
            yield family
        waves = CounterMetricFamily(
            "dynamo_engine_prefill_waves",
            "Prefill waves dispatched, by the token bucket they rode",
            labels=["service", "bucket"],
        )
        for bucket, n in sorted(stats.get("prefill_waves", {}).items()):
            waves.add_metric(["engine", str(bucket)], float(n))
        yield waves
        bucket_ms = GaugeMetricFamily(
            "dynamo_engine_prefill_bucket_ms",
            "Measured ms of one prefill wave per token bucket (warm-up "
            "times each compiled program once); the waves planner covers "
            "the waiting prompt tokens with the cheapest set of these. No "
            "series: not measured, and every wave pads to the next bucket",
            labels=["service", "bucket"],
        )
        for bucket, ms in sorted(stats.get("prefill_bucket_ms", {}).items()):
            bucket_ms.add_metric(["engine", str(bucket)], float(ms))
        yield bucket_ms
        from dynamo_tpu.ops.ragged_attention import traced_calls

        traced = CounterMetricFamily(
            "dynamo_engine_attention_calls_traced",
            "Attention calls traced into step programs, by the shape the "
            "caller stated (decode: one query token a sequence, the "
            "kernel's decode grid; ragged; window-, latent- and gqa- before "
            "them for a window layer's call, the latent page's and the "
            "wide-key page's) and the implementation chosen (library: the "
            "library's Pallas kernel; pallas: a first-party one; reference, "
            "jnp: no kernel); the choice is static per compiled program",
            labels=["service", "shape", "impl"],
        )
        for (shape, impl), n in sorted(traced_calls().items()):
            traced.add_metric(["engine", shape, impl], float(n))
        yield traced
        from dynamo_tpu.ops import grouped_matmul, linear_attention, ssm

        linear = CounterMetricFamily(
            "dynamo_engine_linear_calls_traced",
            "Linear-attention (gated delta rule) state calls traced into step "
            "programs, by shape (step: one row a lane, the decode step's read and "
            "write of every live lane's state; scan: the chunked scan of a ragged "
            "batch) and the implementation chosen (pallas: the first-party "
            "kernel; jnp: no kernel)",
            labels=["service", "shape", "impl"],
        )
        for (shape, impl), n in sorted(linear_attention.traced_calls().items()):
            linear.add_metric(["engine", shape, impl], float(n))
        yield linear
        ssm_calls = CounterMetricFamily(
            "dynamo_engine_ssm_calls_traced",
            "Mamba-2 (state-space) state calls traced into step programs, by "
            "shape (step: one row a lane, the decode step's read and write of "
            "every live lane's state; scan: the chunked scan of a ragged batch) "
            "and the implementation chosen (pallas: the first-party kernel; "
            "jnp: no kernel)",
            labels=["service", "shape", "impl"],
        )
        for (shape, impl), n in sorted(ssm.traced_calls().items()):
            ssm_calls.add_metric(["engine", shape, impl], float(n))
        yield ssm_calls
        slots = GaugeMetricFamily(
            "dynamo_engine_state_slots",
            "Lane slots of the linear-attention or mamba layers' slab, by state: held by "
            "a running sequence or free (the garbage slot apart); no series for "
            "a model without such layers",
            labels=["service", "state"],
        )
        for state, n in sorted(stats.get("state_slots", {}).items()):
            slots.add_metric(["engine", state], float(n))
        yield slots
        experts = CounterMetricFamily(
            "dynamo_engine_expert_calls_traced",
            "Sparse layers' expert calls traced into step programs, by "
            "shape (wave: more rows than every expert on every row serves, "
            "a prefill wave; step: a decode step's rows) and the path "
            "chosen (grouped/stream: the chosen pairs sorted by expert "
            "through one Pallas kernel that streams each touched expert's "
            "weights once; grouped/pallas, grouped/ragged_dot: two grouped "
            "products over them; "
            "stream/pallas, all_rows: every held expert on every row, as "
            "one Pallas kernel that streams each expert's weights once or "
            "as a loop of XLA products)",
            labels=["service", "shape", "impl"],
        )
        for (shape, impl), n in sorted(grouped_matmul.traced_calls().items()):
            experts.add_metric(["engine", shape, impl], float(n))
        yield experts
        kinds = GaugeMetricFamily(
            "dynamo_engine_cache_layers",
            "Page arrays the cache holds, by what a layer of that kind "
            "caches: attention (planes of K/V, or latent rows), conv (the "
            "short convolution's state pages), window (K/V of a sliding "
            "window, in a pool of its own), linear or ssm (a slab of float32 "
            "state indexed by lane slot) or none (a block that is a feed-forward "
            "alone caches nothing)",
            labels=["service", "kind"],
        )
        for kind, n in sorted(stats.get("cache_layers", {}).items()):
            kinds.add_metric(["engine", kind], float(n))
        yield kinds
        block_bytes = GaugeMetricFamily(
            "dynamo_engine_cache_bytes_per_block",
            "Bytes one block of a cache pool holds over all the layers of that "
            "kind, at the cache's dtype: the pools of a model whose window "
            "layers have KV heads of their own hold blocks of different bytes",
            labels=["service", "kind"],
        )
        for kind, n in sorted(stats.get("cache_bytes_per_block", {}).items()):
            block_bytes.add_metric(["engine", kind], float(n))
        yield block_bytes
        page = GaugeMetricFamily(
            "dynamo_engine_cache_page_values",
            "Values in one layer's page of a block, by the layer's kind and "
            "the page's shape (ModelConfig.kv_page_tail)",
            labels=["service", "kind", "shape"],
        )
        for kind, shape in sorted(stats.get("cache_page_shape", {}).items()):
            n = 1
            for dim in shape:
                n *= dim
            page.add_metric(["engine", kind, "x".join(str(d) for d in shape)], float(n))
        yield page
        reads = CounterMetricFamily(
            "dynamo_engine_conv_state_reads",
            "Times a sequence's rows read convolution state from the pages, "
            "by where it was written: same_step (an earlier iteration of the "
            "same megastep), earlier_dispatch (the sequence's own previous "
            "chunk or step), prefix_hit (a shared block another request "
            "filled). The state is found, never rebuilt",
            labels=["service", "from"],
        )
        for source, n in sorted(stats.get("conv_state_reads", {}).items()):
            reads.add_metric(["engine", source], float(n))
        yield reads
        by_phase = stats.get("expert_stats", {})
        for i, (name, doc) in enumerate(EXPERT_COUNTERS):
            family = CounterMetricFamily(
                f"dynamo_{name}", doc, labels=["service", "phase"])
            for phase, counts in sorted(by_phase.items()):
                family.add_metric(["engine", phase], float(counts[i]))
            yield family


    @staticmethod
    def _device_account(account: dict):
        """The step clock's account (``StepClock.account``), from the same
        readings as the phases above."""
        from prometheus_client.core import CounterMetricFamily

        for key, (name, doc, labels) in DEVICE_ACCOUNT_COUNTERS.items():
            family = CounterMetricFamily(
                f"dynamo_{name}", doc, labels=["service", *labels])
            for label, value in account[key].items():
                values = label if isinstance(label, tuple) else (label,)
                family.add_metric(["engine", *values], float(value))
            yield family


def bind_engine_counters(
    status: "SystemStatusServer | None",
    phase_seconds: Callable[[], dict],
    scheduler_stats: Callable[[], dict],
    device_account: Callable[[], dict] | None = None,
) -> None:
    """Export ``dynamo_engine_step_phase_seconds_total{phase, blocks}``
    (``phase_seconds`` returns ``{(phase, blocks): seconds}``), the
    :data:`ENGINE_COUNTERS` and, beside the phases, the step clock's
    account of the device (``device_account``:
    ``dynamo_engine_device_seconds_total{kind}``,
    ``..._late_landings_total{kind}``,
    ``..._device_starved_seconds_total{bound, phase, after}``,
    ``..._lane_seconds_total{state}``) on a worker's /metrics. No-op when
    the status server is disabled."""
    if status is None:
        return
    status.metrics.registry.register(
        _EngineCounters(phase_seconds, scheduler_stats, device_account))


# Speculative-decoding gauge export: stats-dict key -> (name, doc). Keys
# match EngineCore.spec_decode_stats() / MockTpuEngine.spec_decode_stats()
# (SpecStats.as_dict + "enabled").
SPEC_GAUGES: dict[str, tuple[str, str]] = {
    "enabled": (
        "spec_decode_enabled",
        "1 when an engine-level speculative-decoding policy is configured",
    ),
    "acceptance_rate": (
        "spec_decode_acceptance_rate",
        "Drafted tokens the target model accepted / drafted tokens",
    ),
    "mean_accepted_len": (
        "spec_decode_mean_accepted_len",
        "Mean tokens emitted per verify row (>= 1.0; the dispatch "
        "amortization speculation buys)",
    ),
    "drafted_tokens": (
        "spec_decode_drafted_tokens_total",
        "Draft tokens proposed (and verified) since start",
    ),
    "accepted_tokens": (
        "spec_decode_accepted_tokens_total",
        "Draft tokens accepted since start",
    ),
    "wasted_tokens": (
        "spec_decode_wasted_tokens_total",
        "Draft tokens verified and rejected since start (speculation loss)",
    ),
    "verify_steps": (
        "spec_decode_verify_steps_total",
        "Engine steps that carried at least one verify row",
    ),
    # On-device drafting (ISSUE 18): draft->verify->accept rounds riding
    # INSIDE megastep dispatches, and the amortization gauge they move.
    "device_rounds": (
        "spec_device_rounds_total",
        "On-device draft rounds ridden inside megastep dispatches",
    ),
    "device_hits": (
        "spec_device_draft_hits_total",
        "On-device draft rounds whose history-ring match proposed at "
        "least one token",
    ),
    "dispatches_per_accepted_token": (
        "spec_decode_dispatches_per_accepted_token",
        "Device dispatches per accepted draft token (lower is better; "
        "on-device drafting compounds accepted depth per dispatch)",
    ),
}


def bind_spec_gauges(
    status: "SystemStatusServer | None", spec_stats: Callable[[], dict]
) -> None:
    """Export a worker's speculative-decoding gauges on /metrics (same
    scrape-time evaluation as the scheduler gauges)."""
    if status is None:
        return
    scoped = status.metrics.scoped(service="engine")
    for key, (name, doc) in SPEC_GAUGES.items():
        scoped.gauge(name, doc).set_function(
            lambda k=key: float(spec_stats().get(k, 0) or 0)
        )


# Prefix-cache gauge export: stats-dict key -> (name, doc). Keys match
# EngineCore.kv_cache_stats() / MockTpuEngine.kv_cache_stats() — the
# allocator has counted prefix queries/hits since the prefix cache
# landed, but never surfaced them on /metrics.
KV_CACHE_GAUGES: dict[str, tuple[str, str]] = {
    # Quantized-KV capacity observability (ISSUE 8): the int8 capacity
    # doubling must be readable off /metrics, not just asserted in tests.
    "kv_dtype_int8": (
        "kv_cache_dtype_int8",
        "1 when the paged KV cache stores int8 pages + scale metadata "
        "(kv_dtype=int8), 0 for the bf16/model-dtype layout",
    ),
    "bytes_per_block": (
        "kv_cache_bytes_per_block",
        "Bytes one KV block occupies across all layers, scale metadata "
        "included (int8 is ~0.52x the bf16 page at head_dim 128)",
    ),
    "capacity_blocks": (
        "kv_cache_capacity_blocks",
        "Total resident-block capacity of the device KV pool",
    ),
    "resident_blocks": (
        "kv_cache_resident_blocks",
        "KV blocks currently resident (pinned + cached)",
    ),
    "prefix_queries": (
        "kv_prefix_cache_queries_total",
        "match_prefix probes (router overlap scoring, disagg "
        "local-vs-remote decisions) since start",
    ),
    "prefix_hits": (
        "kv_prefix_cache_hits_total",
        "match_prefix probes that found at least one cached leading block",
    ),
    "prefix_hit_rate": (
        "kv_prefix_cache_hit_rate",
        "prefix_hits / prefix_queries (probe series; 0 when no queries)",
    ),
    "admitted_queries": (
        "kv_prefix_cache_admitted_queries_total",
        "Sequences admitted by the scheduler since start",
    ),
    "admitted_hits": (
        "kv_prefix_cache_admitted_hits_total",
        "Admitted sequences whose prompt prefix was served from cache "
        "(device blocks or host-tier onboard)",
    ),
    "admitted_hit_rate": (
        "kv_prefix_cache_admitted_hit_rate",
        "admitted_hits / admitted_queries (0 when nothing admitted yet)",
    ),
}


def bind_kv_cache_gauges(
    status: "SystemStatusServer | None", kv_cache_stats: Callable[[], dict]
) -> None:
    """Export a worker's prefix-cache + KV-layout gauges on /metrics
    (same scrape-time evaluation as the scheduler gauges). The cache
    dtype also exports as a labeled info gauge —
    ``kv_cache_dtype{kv_dtype="int8"} 1`` — the Prometheus idiom for
    string-valued facts."""
    if status is None:
        return
    scoped = status.metrics.scoped(service="engine")
    for key, (name, doc) in KV_CACHE_GAUGES.items():
        scoped.gauge(name, doc).set_function(
            lambda k=key: float(kv_cache_stats().get(k, 0) or 0)
        )
    dtype = str(kv_cache_stats().get("kv_dtype", "") or "")
    if dtype:
        status.metrics.scoped(service="engine", kv_dtype=dtype).gauge(
            "kv_cache_dtype",
            "KV cache storage dtype as an info gauge (value label)",
        ).set(1.0)


# Cluster KV pool gauges (ISSUE 11): the worker's peer-pull outcomes and
# its published global-index contribution. Keys match
# PeerKvClient.pool_stats() + KvEventPublisher.stats() on the jax backend
# and MockTpuEngine.kv_pool_stats() on the mocker — identical series on
# both, like every other gauge family here.
KV_POOL_GAUGES: dict[str, tuple[str, str]] = {
    "pulls_attempted": (
        "kv_pool_peer_pulls_attempted_total",
        "Peer prefix pulls started (router hinted a better-overlapping peer)",
    ),
    "pulls_succeeded": (
        "kv_pool_peer_pulls_succeeded_total",
        "Peer pulls that streamed to completion (imported blocks prefix-hit)",
    ),
    "pulls_fallback": (
        "kv_pool_peer_pulls_fallback_total",
        "Peer pulls that degraded to local recompute (sever/stall/dead "
        "peer/dtype mismatch — never a stalled request)",
    ),
    "blocks_pulled": (
        "kv_pool_blocks_pulled_total",
        "KV blocks imported from peers since start",
    ),
    "bytes_pulled": (
        "kv_pool_bytes_pulled_total",
        "KV page bytes received from peers (canonical packed wire buffer)",
    ),
    "last_pull_ms": (
        "kv_pool_last_pull_latency_ms",
        "Wall-clock latency of the most recent peer pull",
    ),
    "pull_ms_total": (
        "kv_pool_pull_latency_ms_total",
        "Cumulative peer-pull wall-clock milliseconds",
    ),
    "breaker_fast_fails": (
        "kv_pool_breaker_fast_fails_total",
        "Peer pulls refused in microseconds by an open dataplane circuit "
        "breaker (recompute instead of burning a connect timeout)",
    ),
    "dtype_mismatches": (
        "kv_pool_dtype_mismatch_total",
        "Peer pulls refused by the kv_dtype fail-fast contract (mixed "
        "int8/float fleet; re-quantizing would break bit-stability)",
    ),
    "published_blocks": (
        "kv_pool_published_blocks",
        "Net blocks this worker currently advertises to the global index "
        "(its stored-minus-removed contribution, all tiers)",
    ),
    "events_dropped": (
        "kv_events_dropped_total",
        "KV events dropped by the bounded publisher buffer (each schedules "
        "an anti-entropy full-inventory resync)",
    ),
    "events_published": (
        "kv_events_published_total",
        "KV events published to the control plane since start",
    ),
    "resyncs": (
        "kv_events_resyncs_total",
        "Full-inventory re-publishes (after buffer overflow or an "
        "indexer-requested resync)",
    ),
}


def bind_kv_pool_gauges(
    status: "SystemStatusServer | None", kv_pool_stats: Callable[[], dict]
) -> None:
    """Export a worker's cluster-KV-pool gauges on /metrics (same
    scrape-time evaluation as the scheduler gauges). No-op when the
    status server is disabled."""
    if status is None:
        return
    scoped = status.metrics.scoped(service="kv_pool")
    for key, (name, doc) in KV_POOL_GAUGES.items():
        scoped.gauge(name, doc).set_function(
            lambda k=key: float(kv_pool_stats().get(k, 0) or 0)
        )


# Streaming-disaggregation handoff gauges (ISSUE 17): chunk-pipelined
# pull progress on the decode side. `early_chunks` is the headline — a
# nonzero value PROVES transfer/compute overlap (chunks landed before
# the prefill's final cursor), which is what the disagg smoke asserts.
DISAGG_GAUGES: dict[str, tuple[str, str]] = {
    "handoffs_started": (
        "disagg_handoffs_total",
        "Streaming handoffs attempted for remotely-prefilled requests",
    ),
    "handoffs_streamed": (
        "disagg_handoffs_streamed_total",
        "Handoffs fully streamed chunk-by-chunk (legacy pull skipped)",
    ),
    "handoffs_fallback": (
        "disagg_handoff_fallback_total",
        "Handoffs degraded to the reply-gated pull (cursor timeout, "
        "severed window, or import refusal)",
    ),
    "chunks_pulled": (
        "disagg_chunks_pulled_total",
        "KV chunk windows pulled over the streaming handoff",
    ),
    "early_chunks": (
        "disagg_early_chunks_total",
        "Chunk windows pulled BEFORE the prefill finished (the overlap "
        "the subsystem exists to create)",
    ),
    "blocks_streamed": (
        "disagg_streamed_blocks_total",
        "KV blocks moved by streaming windows",
    ),
    "cursor_timeouts": (
        "disagg_cursor_timeouts_total",
        "Handoffs that saw no cursor advance within the timeout",
    ),
}


def bind_disagg_gauges(
    status: "SystemStatusServer | None", disagg_stats: Callable[[], dict]
) -> None:
    """Export a decode worker's streaming-handoff gauges on /metrics."""
    if status is None:
        return
    scoped = status.metrics.scoped(service="disagg")
    for key, (name, doc) in DISAGG_GAUGES.items():
        scoped.gauge(name, doc).set_function(
            lambda k=key: float(disagg_stats().get(k, 0) or 0)
        )


# Per-tenant fair-queue gauges: queue depth and DRR deficit per tenant.
# Tenant labels are dynamic (tenants appear as their first request
# arrives), so these sync via a before_render hook like the egress
# gauges rather than pre-bound set_function children.
FAIR_QUEUE_GAUGES: dict[str, tuple[str, str]] = {
    "depth": (
        "scheduler_tenant_queue_depth",
        "Requests waiting in this tenant's admission queue",
    ),
    "deficit": (
        "scheduler_tenant_deficit_tokens",
        "The tenant's current deficit-round-robin token balance",
    ),
}


# Tenant labels come from the CLIENT-controlled x-tenant-id header, so
# the export is bounded: at most this many distinct tenant series, the
# overflow aggregated under tenant="__other__", and drained tenants'
# series REMOVED (not zeroed) so /metrics output cannot grow without
# bound from a rotating-tenant spray.
MAX_TENANT_GAUGES = 64


def bind_fair_queue_gauges(
    status: "SystemStatusServer | None", fair_queue_stats: Callable[[], dict]
) -> None:
    """Export a worker's per-tenant admission-queue gauges on /metrics
    (labels: service=engine, tenant=<id>). ``fair_queue_stats`` returns
    {tenant: {"depth": n, "deficit": d}} (EngineCore/MockTpuEngine
    fair_queue_stats). No-op when the status server is disabled."""
    if status is None:
        return

    seen: set[str] = set()

    def sync() -> None:
        stats = fair_queue_stats()
        if len(stats) > MAX_TENANT_GAUGES:
            ranked = sorted(
                stats.items(), key=lambda kv: -kv[1].get("depth", 0.0)
            )
            stats = dict(ranked[:MAX_TENANT_GAUGES])
            other = {"depth": 0.0, "deficit": 0.0}
            for _t, st in ranked[MAX_TENANT_GAUGES:]:
                for k in other:
                    other[k] += st.get(k, 0.0)
            stats["__other__"] = other
        # Tenants that left the snapshot take their series with them —
        # a stale zeroed series per tenant-ever-seen is still unbounded
        # /metrics growth.
        for tenant in seen - set(stats):
            scoped = status.metrics.scoped(service="engine", tenant=tenant)
            for _key, (name, _doc) in FAIR_QUEUE_GAUGES.items():
                scoped.remove_gauge(name)
        seen.intersection_update(stats)
        for tenant, st in stats.items():
            seen.add(tenant)
            scoped = status.metrics.scoped(service="engine", tenant=tenant)
            for key, (name, doc) in FAIR_QUEUE_GAUGES.items():
                scoped.gauge(name, doc).set(float(st.get(key, 0.0)))

    status.before_render.append(sync)


# Dataplane egress containment gauges: per-address circuit-breaker state
# and stall counters (EgressClient.stats() keys). Addresses are dynamic —
# they appear as the pool dials — so these sync via a before_render hook
# instead of set_function children.
EGRESS_GAUGES: dict[str, tuple[str, str]] = {
    "breaker_open": (
        "egress_breaker_open",
        "1 when the address's circuit breaker is open (dials fail fast)",
    ),
    "breaker_half_open": (
        "egress_breaker_half_open",
        "1 while a single half-open probe decides the breaker's fate",
    ),
    "consecutive_failures": (
        "egress_consecutive_failures",
        "Consecutive connect failures / conn deaths / stalls for the address",
    ),
    "opens_total": (
        "egress_breaker_opens_total",
        "Times the address's breaker has opened since start",
    ),
    "stalls_total": (
        "egress_stream_stalls_total",
        "Response streams declared stalled (per-token deadline) for the address",
    ),
    "connected": (
        "egress_connected",
        "1 while a live pooled connection to the address exists",
    ),
}


def bind_egress_gauges(status: "SystemStatusServer | None", egress) -> None:
    """Export the egress pool's per-address breaker/stall state on
    /metrics (labels: service=dataplane, address=<host:port>). No-op when
    the status server is disabled."""
    if status is None:
        return

    def sync() -> None:
        for address, st in egress.stats().items():
            scoped = status.metrics.scoped(service="dataplane", address=address)
            values = {
                "breaker_open": 1.0 if st["state"] == "open" else 0.0,
                "breaker_half_open": 1.0 if st["state"] == "half-open" else 0.0,
                "consecutive_failures": float(st["consecutive_failures"]),
                "opens_total": float(st["opens_total"]),
                "stalls_total": float(st["stalls_total"]),
                "connected": 1.0 if st["connected"] else 0.0,
            }
            for key, (name, doc) in EGRESS_GAUGES.items():
                scoped.gauge(name, doc).set(values[key])

    status.before_render.append(sync)


# Control-plane connectivity gauges (ISSUE 15): the store client's
# connection-state surface, exported on every process's /metrics (both
# backends via their mains, the frontend via _bind_store_gauges on its
# own registry). Keys match StoreClient.stats().
STORE_GAUGES: dict[str, tuple[str, str]] = {
    "connected": (
        "store_connected",
        "1 while a live control-plane store session exists; 0 means this "
        "process is serving in degraded mode on cached discovery state",
    ),
    "outage_seconds": (
        "store_outage_seconds",
        "Cumulative seconds without a store session since start, the "
        "current outage included",
    ),
    "disconnected_for_s": (
        "store_disconnected_seconds",
        "Seconds since the current outage began (0 while connected)",
    ),
    "keepalive_failures": (
        "store_keepalive_failures_total",
        "Lease-keepalive beats that failed transiently (the loop "
        "survives them and re-attaches expired leases; a rising counter "
        "with store_connected=1 means keepalives are being lost)",
    ),
    "reconnects": (
        "store_session_rebuilds_total",
        "Store sessions rebuilt after an outage (leases re-granted, "
        "lease-bound KV replayed, watches and subscriptions resumed)",
    ),
}


def _bind_store_gauges(metrics: MetricsRegistry, hooks: list, store) -> None:
    """Registry-level binder (the HTTP frontend reuses it on its own
    metrics registry + before_metrics hooks)."""
    scoped = metrics.scoped(service="store")

    def sync() -> None:
        st = store.stats()
        for key, (name, doc) in STORE_GAUGES.items():
            scoped.gauge(name, doc).set(float(st.get(key, 0) or 0))

    hooks.append(sync)


def control_plane_section(store) -> tuple[dict, bool]:
    """The /health ``control_plane`` payload + connected flag, shared by
    the worker status server and the HTTP frontend so the two health
    surfaces can never diverge."""
    st = store.stats()
    connected = bool(st.get("connected"))
    return (
        {
            "connected": connected,
            "outage_seconds": round(float(st.get("outage_seconds", 0.0)), 3),
            "session_rebuilds": int(st.get("reconnects", 0)),
        },
        connected,
    )


def bind_store_gauges(status: "SystemStatusServer | None", store) -> None:
    """Export the process's control-plane connection state on /metrics
    and surface it in /health's ``control_plane`` section. No-op when the
    status server is disabled."""
    if status is None:
        return
    status.store = store
    _bind_store_gauges(status.metrics, status.before_render, store)


def bind_startup_gauges(status: "SystemStatusServer | None", clock) -> None:
    """Export a worker's start-up clock (tracing/startclock.py) on /metrics:
    ``dynamo_worker_startup_seconds{stage}``, a series a stage the worker
    has left, and ``dynamo_worker_start_to_serving_seconds`` once it
    serves: what a planner's scale-up lag and a start-up probe's deadline
    are set from. No-op when the status server is disabled."""
    if status is None:
        return

    def sync() -> None:
        snap = clock.snapshot()
        serving = snap["stage_now"] is None
        for stage, seconds in snap["stages"].items():
            if serving or (seconds and stage != snap["stage_now"]):
                status.metrics.scoped(service="worker", stage=stage).gauge(
                    "worker_startup_seconds",
                    "Seconds of the worker's start-up spent in the stage "
                    "(the stages partition process start to 'serving model')",
                ).set(seconds)
        if serving:
            status.metrics.scoped(service="worker").gauge(
                "worker_start_to_serving_seconds",
                "Seconds from the operating system's start of the worker's "
                "process to 'serving model'",
            ).set(snap["total_s"])
            status.before_render.remove(sync)   # closed: nothing moves again

    status.before_render.append(sync)


class SystemStatusServer:
    def __init__(
        self,
        metrics: MetricsRegistry | None = None,
        host: str = "0.0.0.0",
        port: int = 0,
    ):
        self.metrics = metrics or MetricsRegistry()
        self.host = host
        self.port = port
        self._started_at = time.monotonic()
        # Hooks run before each /metrics render — for exporters whose
        # label sets are dynamic (e.g. per-address breaker gauges, where
        # addresses appear as the egress pool dials new workers) and so
        # cannot pre-bind set_function children.
        self.before_render: list[Callable[[], None]] = []
        # endpoint path -> "ready" | "notready"
        self.endpoint_health: dict[str, str] = {}
        # Store client whose connectivity /health reports (wired by
        # bind_store_gauges); None = no control-plane section.
        self.store = None
        # Extra /health sections: name -> zero-argument callable evaluated
        # per request (the JAX worker reports its device, start-up timings,
        # compile log and device memory here).
        self.health_sections: dict[str, Callable[[], object]] = {}
        self.app = web.Application()
        self.app.router.add_get("/health", self.health)
        self.app.router.add_get("/live", self.live)
        self.app.router.add_get("/metrics", self.prometheus)
        self.app.router.add_get("/traces", self.traces)
        self._runner: web.AppRunner | None = None
        # Per-phase latency histograms ride this registry (scraped by the
        # planner observer alongside the frontend series).
        tracing.get_collector().bind_metrics(self.metrics)

    def set_endpoint_health(self, path: str, ready: bool) -> None:
        self.endpoint_health[path] = "ready" if ready else "notready"

    @property
    def uptime_s(self) -> float:
        return time.monotonic() - self._started_at

    async def start(self) -> None:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        for addr in self._runner.addresses:
            self.port = addr[1]
        log.info("status server on http://%s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner:
            await self._runner.cleanup()

    async def health(self, request: web.Request) -> web.Response:
        ready = all(s == "ready" for s in self.endpoint_health.values())
        status = "healthy" if ready and self.endpoint_health else "starting"
        payload = {
            "status": status,
            "uptime_s": round(self.uptime_s, 3),
            "endpoints": dict(self.endpoint_health),
        }
        if self.store is not None:
            payload["control_plane"], connected = control_plane_section(
                self.store
            )
            if status == "healthy" and not connected:
                # Degraded, NOT unhealthy: the data plane still serves
                # (that is the whole point of ISSUE 15) — stay 200 so
                # orchestrators don't kill a working worker over a store
                # blackout, but make the state visible.
                payload["status"] = status = "degraded"
        for name, section in self.health_sections.items():
            payload[name] = section()
        return web.json_response(
            payload,
            status=200 if status in ("healthy", "degraded") else 503,
        )

    async def live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def prometheus(self, request: web.Request) -> web.Response:
        self.metrics.scoped(service="system").gauge("system_uptime_seconds").set(
            self.uptime_s
        )
        for hook in self.before_render:
            hook()
        return web.Response(body=self.metrics.render(), content_type="text/plain")

    async def traces(self, request: web.Request) -> web.Response:
        return web.json_response(render_traces(request))


def render_traces(request: web.Request) -> dict:
    """Shared ``/traces`` payload (status server + HTTP frontend):
    ``?limit=N`` recent traces, ``?trace_id=...`` to pin one."""
    collector = tracing.get_collector()
    trace_id = request.query.get("trace_id")
    if trace_id:
        traces = collector.traces(trace_id=trace_id)
    else:
        try:
            limit = max(1, min(200, int(request.query.get("limit", "20"))))
        except ValueError:
            limit = 20
        traces = collector.traces(limit=limit)
    return {
        "enabled": tracing.trace_enabled(),
        "buffered_spans": len(collector),
        "stat_spans": len(collector.stats()),
        "capacity": collector.capacity,
        "traces": traces,
    }
