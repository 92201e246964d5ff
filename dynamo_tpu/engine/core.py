"""EngineCore: synchronous continuous-batching scheduler over jitted steps.

The TPU-native analogue of vLLM's engine loop, which the reference only
wraps (`components/backends/vllm`); here it is first-party. One `step()`
is one engine iteration: drain new requests, admit under a free-block
watermark, then either run one ragged prefill wave (prefill-priority,
like vLLM's default scheduler) or one batched decode+sample chain for
every running sequence. Both ride the SAME unified ragged forward
(`model.forward_tokens`): a prefill wave is S sequences with ragged chunk
lengths packed into one token buffer (no per-lane padding), a decode step
is S sequences of q_len 1. Programs are static-shaped — total prefill
tokens snap to `prefill_buckets`, decode width to `decode_buckets` — so
XLA compiles a small fixed set of programs and every later call replays
them.

Design notes:
- Sampling is fused into the decode program (one dispatch, one [B] int
  transfer back per token) with per-lane PRNG derived from (seed, counter)
  inside jit — seeded requests reproduce regardless of batch neighbors.
- Blocks are committed to the allocator exactly when their K/V has been
  written on device, so the KV events this engine emits describe cache
  reality (parity: reference worker KV events, kv_router/publisher.rs).
- Preemption = release everything + token-replay re-prefill (the same
  trick request migration uses across workers, migration.rs).

This module is the step loop: admission, the planners, the dispatchers,
commit, emission, the statistics. What the loop is built from lives beside
it and imports nothing from here: the device programs (engine/programs.py),
the KV that leaves the device (engine/kv_transfer.py, inherited), what an
engine may be built with (engine/options.py) and where weights and cache
are placed (parallel/placement.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu import tracing
from dynamo_tpu.tracing import startclock
from dynamo_tpu.tracing.stepclock import PHASES, StepClock
from dynamo_tpu.engine.block_allocator import DeviceBlockAllocator, OutOfBlocksError
from dynamo_tpu.engine.config import SLAB_KINDS, EngineConfig, ModelConfig
from dynamo_tpu.engine.fair_queue import FairQueue
from dynamo_tpu.engine.prefill_cover import cheapest_cover
from dynamo_tpu.runtime.engine import EngineOverloadedError
from dynamo_tpu.engine.model import embed_forward, expert_call_shape
from dynamo_tpu.engine.kv_transfer import KvTransfer
# chipbench/rehearse_v5e.py reads the two serving programs through this module
# and tests/chipbench/test_chipbench_sdar.py ``_resolve_block_megastep``; tests
# REBIND ``pack_lanes`` here, where ``_dispatch_megastep`` looks it up.
from dynamo_tpu.engine.options import (  # noqa: F401
    _BLOCK_STEP,
    _resolve_block_megastep,
    refuse_mm_embeds,
    resolve,
)
from dynamo_tpu.engine.programs import (  # noqa: F401
    MEGASTEP_WATCH_W, _megastep_body, _prefill_and_sample, _program, compile_programs,
    pack_lanes,
)
from dynamo_tpu.ops import grouped_matmul
from dynamo_tpu.ops.linear_attention import traced_impl as linear_traced_impl
from dynamo_tpu.ops.ssm import traced_impl as ssm_traced_impl
from dynamo_tpu.ops.ragged_attention import traced_impl
from dynamo_tpu.engine.sampler import hidden_at_most
from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics, KvStats, WorkerStats
from dynamo_tpu.spec import SpecConfig, SpecStats, propose_ngram, resolve_spec_config
from dynamo_tpu.parallel.placement import place
from dynamo_tpu.parallel.multihost import (
    fetch_replicated,
    fetch_replicated_many,
    start_host_copy,
)
from dynamo_tpu.llm.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.tokens import TokenBlockSequence, compute_seq_hashes

log = logging.getLogger("dynamo_tpu.engine")


@dataclass(eq=False)
class Sequence:
    """One request's lane. A lane is itself: two sequences are equal only
    where they are the same object, so the planners' ``seq in
    self.running``, ``victim in ready`` and ``self.running.remove(seq)``
    compare identities and build no tuple of 29 fields for every lane
    they pass (1.1 us a comparison, thousands a plan at 128 lanes: PERF.md
    section 6, PRs 39 and 40)."""

    request_id: str
    prompt: list[int]
    sampling: SamplingOptions
    stop: StopConditions
    seed: int
    # Requested top-k logprob alternatives; None = logprobs off.
    logprobs: int | None = None
    # -- device-cache bookkeeping --
    prompt_hashes: list[int] = field(default_factory=list)
    block_ids: list[int] = field(default_factory=list)
    hashed: TokenBlockSequence | None = None   # tokens whose K/V is written
    pinned_hashes: list[int] = field(default_factory=list)
    committed_blocks: int = 0                  # prefix of block_ids committed
    num_cached_tokens: int = 0
    # The WINDOW pool's blocks (a model with sliding_attention layers):
    # block ``win_first + j`` of the sequence lies in that pool's block
    # ``win_ids[j]``; blocks before ``win_first`` slid out of every later
    # query's window and were given back (EngineCore._hold_window).
    win_first: int = 0
    win_ids: list[int] = field(default_factory=list)
    # The lane SLOT of a model with linear layers (their slab's index:
    # model.linear_layer), held from admission to the end, preemption or
    # cancel; -1: none (every other model, and a sequence not running).
    slot: int = -1
    # -- progress --
    prefilled: int = 0      # prompt tokens with K/V written
    processed: int = 0      # all tokens with K/V written
    pending: int | None = None  # sampled, not yet processed
    generated: int = 0
    finish: str | None = None
    cancelled: bool = False
    emitted_first: bool = False
    # Disaggregation: a remote-decode prefill holds its blocks after finish
    # until the decode worker pulls them (reference disagg_serving.md flow).
    hold_blocks: bool = False
    # Multimodal: encoder output rows to splice over placeholder prompt
    # positions ([n_total, h] f32) and their [start, count] spans.
    mm_embeds: Any = None
    mm_positions: list | None = None
    # -- scheduling attribution (sched_admit span endpoints) --
    t_queued: float = 0.0       # wall-clock at enqueue into the scheduler
    t_first_sched: float = 0.0  # first chunk dispatched to the device
    # -- speculative decoding (dynamo_tpu/spec) --
    # Resolved policy (SpecConfig) or None; set once at admission from the
    # engine default + the request's spec_decode override.
    spec: SpecConfig | None = None
    # Every emitted token, in order (the drafter's lookup history beyond
    # the prompt; cleared on preemption — the rebuilt prompt absorbs it).
    out_tokens: list[int] = field(default_factory=list)
    # -- overload robustness (ISSUE 10) --
    # Fairness identity (validated x-tenant-id; "" = default tenant):
    # keys the admission queue's per-tenant DRR.
    tenant_id: str = ""
    # Ordering hint WITHIN the tenant's queue (higher admits first).
    priority: int = 0
    # Absolute wall-clock deadline (time.time() domain): a sequence
    # still QUEUED past it is expired with a typed retryable error
    # frame; admitted sequences always run to completion (expiring a
    # partially-streamed request would break the stream).
    deadline_epoch: float | None = None
    # A block-diffusion model: the prompt's last ``prompt_len % B`` tokens
    # are held back from the prefill wave and open the first block as known
    # places (EngineCore._plan_blocks). 0 for every other model.
    tail: int = 0
    # A block-diffusion lane's PENDING block: the newest block's ``B`` tokens,
    # revealed and streamed, whose final K/V (its clean rows) ride the first
    # pass of the lane's next block. They lie past the cursor (``processed``)
    # until then; a lane that ends, is cancelled or is preempted drops them.
    pending_block: list[int] = field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def wave_len(self) -> int:
        """Prompt tokens a prefill wave runs: all but the held-back tail."""
        return len(self.prompt) - self.tail

    @property
    def prefill_done(self) -> bool:
        return self.prefilled >= self.wave_len

    @property
    def num_computed_tokens(self) -> int:
        """Chunked-prefill cursor: tokens whose K/V is written (cached
        prefix + prompt chunks run so far + generated tokens) — the
        vLLM-vocabulary alias of ``processed``; carries prefill progress
        across mixed steps so a long prompt streams instead of
        monopolizing one."""
        return self.processed


class _NeedDrain(Exception):
    """Plan-time block growth failed while a step is in flight: the
    planner must not preempt over uncommitted state (the victim's emitted
    tokens may still be on device), so the async loop commits the
    in-flight step and re-plans from settled state, where normal
    preemption applies."""


class _PendingFetch:
    """In-flight device outputs of ONE dispatch plus their double-buffered
    D2H copies. Construction enqueues ``copy_to_host_async`` on every
    output array, so by the time :meth:`land` blocks — one full device
    step later under async execution — the bytes have been streaming to
    host while the next step computes. ``sr`` carries the (S, R) reshape
    for sample-width dispatches (the legacy 2-D return shape)."""

    def __init__(self, core: "EngineCore", toks, lps, sr=None, aux=None,
                 expert_stats=None, finishing: int = 0):
        self.core = core
        self.toks = toks
        self.lps = lps
        self.sr = sr
        self.aux = aux
        # (phase, int32 [4] on device); None where the program returned no
        # counts (a model without the sparse layer, the pipeline's programs)
        if expert_stats is not None and expert_stats[1] is None:
            expert_stats = None
        self.expert_stats = expert_stats
        # lanes a decode megastep runs to the end of their budget
        # (_PlannedStep.finishing)
        self.finishing = finishing
        self.no = core._note_dispatch()
        core.clock.in_flight(self.no, toks)
        start_host_copy(toks)
        if expert_stats is not None:
            start_host_copy(expert_stats[1])
        if aux is not None:
            start_host_copy(aux)
        if lps is not None:
            for a in lps:
                start_host_copy(a)

    def land_aux(self):
        """Land the side-channel int array (device-draft round
        accounting); call only after construction with ``aux``."""
        self.core.clock.mark("land")
        aux = fetch_replicated(self.aux)  # dynalint: sync-ok — double-buffered landing point
        self.core.clock.mark("commit")
        return aux

    def land(self):
        core = self.core
        if core._exec_log is not None:
            core._exec_log.append(("land", self.no))
        # The blocking fetch is the step clock's ``land`` phase (the
        # commit wrapper opened it; a merged plan's later parts reopen it).
        core.clock.mark("land")
        toks = fetch_replicated(self.toks)  # dynalint: sync-ok — double-buffered landing point
        if self.expert_stats is not None:
            phase, counts = self.expert_stats
            core.expert_stats[phase] += fetch_replicated(counts)  # dynalint: sync-ok — lands with the tokens
        lps = self.lps
        if lps is not None:
            lps = tuple(fetch_replicated_many(lps))  # dynalint: sync-ok — batched logprob landing
        # ``land`` ends, and with it the dispatch's record on the clock.
        core.clock.landed(self.no)
        if self.sr is not None:
            # fetch_replicated already landed host np arrays; reshape to
            # the legacy 2-D ([S, R], [S, R, ...]) sample-width views.
            S, R = self.sr
            toks = toks.reshape(S, R)
            if lps is not None:
                lps = tuple(a.reshape((S, R) + a.shape[1:]) for a in lps)
        return toks, lps


@dataclass
class _PlannedStep:
    """One planned-and-dispatched engine step awaiting commit.

    The plan/dispatch/commit split is the async execution tentpole: the
    plan side assembles host arrays and enqueues the device program(s);
    the commit side lands the double-buffered outputs and applies every
    piece of host bookkeeping (block commits, cursor advances, stop
    scans, stream emission). On the synchronous loop commit runs
    immediately after plan — the classic loop. On the pipelined loop
    (``EngineCore.pipelined``) the engine keeps ONE of these in flight
    and plans step N+1 against the optimistic ``adv`` overlays before
    committing step N.
    """

    core: "EngineCore"
    commit_fn: Callable[[], list]
    # Optimistic per-lane deltas this step will apply once committed:
    # request_id -> (d_prefilled, d_processed, d_generated). The next
    # plan reads real-state + adv while this step is in flight.
    adv: dict[str, tuple[int, int, int]] = field(default_factory=dict)
    # Device-resident sampled tokens of this step (flat [S*R] or
    # [n_steps, B]) + request_id -> flat index of each lane's newest
    # token: the next plan's token buffer gathers from here on device.
    feed_tokens: Any = None
    # feed_tokens padded to the engine's feedback width (EngineCore._fed).
    feed_padded: Any = None
    feed_index: dict[str, int] = field(default_factory=dict)
    # request_id -> (start, stride, count): this step's FULL per-lane
    # emission as flat indices into feed_tokens, in stream order. Set
    # only by deterministic plans (exactly the ones the async loop may
    # plan over); a device-drafting lane's next plan gathers these into
    # its history ring so the on-device drafter sees in-flight tokens
    # (ISSUE 18).
    feed_series: dict[str, tuple[int, int, int]] = field(default_factory=dict)
    # False when any lane's advance is data-dependent (verify rows with
    # live drafts): the next plan must commit this step first.
    deterministic: bool = True
    committed: bool = False
    # Lanes this step's decode megastep runs to the end of their budget:
    # still in ``running`` while it is in flight, and decode-ready no more.
    finishing: int = 0

    def commit(self) -> list:
        if self.committed:
            return []
        self.committed = True
        core = self.core
        t0 = core.clock.mark("land")
        out = self.commit_fn()
        core.clock.mark("commit")  # a boundary only where nothing was landed
        core.exec_stats["commits"] += 1
        # From the landing's start to the step clock's next boundary.
        core.clock.close_at_next("engine_commit", t0, {"outputs": len(out)})
        return out


def _lp_entry(token: int, chosen, top_ids, top_lps, k: int) -> dict:
    """Host-side logprob record for one emitted token: the device returns
    LOGPROBS_K alternatives; slice to the k the request asked for.
    ``top`` is [[token_id, logprob], ...] (descending) — NOT a dict: the
    data plane's msgpack decoder rejects integer map keys."""
    k = min(k, len(top_ids))
    return {
        "token_id": token,
        "logprob": float(chosen),
        "top": [[int(top_ids[j]), float(top_lps[j])] for j in range(k)],
    }


@dataclass
class _RaggedBatch:
    """Host-assembled inputs of one ragged forward over arbitrary rows
    (:meth:`EngineCore._assemble_ragged`): the iteration the plain
    single-step dispatch runs, and the universal megastep's first."""

    T: int
    R: int
    tokens: np.ndarray
    positions: np.ndarray
    write_pages: np.ndarray
    write_offs: np.ndarray
    kv_lens: np.ndarray
    tables: np.ndarray
    cu: np.ndarray
    last_rows: np.ndarray
    gather: np.ndarray
    counters: np.ndarray
    seeds: np.ndarray
    temp: np.ndarray
    top_k: np.ndarray
    top_p: np.ndarray
    feed_idx: np.ndarray | None
    mm_embeds: np.ndarray
    mm_mask: np.ndarray
    need_mask: bool
    want_lp: bool
    want_mm: bool


class EngineCore(KvTransfer):
    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        params: Any = None,
        seed: int = 0,
        eos_token_ids: tuple[int, ...] = (),
        on_stored: Callable[[list[int], int | None], None] | None = None,
        on_removed: Callable[[list[int]], None] | None = None,
        mesh: Any = None,
        sp_mesh: Any = None,
        pp_mesh: Any = None,
        on_tier_stored: Callable[[list[int], int | None, str], None] | None = None,
        on_tier_removed: Callable[[list[int], str], None] | None = None,
    ):
        """``mesh`` (a jax.sharding.Mesh with axes ("dp", "tp")) turns on
        in-engine model parallelism: params/cache shard per
        parallel/sharding.py (megatron TP over ICI; MoE experts over the
        same axis), decode batches shard over dp. The reference only plumbs
        tp_size flags to its engines (vllm/args.py:239-258); here the
        partitioning is first-party. ``pp_mesh`` (axes ("pp",)) selects
        pipeline parallelism instead: layer-staged GPipe prefill waves and
        wavefront decode chains (parallel/pipeline.py)."""
        model_cfg, engine_cfg = resolve(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh)
        bs = engine_cfg.block_size
        self._sched_chunked = engine_cfg.scheduling == "chunked"
        # Which step loop serves: one algorithm (plan / dispatch /
        # commit) whose commit is immediate or deferred one call.
        # Deferred — one planned step in flight, the host's work hidden
        # behind the device's — unless what the engine was built with
        # forces "immediate": an sp mesh (ring prefill commits in
        # place), or drafts proposed from host history (a drafter one
        # step stale proposes nothing: the stream stays bit-identical
        # but no verify row forms). EngineConfig.async_exec pins it.
        host_drafted = (
            engine_cfg.spec_decode != "off"
            and not engine_cfg.spec_device_draft
        )
        self.pipelined: bool = (
            sp_mesh is None and not host_drafted
            if engine_cfg.async_exec is None
            else engine_cfg.async_exec
        )
        # Verify-row sample width: STATIC per engine so the compiled
        # program set stays O(buckets x widths x variants), not O(draft
        # lengths). Rows with shorter drafts pad the sample gather with
        # duplicate reads.
        self._spec_R = engine_cfg.spec_k + 1
        self._spec_default = (
            SpecConfig(
                method=engine_cfg.spec_decode,
                k=engine_cfg.spec_k,
                ngram_min=engine_cfg.spec_ngram_min,
                ngram_max=engine_cfg.spec_ngram_max,
                window=engine_cfg.spec_window,
                device=engine_cfg.spec_device_draft,
            )
            if engine_cfg.spec_decode != "off"
            else None
        )
        # On-device drafting (ISSUE 18): per-lane history ring width.
        # The host drafter is handed the last window + ngram_max tokens
        # (`_draft_for`), so a ring of exactly that width sees the same
        # candidate set — device and host proposals cannot diverge.
        self._spec_device = (
            engine_cfg.spec_decode != "off" and engine_cfg.spec_device_draft
        )
        self._ring_H = engine_cfg.spec_window + engine_cfg.spec_ngram_max
        self.spec_stats = SpecStats()
        self.cfg = model_cfg
        self.engine = engine_cfg
        self.eos_token_ids = set(eos_token_ids)
        self.mesh = mesh
        self.pp_mesh = pp_mesh
        (self.params, self.cache, self._dp, self._pp, self._pp_micro,
         self._batch_shardings) = place(model_cfg, engine_cfg, params, seed, mesh, pp_mesh)
        startclock.mark("engine_init")
        # A window model's blocks are found again by no one (prefix caching
        # is off for it): their KV events are not published.
        self.allocator = DeviceBlockAllocator(
            engine_cfg.num_kv_blocks,
            bs,
            enable_prefix_caching=engine_cfg.enable_prefix_caching,
            on_stored=None if model_cfg.windowed else on_stored,
            on_removed=None if model_cfg.windowed else on_removed,
        )
        # The window pool: block ids of its own over the window layers'
        # page arrays (model.cache_for_blocks), never hashed, never cached.
        self.window_allocator = DeviceBlockAllocator(
            engine_cfg.num_window_blocks, bs, enable_prefix_caching=False,
        ) if model_cfg.windowed else None
        self._init_tiers(on_tier_stored, on_tier_removed)
        # The free lane slots of a model with linear layers (None: no slab):
        # one a sequence that runs; the slab's last slot is the garbage slot.
        self._free_slots: list[int] | None = (
            list(range(engine_cfg.max_num_seqs - 1, -1, -1)) if model_cfg.has_slab else None)

        self._inbox: deque[Sequence] = deque()   # thread-safe enqueue
        # Admission queue: per-tenant deficit-round-robin over prompt
        # token cost (ISSUE 10). With fair_scheduling off — the default —
        # every request maps to one tenant and DRR degenerates to the
        # exact FIFO this deque-shaped field has always been. Touched
        # only under _step_lock (intake goes through _inbox).
        self.waiting: FairQueue = FairQueue(
            quantum=engine_cfg.fair_quantum_resolved,
            fair=engine_cfg.fair_scheduling,
            cost_fn=lambda s: s.prompt_len,
        )
        self.running: list[Sequence] = []
        # Typed rejections produced by the queue sweeps (deadline expiry)
        # during planning, delivered with the step's outputs.
        self._shed_outputs: list[tuple[Sequence, LLMEngineOutput]] = []
        # Deadline sweeps are wall-clock; multihost engines disable them
        # (leader and followers would expire divergently — same class of
        # restriction as embeddings there). Likewise the bounded-queue
        # ceiling: leader (staged intake) and follower (direct inbox)
        # queue-length views differ at add time, so the rejection would
        # not replay identically — multihost forces it off.
        self.enforce_deadlines = True
        self._max_waiting = engine_cfg.max_waiting
        self.iterations = 0
        # Step-level stat spans (engine_megastep, engine_mixed_step, ... with
        # token counts). record() on a disabled tracer is a no-op, and the
        # collector's deque.append is atomic — safe from the engine thread.
        self._tracer = tracing.get_tracer("engine")
        # One clock for the step loop: every phase boundary of step() is
        # one reading of it (counters on /metrics, ``engine/<phase>``
        # annotations in a profile — tracing/stepclock.py).
        self.clock = StepClock(self._tracer)
        # What one prefill wave of each token bucket took on this model
        # and device, in ms (``warm_up`` times each compiled program once
        # and installs the table when it is over). Empty until then, and
        # in an engine that skipped warm-up: the waves planner then takes
        # every waiting token and pads to the next bucket. With a table
        # it picks the cheapest set of waves (``_plan_prefill_wave``).
        self.prefill_bucket_ms: dict[int, float] = {}
        self.prefill_waves: dict[int, int] = {}   # bucket -> waves run
        # (host seconds, dispatches) the host's cost per dispatch is
        # counted from: warm-up moves it past its compiles.
        self._host_floor_base = (0.0, 0)
        # Queue-wait stat spans live under their own service so the
        # request-waterfall sched_admit twin (TpuEngine, service
        # "engine") doesn't double-count the histogram series.
        self._sched_tracer = tracing.get_tracer("sched")
        self._req_counter = 0
        self._lock = threading.Lock()
        # Serializes step() against cross-thread cache surgery
        # (import/export of disaggregated KV blocks).
        self._step_lock = threading.Lock()
        # A context every step() runs in, entered and left on the engine
        # thread under the step lock: the worker gathers a step's KV
        # events in it and hands them to its loop in one hop
        # (backends/jax/main.py:StepKvEvents).
        self.step_scope: Callable[[], Any] = contextlib.nullcontext
        self._embed_lock = threading.Lock()
        self._held: dict[str, Sequence] = {}
        # Chunk-commit notification hook: called as
        # ``on_chunk_commit(request_id, committed_blocks, done)`` each
        # time a hold_blocks sequence commits prefill chunks (and once
        # with done=True at finish). Invoked UNDER the step lock on the
        # engine thread — the callback must be non-blocking and must not
        # re-enter the core (hop to the event loop to publish).
        self.on_chunk_commit = None
        # Hold deadlines (monotonic): a decode-side timeout must not pin
        # prefill blocks forever. Touched by the transfer endpoints, swept
        # at the top of each step (before admission needs the blocks).
        self._held_deadline: dict[str, float] = {}
        # Disagg transfer accounting (imported vs dropped must be
        # distinguishable — a half-dropped transfer silently recomputes on
        # the decode side). Surfaced via metrics().
        self.transfer_stats = {
            "transfers": 0,
            "imported_blocks": 0,
            "skipped_cached_blocks": 0,
            "dropped_blocks": 0,
            "partial_transfers": 0,
        }
        self._init_counters()
        # -- async pipelined execution (plan/dispatch/commit) ---------------
        # At most ONE step is in flight; its _PlannedStep carries the
        # optimistic advances the next plan overlays and the
        # device-resident sampled tokens the next dispatch gathers from.
        self._inflight: _PlannedStep | None = None
        # Crash/stall flight recorder (ISSUE 13): one record per step
        # with outputs — step shape, lane cursors, cumulative dispatch
        # counters — dumped to a redacted JSON artifact on SIGTERM
        # drain, stall-deadline fire, breaker open, and chaos kill. The
        # record is a host-side dict append on the COMMIT side (never
        # plan/dispatch); the backend CLI renames it to the worker id.
        from dynamo_tpu.obs.flight_recorder import FlightRecorder

        self.flight = FlightRecorder(f"engine-{id(self) & 0xFFFF:04x}")
        # Test hook: set to [] to record ("dispatch", n) / ("land", n)
        # events — the pipelining contract is that dispatch n+1 precedes
        # the landing of step n's outputs in steady-state decode.
        self._exec_log: list[tuple[str, int]] | None = None
        self._dispatch_no = 0
        # Decode-ready lanes (running, prefill done) as the step's planner
        # counted them: what a wave leaves waiting (_mark_dispatch).
        self._decode_ready = 0
        # The serving programs (engine/programs.py). Attributes, read at every
        # dispatch: chipbench/rehearse_v5e.py rebinds two on a live engine.
        (self._prefill, self._ring, self._decode, self._fused, self._drafted,
         self._prefill_pp, self._decode_pp, self._feed, self._feed_pad) = compile_programs(
            model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh, self._pp_micro)
        # (a block-diffusion lane yields its blocks' places a dispatch)
        blk = model_cfg.block_length
        self._feed_width = (
            (engine_cfg.megastep // (model_cfg.denoising_steps + 1) * blk if blk
             else engine_cfg.megastep * self._spec_R)
            * max(engine_cfg.decode_buckets[-1], engine_cfg.prefill_batch)
        )
        # What a megastep is handed where no lane is fed: zeros of the
        # padded source's shape, placed as a step's sampled tokens are
        # (_replicate_out), so that the megastep compiles once for both.
        self._no_feed = jnp.zeros(self._feed_width, jnp.int32)
        if mesh is not None or pp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._no_feed = jax.device_put(
                self._no_feed, NamedSharding(mesh or pp_mesh, PartitionSpec()))
        self.sp_mesh = sp_mesh

    def _init_counters(self) -> None:
        """What the engine counts, zeroed: read by the statistics methods
        at the end of this class and by the worker's /metrics."""
        # What the sparse layers counted, by the program that ran them:
        # int64 [5] each (model._shared_sparse_mlp), landed with the
        # tokens of every dispatch.
        self.expert_stats = {
            phase: np.zeros(5, np.int64) for phase in ("decode", "prefill")
        }
        # Where the rows of a model with conv layers found the state they
        # read (model.conv_layer), counted at dispatch on the host: the
        # pages written by an earlier iteration of the same megastep, by
        # the sequence's own previous dispatch, or by whoever filled a
        # shared block (a prefix hit; a resume after preemption).
        self.conv_state_reads = {
            source: 0 for source in ("same_step", "earlier_dispatch", "prefix_hit")
        }
        # Scheduler observability (status-server gauges + bench
        # attribution): the chunked-vs-waves decision needs visible queue
        # depth, per-step budget utilization, and preemption counts.
        self.sched_stats = {
            "preemptions": 0,
            "mixed_steps": 0,
            "last_step_batched_tokens": 0,
            "last_step_budget_utilization": 0.0,
            "chunked_prefills_in_flight": 0,
            # Overload counters (ISSUE 10): bounded-queue refusals at
            # add_request and queued requests expired past deadline.
            "shed_total": 0,
            "deadline_expired_total": 0,
        }
        # Execution-pipeline counters (status surface + tests): drains
        # count forced pipeline flushes (block pressure mid-plan).
        self.exec_stats = {
            "dispatches": 0,
            # Dispatches enqueued while another step was in flight (the
            # ``pipelined`` attribute of the engine/dispatch annotation).
            "pipelined_dispatches": 0,
            "commits": 0,
            "drains": 0,
            # Megastep observability: dispatches that fused k > 1 decode
            # iterations vs everything else (prefill waves, mixed steps,
            # verify rows, k == 1 decode), plus committed (client-
            # visible) tokens — the dispatches_per_token gauge divides
            # these, and < 1.0 is the amortization working.
            "megastep_dispatches": 0,
            "single_step_dispatches": 0,
            # Dispatches by the sampler's branch (_count_sampling): every
            # lane at temperature 0, or some lane draws.
            "dispatches_greedy": 0,
            "dispatches_drawn": 0,
            "committed_tokens": 0,
            # Of those, the tokens decode iterations gave: all but each
            # stream's first (the denominator of the clock's lane-seconds).
            "decode_tokens_committed": 0,
            # Universal megastep (ISSUE 12): dispatches that fused a
            # ragged mixed/verify first iteration with scanned decode
            # continuation, and batches forced back to k=1 because a
            # lane's stop watch overflowed the device's MEGASTEP_WATCH_W
            # slots (the one documented un-fused path).
            "fused_mixed_dispatches": 0,
            "megastep_forced_single": 0,
            # Pipeline parallelism (ISSUE 20): decode dispatches that
            # fused k > 1 wavefront iterations across the pipe vs pp
            # chains forced to k == 1 (watch overflow / budget edge —
            # those pay the fill/drain bubble PER TOKEN).
            "pp_fused_dispatches": 0,
            "pp_forced_single": 0,
            # Occupancy (ISSUE 24): live against padded lanes per decode
            # dispatch and real against bucket tokens per ragged (prefill
            # / mixed) dispatch, counted where the batch is built; lane-
            # iterations a decode megastep issued against those that gave
            # a client a token, counted where the chain is committed.
            "decode_live_lanes": 0,
            "decode_padded_lanes": 0,
            "megastep_issued_lane_iters": 0,
            "megastep_useful_lane_iters": 0,
            "ragged_real_tokens": 0,
            "ragged_bucket_tokens": 0,
            # Prefill waves the planner ended before the waiting tokens
            # did, to ride a smaller bucket than they would have padded
            # to (ISSUE 30); ``prefill_waves`` counts all, by bucket.
            "prefill_cut_waves": 0,
            # Looped stacks (ISSUE 27): passes over the layer stack, per
            # live lane and iteration (_mark_dispatch).
            "layer_passes": 0,
            # Window-pool blocks given back while their sequence went on
            # (_release_window_behind); 0 for a model without such a pool.
            "window_blocks_released": 0,
            # Tokens a sequence of a model with linear layers ran AGAIN after a
            # preemption: no block holds its state, so it replays from
            # position 0 (_preempt); 0 while nothing is preempted.
            "state_replayed_tokens": 0,
            # A block-diffusion model (_plan_blocks): denoising passes counted
            # once a live lane a pass; blocks whose clean rows ran (beside a
            # later block's first pass: no forward of their own); the live
            # rows of those passes, a pending block's clean rows among them,
            # and the rows that went through the head and the sampler
            # (sampler.hidden_at_most a lane a pass); blocks revealed and
            # kept; places revealed on the device, by the rule that revealed
            # them; places generated and not kept (after a cut, or in a block
            # run past a stop only the host saw).
            "denoise_forwards": 0,
            "commit_forwards": 0,
            "block_rows": 0,
            "head_rows": 0,
            "blocks_committed": 0,
            # kept blocks whose clean rows rode a next block's first pass and
            # moved the cursor on; revealed blocks whose clean rows never ran
            # (the lane ended, was cancelled or was preempted first)
            "block_clean_folded": 0,
            "block_pending_dropped": 0,
            "places_revealed_threshold": 0,
            "places_revealed_quota": 0,
            "block_places_discarded": 0,
        }
        # Admission-time prefix-cache accounting (kv_prefix_cache_admitted_*
        # gauges). Separate from the allocator's match_prefix counters:
        # those count router/disagg probes, these count admitted sequences
        # whose prefix (device cache + host-tier onboard) was served.
        self._admit_prefix_queries = 0
        self._admit_prefix_hits = 0
        self._ring_prefills = 0  # observability: ring-path invocations

    # -- request intake (any thread) --------------------------------------

    def add_request(self, pre: PreprocessedRequest) -> Sequence:
        limit = self._max_waiting
        if limit and (len(self._inbox) + len(self.waiting)) >= limit:
            # Bounded admission queue (backpressure): refuse with the
            # typed RETRYABLE shed error — on the wire this becomes the
            # same retry-elsewhere shape as the PR 6 drain refusal, so
            # migration moves the request to a less-loaded instance
            # instead of letting this queue grow without bound. The
            # length read is approximate under concurrent intake; the
            # ceiling is a pressure valve, not an exact capacity.
            with self._lock:
                self.sched_stats["shed_total"] += 1
            raise EngineOverloadedError(
                f"scheduler queue full ({limit} requests waiting); "
                f"retry on another instance"
            )
        with self._lock:
            self._req_counter += 1
            n = self._req_counter
        seed = pre.sampling.seed if pre.sampling.seed is not None else n
        # Device seed arrays are int32; fold arbitrary (64-bit) client seeds
        # into range instead of letting numpy raise OverflowError mid-step.
        seed = (seed ^ (seed >> 31)) & 0x7FFFFFFF
        seq = Sequence(
            request_id=pre.request_id or f"req-{n}",
            prompt=list(pre.token_ids),
            sampling=pre.sampling,
            stop=pre.stop,
            seed=seed,
            logprobs=pre.output.logprobs,
        )
        if not seq.prompt:
            raise ValueError("empty prompt")
        if self.cfg.block_length:
            self._refuse_block_request(pre)
            seq.tail = seq.prompt_len % self.cfg.block_length
        limit = self.engine.max_model_len
        if seq.prompt_len >= limit:
            raise ValueError(
                f"prompt of {seq.prompt_len} tokens exceeds max_model_len {limit}"
            )
        # Clamp the generation budget to the context window (vLLM semantics).
        budget = limit - seq.prompt_len
        if seq.stop.max_tokens is None or seq.stop.max_tokens > budget:
            seq.stop = type(seq.stop)(
                max_tokens=budget,
                min_tokens=seq.stop.min_tokens,
                stop=seq.stop.stop,
                stop_token_ids=seq.stop.stop_token_ids,
                ignore_eos=seq.stop.ignore_eos,
            )
        if (pre.kv_transfer_params or {}).get("do_remote_decode"):
            seq.hold_blocks = True
        # Per-request speculation: the request's spec_decode dict overrides
        # the engine default (method "off" disables; k clamps to the
        # engine's static spec_k). Bad configs reject HERE, not at the
        # first verify step.
        seq.spec = resolve_spec_config(
            self._spec_default, pre.spec_decode, self.engine.spec_k
        )
        if seq.spec is not None and self.pp_mesh is not None:
            raise ValueError(
                "speculative decoding under pipeline parallelism is not "
                "wired yet (route spec requests to a tp/dp worker)"
            )
        if pre.mm and pre.mm.get("embeds") is not None:
            refuse_mm_embeds(self.cfg)
            if self.pp_mesh is not None:
                # Reject at admission (a NotImplementedError inside the
                # prefill wave would fail every co-scheduled request).
                raise ValueError(
                    "multimodal embedding splice under pipeline parallelism "
                    "is not wired yet (route mm requests to a tp/dp worker)"
                )
            embeds = np.frombuffer(pre.mm["embeds"], np.float32).reshape(
                tuple(pre.mm["embeds_shape"])
            )
            if embeds.shape[1] != self.cfg.hidden_size:
                raise ValueError(
                    f"multimodal embeds of width {embeds.shape[1]} != "
                    f"hidden_size {self.cfg.hidden_size}"
                )
            positions = [list(p) for p in pre.mm["positions"]]
            need_rows = sum(cnt for _, cnt in positions)
            if embeds.shape[0] < need_rows:
                # Reject HERE, not as an IndexError inside the prefill
                # wave (which would fail every co-scheduled request).
                raise ValueError(
                    f"multimodal embeds have {embeds.shape[0]} rows but the "
                    f"placeholder spans need {need_rows}"
                )
            seq.mm_embeds = embeds
            seq.mm_positions = positions
        # Overload metadata (ISSUE 10): fairness identity + deadline.
        # A deadline_ms budget with no frontend-stamped epoch starts the
        # clock here (direct-engine callers and tests).
        seq.tenant_id = pre.tenant_id or ""
        seq.priority = pre.priority or 0
        if pre.deadline_epoch is not None:
            seq.deadline_epoch = pre.deadline_epoch
        elif pre.deadline_ms is not None and pre.deadline_ms > 0:
            seq.deadline_epoch = time.time() + pre.deadline_ms / 1000.0
        seq.t_queued = time.time()
        self._enqueue(seq)
        return seq

    def _refuse_block_request(self, pre: PreprocessedRequest) -> None:
        """What a request may not ask of a block-diffusion model, by name:
        the places of a block are revealed surest first, not left to right,
        so a penalty over "the tokens so far" has no order to follow, and
        ``n`` samples of one prompt are ``n`` requests."""
        sampling = pre.sampling
        asked = {
            "frequency_penalty": sampling.frequency_penalty != 0.0,
            "presence_penalty": sampling.presence_penalty != 0.0,
            "repetition_penalty": sampling.repetition_penalty != 1.0,
            "n": sampling.n != 1,
            "spec_decode": (pre.spec_decode or {}).get("method", "off") != "off",
        }
        for option, was in asked.items():
            if was:
                raise ValueError(
                    f"{option} is not carried by {self.cfg.name!r}: " + _BLOCK_STEP
                    + "its places are revealed surest first, not left to right")

    def _enqueue(self, seq: Sequence) -> None:
        """Hand a validated sequence to the scheduler (overridden by the
        multihost LeaderCore to stage intake until it is journaled)."""
        self._inbox.append(seq)

    def cancel_request(self, seq: Sequence) -> None:
        """Cancel hook (overridden by the multihost LeaderCore: cancels
        must become visible to the scheduler only once journaled, or
        leader and followers would diverge)."""
        seq.cancelled = True

    # -- scheduling --------------------------------------------------------

    def has_work(self) -> bool:
        # An in-flight step is work: its outputs (possibly a stream's
        # final tokens) are not committed until the next step() call.
        return bool(
            self._inbox or self.waiting or self.running
            or self._inflight is not None
        )

    # -- optimistic overlays (async planning) -------------------------------

    def _adv3(self, seq: Sequence) -> tuple[int, int, int]:
        """Optimistic (prefilled, processed, generated) deltas the
        in-flight step will apply to this sequence once committed —
        (0, 0, 0) with an empty pipeline, so every plan-time computation
        reads ``real + _adv3`` and is bit-identical to the classic
        synchronous loop."""
        if self._inflight is None:
            return (0, 0, 0)
        return self._inflight.adv.get(seq.request_id, (0, 0, 0))

    def _eff_prefill_done(self, seq: Sequence) -> bool:
        return seq.prefilled + self._adv3(seq)[0] >= seq.wave_len

    def _eff_processed(self, seq: Sequence) -> int:
        return seq.processed + self._adv3(seq)[1]

    def _eff_generated(self, seq: Sequence) -> int:
        return seq.generated + self._adv3(seq)[2]

    def _eff_block_start(self, seq: Sequence) -> int:
        """First place of a block-diffusion lane's next block: past the
        cursor, the pending block and the blocks of the step in flight
        (:meth:`_eff_processed` for every other model, which has none)."""
        return self._eff_processed(seq) + len(seq.pending_block)

    def _feed_src(self, seq: Sequence) -> int | None:
        """Flat index of this lane's newest sampled token in the in-flight
        step's device output, or None when the pending token is committed
        host-side."""
        if self._inflight is None:
            return None
        return self._inflight.feed_index.get(seq.request_id)

    def _feed_series(self, seq: Sequence) -> tuple[int, int, int] | None:
        """The in-flight step's FULL emission for this lane as an
        arithmetic series of flat device-output indices
        (start, stride, count), or None. Where :meth:`_feed_src` feeds
        one pending token into the next plan's token buffer, this feeds
        the whole in-flight tail into a device-drafting lane's history
        ring — the pending token AND the draft context live on device,
        so the drafter matches against up-to-the-dispatch history
        instead of the stale host-visible tail (ISSUE 18: a
        device-drafting lane no longer needs the pipeline barrier host
        drafting implied)."""
        if self._inflight is None:
            return None
        return self._inflight.feed_series.get(seq.request_id)

    def _feed_source(self) -> jax.Array:
        """The in-flight step's sampled output as a feed index names it:
        flat, and padded once, on first use, to the engine's one feedback
        width."""
        plan = self._inflight
        if plan.feed_padded is None:
            plan.feed_padded = self._feed_pad(
                plan.feed_tokens,
                width=max(self._feed_width, plan.feed_tokens.size),
            )
        return plan.feed_padded

    def _fed(self, host_tokens: jax.Array, src_idx: np.ndarray) -> jax.Array:
        """``host_tokens`` with the slots ``src_idx`` names (>= 0: a flat
        index into the in-flight step's sampled output) overridden on
        device by those just-sampled ids — enqueued on the device
        stream, never blocking. A ragged dispatch's token buffer; a
        megastep gathers inside its own program (:func:`unpack_lanes`)."""
        return self._feed(self._feed_source(), host_tokens, jnp.asarray(src_idx))

    def _count_sampling(
        self, temp: np.ndarray, top_k: np.ndarray, top_p: np.ndarray
    ) -> bool:
        """Count a dispatch by the branch its sampler takes on the device
        (``sampler._sample``: the arg-max alone where no lane of the batch
        draws, padding included) and return its ``need_mask``: whether a
        lane that DRAWS asks for top-k / top-p. A lane at temperature 0
        gets its arg-max whatever else it asks for, so a greedy batch
        never compiles the masked variant."""
        draws = temp > 0.0
        self.exec_stats["dispatches_drawn" if draws.any() else "dispatches_greedy"] += 1
        return bool((draws & ((top_k > 0) | (top_p < 1.0))).any())

    def _note_dispatch(self) -> int:
        """Dispatch-side bookkeeping for the pipelining invariants: the
        sequence number (the one :meth:`_mark_dispatch` gave the step
        clock's record) feeds the test hook: the async contract is that
        dispatch N+1 precedes the landing of step N's outputs."""
        self._dispatch_no += 1
        self.exec_stats["dispatches"] += 1
        if self._inflight is not None:
            self.exec_stats["pipelined_dispatches"] += 1
        if self._exec_log is not None:
            self._exec_log.append(("dispatch", self._dispatch_no))
        return self._dispatch_no

    def _mark_dispatch(
        self, kind: str, lanes: int, width: int, k: int, real: int, padded: int,
        **attrs: Any,
    ) -> None:
        """Open the step clock's ``dispatch`` phase (the jitted call, until
        it returns). Its profile annotation carries the dispatch's shape:
        kind (prefill / decode / megastep / mixed), live lanes against
        the padded width, fused iterations, the passes each iteration
        makes over the layer stack, real against padded tokens,
        whether a step was in flight when it was enqueued, and the
        attention implementation this process's programs of that shape
        were traced with (``attn``: the decode shape's for decode
        iterations, the ragged one's for a prefill wave) and, for a
        sparse model, the path its expert products got (``experts``: a
        wave's for a prefill dispatch, a step's otherwise). ``attrs`` adds
        what only one kind of dispatch has (a prefill wave's ``cover``).
        ``no`` is the number :meth:`_note_dispatch` is about to give it.

        The clock opens the dispatch's record with the decode-ready lanes
        it carries and those it leaves waiting (counted by the planner,
        not walked): a wave carries none and every decode-ready lane waits
        behind it; a decode step carries its lanes and leaves none (a
        lane it does not carry is one its predecessor finishes); a mixed
        step carries the decode-ready lanes among its rows.

        Also counts ``layer_passes``: a pass over the stack for each live
        lane of each fused iteration (a prefill wave: each sequence,
        once). Over the committed tokens it reads ``ut_steps`` where no
        iteration is wasted; a looped model with adaptive exit would
        read less."""
        ut = self.cfg.ut_steps
        self.exec_stats["layer_passes"] += lanes * k * ut
        if kind in ("megastep", "decode"):
            carried, waiting = lanes, 0
        else:
            carried = 0 if kind == "prefill" else min(lanes, self._decode_ready)
            waiting = self._decode_ready - carried
        self.clock.dispatch_begin(
            self._dispatch_no + 1, kind, carried, waiting,
            lanes=lanes, width=width, k=k, ut_steps=ut,
            real=real, padded=padded, pipelined=self._inflight is not None,
            attn=traced_impl(
                ("latent-" if self.cfg.latent else "block-" if self.cfg.block_length else "")
                + ("ragged" if kind == "prefill" else "decode")
            ),
            attention=self.cfg.attention,
            **({"linear": linear_traced_impl("scan" if kind == "prefill" else "step")}
               if self.cfg.linear else {}),
            **({"ssm": ssm_traced_impl("scan" if kind == "prefill" else "step")}
               if self.cfg.ssm else {}),
            **self._window_traced(kind),
            **self._experts_traced(kind, padded, width),
            **attrs,
        )

    def _window_traced(self, kind: str) -> dict[str, Any]:
        """What only a window model's dispatch annotation has: the window,
        the query heads of its full / window layers, and what its window
        calls of that shape were traced with."""
        if not self.cfg.windowed:
            return {}
        heads = {k: self.cfg.heads_of(self.cfg.layers_of(k)[0])
                 for k in ("attention", "window") if self.cfg.layers_of(k)}
        return {
            "window": self.cfg.sliding_window,
            "heads": "/".join(str(n) for n in heads.values()),
            "attn_window": traced_impl(
                ("window-gqa-" if self.cfg.wide_key else "window-")
                + ("ragged" if kind == "prefill" else "decode")),
        }

    def _experts_traced(self, kind: str, tokens: int, width: int = 1) -> dict[str, str]:
        """``{"experts": path}`` of a sparse model's dispatch, for its
        annotation: what this process's sparse layers of that shape were
        traced with (a prefill dispatch of ``tokens`` rows: a wave's where
        it is wide enough for one; every other dispatch a step's, but a
        block-diffusion model's, whose ``width`` lanes bring a block of rows
        each and may be as wide as a wave)."""
        if not self.cfg.shared_sparse:
            return {}
        rows = tokens if kind == "prefill" else (
            width * self.cfg.block_length if self.cfg.block_length else 1)
        return {"experts": grouped_matmul.traced_impl(expert_call_shape(rows))}

    def _bucket_for(self, n: int) -> int:
        """The token bucket a ragged dispatch of ``n`` tokens pads to:
        the smallest that holds them. How many tokens a prefill wave
        takes, and so which bucket it rides, is the planner's choice
        (:meth:`_prefill_cover`); this only rounds what it took."""
        for b in self.engine.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(f"{n} exceeds largest prefill bucket")

    def count_host_floor_from_here(self) -> None:
        """Start the host's cost per dispatch (:meth:`host_floor_ms`) at
        this moment: warm-up calls it when its compiles are over and its
        waves are about to be timed, so that the mean is of dispatches as
        serving makes them."""
        self._host_floor_base = (
            self._host_seconds(), self.exec_stats["dispatches"]
        )

    def _host_seconds(self) -> float:
        """The step clock's seconds in the phases that block on host
        work, but for ``dispatch``: the jitted call returns at once unless
        it traces and compiles first, which a program left to its first
        use does for seconds (logprobs, masked sampling) and which is no
        part of what a dispatch costs from then on."""
        return sum(
            sec for phase, sec in self.clock.seconds().items()
            if PHASES[phase] == "host" and phase != "dispatch"
        )

    def host_floor_ms(self) -> float:
        """Host ms a dispatch has cost so far: the step clock's host
        seconds (:meth:`_host_seconds`; /metrics has them as
        ``step_phase_seconds_total{blocks="host"}``) over the dispatches
        made, counted from the end of warm-up's compiles. The next wave
        cannot reach the device sooner, so no wave is predicted to cost
        less."""
        host0, n0 = self._host_floor_base
        n = self.exec_stats["dispatches"] - n0
        if n <= 0:
            return 0.0
        return 1e3 * (self._host_seconds() - host0) / n

    def _prefill_cover(self, tokens: int) -> tuple[int, ...]:
        """The buckets of the waves that should prefill ``tokens`` waiting
        tokens, largest first: the cheapest cover by the measured table
        and the host's floor (``engine/prefill_cover.py``; the floor is
        taken to a tenth of a ms, so the search is memoised while it
        holds still). Empty without a table."""
        if not self.prefill_bucket_ms:
            return ()
        return cheapest_cover(
            tokens,
            tuple(sorted(self.prefill_bucket_ms.items())),
            round(self.host_floor_ms(), 1),
        )

    def _decode_width(self, n: int) -> int:
        for b in self.engine.decode_buckets:
            if b >= n:
                return b
        return self.engine.decode_buckets[-1]

    def _mark_first_sched(self, seq: Sequence, now: float) -> None:
        """First chunk of this sequence is being dispatched: close the
        admit→first-chunk-start window as a ``sched_admit`` stat span
        (queue-wait attribution — bench and the /metrics histograms read
        it). Recorded under service "sched", NOT "engine": TpuEngine
        files a request-waterfall twin under "engine" with the dataplane
        headers, and sharing a (service, phase) key would double-observe
        every request in the phase-duration histogram."""
        if seq.t_first_sched:
            return
        seq.t_first_sched = now
        if seq.t_queued:
            self._sched_tracer.record(
                "sched_admit", seq.t_queued, now,
                attrs={
                    "request_id": seq.request_id,
                    "prompt_tokens": seq.prompt_len,
                    "cached_tokens": seq.num_cached_tokens,
                },
                stat=True,
            )

    def _sweep_queue(self) -> None:
        """Queue hygiene ahead of admission: drop cancelled requests from
        ANY queue position (a disconnected client must not wait for its
        request to reach the head of the line — satellite: disconnect-
        while-queued cleanup; queued sequences hold no blocks or pins,
        so removal IS the cleanup), and expire queued requests past
        their deadline with a typed retryable error frame (pattern:
        _sweep_expired_holds). Only never-scheduled sequences expire —
        an admitted (or preempted-mid-stream) sequence runs to
        completion, because expiring it would break a stream that
        already emitted tokens."""
        now = time.time()
        deadlines = self.enforce_deadlines

        def dead(s: Sequence) -> bool:
            # ONE combined pass per step (cancel + expiry): the sweep is
            # hot-loop work inside the step lock, and the common case
            # finds nothing.
            return s.cancelled or (
                deadlines
                and s.deadline_epoch is not None
                and now > s.deadline_epoch
                and not s.emitted_first
            )

        expired = [
            s for s in self.waiting.sweep(dead) if not s.cancelled
        ]
        for seq in expired:
            self.sched_stats["deadline_expired_total"] += 1
            waited_ms = (now - seq.t_queued) * 1e3 if seq.t_queued else 0.0
            log.info(
                "expiring %s: deadline passed after %.0f ms in queue",
                seq.request_id, waited_ms,
            )
            out = LLMEngineOutput(
                token_ids=[], finish_reason=FinishReason.ERROR.value,
                prompt_tokens=seq.prompt_len, completion_tokens=0,
            )
            out.meta = {
                "shed": "deadline",
                "detail": (
                    f"request {seq.request_id} expired after "
                    f"{waited_ms:.0f} ms in the scheduler queue"
                ),
            }
            self._shed_outputs.append((seq, out))

    def _admit(self) -> None:
        while self._inbox:
            self.waiting.append(self._inbox.popleft())
        self._sweep_queue()
        bs = self.engine.block_size
        watermark = 0.01 * self.allocator.capacity
        while self.waiting and len(self.running) < self.engine.max_num_seqs:
            if self._free_slots is not None and not self._free_slots:
                return   # every lane slot of the slab is held
            # Deficit-round-robin head: FIFO head with fairness off or a
            # single tenant; pop() charges the admitted prompt's token
            # cost to its tenant once admission actually succeeds.
            seq = self.waiting.head()
            P = seq.prompt_len
            seq.prompt_hashes = compute_seq_hashes(seq.prompt, bs)
            # Cap the reusable prefix so at least one token is prefilled
            # (the engine needs last-token logits to start decoding).
            # A block-diffusion model needs no such logits: its wave ends
            # where the prompt's whole blocks do, and may be all cached.
            cap = seq.wave_len // bs if self.cfg.block_length else (P - 1) // bs
            cached_ids = self.allocator.acquire_cached(seq.prompt_hashes[:cap])
            ncached = len(cached_ids)
            if self.host_pool is not None:
                cached_ids, ncached = self._onboard_from_host(
                    seq.prompt_hashes, cached_ids, ncached, cap
                )
            total_blocks = -(-P // bs)
            need = total_blocks - ncached
            if (
                self.allocator.free_blocks - need < watermark
                and self.running
            ):
                self.allocator.release(seq.prompt_hashes[:ncached])
                return
            try:
                new_ids = self.allocator.alloc_many(need)
            except OutOfBlocksError:
                self.allocator.release(seq.prompt_hashes[:ncached])
                return
            self.waiting.pop()
            # Admission-time prefix accounting (one query per ADMITTED
            # sequence — watermark retries don't double-count). DEDICATED
            # counters: the allocator's prefix_queries/prefix_hits belong
            # to match_prefix probes (router/disagg), and sharing them
            # would double-count requests that are probed AND admitted.
            self._admit_prefix_queries += 1
            if ncached:
                self._admit_prefix_hits += 1
            seq.block_ids = cached_ids + new_ids
            seq.committed_blocks = ncached
            seq.pinned_hashes = list(seq.prompt_hashes[:ncached])
            seq.num_cached_tokens = ncached * bs
            seq.prefilled = seq.processed = ncached * bs
            seq.hashed = TokenBlockSequence(seq.prompt[: seq.prefilled], bs)
            if self._free_slots is not None:
                seq.slot = self._free_slots.pop()
            self.running.append(seq)

    # -- device-step assembly ---------------------------------------------

    def _to_device(self, arr: np.ndarray) -> jax.Array:
        """``jnp.asarray``, after a poll of the step clock: a dozen
        transfers make ``h2d`` the phase a wave most often ends under."""
        self.clock.poll()
        return jnp.asarray(arr)

    def _put_batch(self, arr: np.ndarray) -> jax.Array:
        """Place a host batch array: leading axis split over dp when the
        mesh is on and the width divides (decode buckets always do)."""
        self.clock.poll()
        if self.mesh is None or arr.shape[0] % self._dp:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        spec = PartitionSpec("dp", *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _blank_tables(self, rows: int) -> np.ndarray:
        """``rows`` block tables that point nowhere: every column the
        garbage page. A window model's row is three parts sent as one
        (model.split_tables): the full pool's table, the index of the
        window table's first block, the window pool's table. A model with
        linear layers: the table and one column more, the sequence's lane
        slot (the garbage slot here)."""
        P = self.engine.max_blocks_per_seq
        if self._free_slots is not None:   # [blocks | the lane slot] (model.split_slots)
            t = np.full((rows, P + 1), self.engine.garbage_block, np.int32)
            t[:, P] = self.engine.garbage_slot
            return t
        if self.window_allocator is None:
            return np.full((rows, P), self.engine.garbage_block, np.int32)
        W = self.engine.window_table_blocks(self.cfg.sliding_window)
        t = np.full((rows, P + 1 + W), self.engine.num_window_blocks, np.int32)
        t[:, :P] = self.engine.garbage_block
        t[:, P] = 0
        return t

    def _table_row(self, row: np.ndarray, seq: Sequence) -> None:
        """Fill ``row`` of :meth:`_blank_tables` with ``seq``'s blocks, as
        the plan that is being dispatched grew and slid them
        (:meth:`_grow_blocks`, :meth:`_hold_window`)."""
        row[: len(seq.block_ids)] = seq.block_ids
        if self._free_slots is not None:
            row[self.engine.max_blocks_per_seq] = seq.slot
        if self.window_allocator is not None:
            P = self.engine.max_blocks_per_seq
            row[P] = seq.win_first
            row[P + 1 : P + 1 + len(seq.win_ids)] = seq.win_ids

    def _hold_window(self, seq: Sequence, p0: int, n_tokens: int) -> bool:
        """Make ``seq``'s window blocks those that its next ``n_tokens``
        queries, the first at position ``p0``, see and write: positions
        ``p0 - window + 1 .. p0 + n_tokens - 1``. Blocks wholly before them
        slid out of every later query's window (a cursor never goes back
        for a sequence that goes on) and return to the pool NOW: the device
        runs dispatches in order, so whoever is handed them next writes them
        after every dispatch already enqueued has read them. False, with
        nothing new held, where the pool cannot give the blocks ahead: the
        caller preempts, drains or cuts its wave as for the full pool.
        True at once for a model without window layers."""
        pool = self.window_allocator
        if pool is None:
            return True
        self._release_window_behind(seq, p0)
        need = ((p0 + n_tokens - 1) // self.engine.block_size + 1
                - (seq.win_first + len(seq.win_ids)))
        if need > pool.free_blocks:
            return False
        seq.win_ids.extend(pool.alloc() for _ in range(max(0, need)))
        return True

    def _release_window_behind(self, seq: Sequence, p: int) -> None:
        """Give back ``seq``'s window blocks that lie wholly before what a
        query at position ``p`` sees (:meth:`_hold_window`'s first half). A
        wave calls it for ``p`` = the position AFTER its chunk as soon as
        the chunk is dispatched: a prompt that waits for its next chunk, or
        for its first decode step behind other prompts' waves, then holds
        its window and not its last chunk too (48 prompts that arrive
        together otherwise fill the pool with chunks no one will read, and
        the lanes that decode are preempted for it)."""
        first = max(0, p - self.cfg.sliding_window + 1) // self.engine.block_size
        behind = min(first - seq.win_first, len(seq.win_ids))
        if behind > 0:
            for b in seq.win_ids[:behind]:
                self.window_allocator.free_partial(b)
            del seq.win_ids[:behind]
            self.exec_stats["window_blocks_released"] += behind
        if first > seq.win_first:
            seq.win_first = first

    def _commit_completed(self, seq: Sequence, completed) -> None:
        for blk in completed:
            idx = blk.position
            canonical = self.allocator.commit(
                seq.block_ids[idx], blk.block_hash, blk.parent_hash
            )
            seq.block_ids[idx] = canonical
            seq.pinned_hashes.append(blk.block_hash)
            seq.committed_blocks += 1
        if completed and seq.hold_blocks and self.on_chunk_commit is not None:
            # Streaming handoff: the committed prefix is immutable and
            # readable from now on — advertise the chunk cursor so a
            # decode peer can pull it while this prefill keeps chunking.
            self.on_chunk_commit(seq.request_id, seq.committed_blocks, False)

    def _assemble_ragged(
        self, rows: list[tuple[Sequence, list[int], int, int]], S: int,
        n_sample: list[int] | None = None,
        feed_rows: list[int | None] | None = None,
        force_R: bool = False,
    ) -> "_RaggedBatch":
        """Host-side assembly of ONE ragged forward's inputs over
        arbitrary rows — shared by the plain single-step dispatch
        (:meth:`_dispatch_ragged`) and the universal megastep's first
        iteration (:meth:`_dispatch_fused`), so the two can never
        disagree about row packing, sample gathers, or counter keys.
        ``force_R`` keeps the verify sample width even when every row is
        q_len=1 — a device-drafting dispatch needs the R-wide slots for
        its inner rounds although iteration 0 carries no host draft."""
        bs = self.engine.block_size
        total = sum(len(tl) for _, tl, _, _ in rows)
        T = self._bucket_for(total)
        # Bucket fill: real tokens against the padded bucket they ride in.
        self.exec_stats["ragged_real_tokens"] += total
        self.exec_stats["ragged_bucket_tokens"] += T
        R = (
            self._spec_R
            if force_R
            or (n_sample is not None and any(n > 1 for n in n_sample))
            else 1
        )

        tokens = np.zeros(T, np.int32)
        positions = np.zeros(T, np.int32)
        write_pages = np.full(T, self.engine.garbage_block, np.int32)
        write_offs = np.zeros(T, np.int32)
        kv_lens = np.zeros(S, np.int32)
        tables = self._blank_tables(S)
        cu = np.zeros(S + 1, np.int32)
        last_rows = np.zeros(S, np.int32)
        # Sample gather + per-slot rng counters [S, R]: slot (i, j) of a
        # verify row reads the logits after that row's j-th token and
        # draws with counter generated+j — bit-identical to the counter
        # the sequential decode path would use for that same token.
        gather = np.zeros((S, R), np.int32)
        counters = np.zeros((S, R), np.int32)
        seeds = np.zeros(S, np.int32)
        # a padded lane is at temperature 0: the device takes the sampler's
        # arg-max branch when no lane draws, padding included
        temp = np.zeros(S, np.float32)
        top_k = np.zeros(S, np.int32)
        top_p = np.ones(S, np.float32)

        t = 0
        feed_idx = None
        if feed_rows is not None and any(f is not None for f in feed_rows):
            feed_idx = np.full(T, -1, np.int32)
        for i, (seq, toks_list, pos0, kv_len) in enumerate(rows):
            chunk = len(toks_list)
            if self.cfg.hybrid and pos0 > 0:
                # a (re)admitted sequence's first rows start at its hit
                self.conv_state_reads[
                    "prefix_hit" if pos0 == seq.num_cached_tokens else "earlier_dispatch"
                ] += 1
            pos = np.arange(pos0, pos0 + chunk, dtype=np.int32)
            tokens[t : t + chunk] = toks_list
            positions[t : t + chunk] = pos
            ids = np.asarray(seq.block_ids, np.int32)  # dynalint: sync-ok — host list, not a device array
            write_pages[t : t + chunk] = ids[pos // bs]
            write_offs[t : t + chunk] = pos % bs
            kv_lens[i] = kv_len
            self._table_row(tables[i], seq)
            last_rows[i] = t + chunk - 1
            # Counters read through the optimistic overlay: with a step in
            # flight the lane's generated count lags by exactly the tokens
            # the in-flight step will commit, and the replayed (seed,
            # counter) keys must match the synchronous loop bit-for-bit.
            gen0 = seq.generated + self._adv3(seq)[2]
            if n_sample is not None and n_sample[i] > 1:
                j = np.arange(R, dtype=np.int32)
                off = np.minimum(j, chunk - 1)
                gather[i] = t + off
                counters[i] = gen0 + off
            else:
                gather[i] = t + chunk - 1
                counters[i] = gen0
            if feed_idx is not None and feed_rows[i] is not None:
                feed_idx[t] = feed_rows[i]
            seeds[i] = seq.seed
            temp[i] = seq.sampling.temperature
            top_k[i] = seq.sampling.top_k
            top_p[i] = seq.sampling.top_p
            t += chunk
        cu[1 : len(rows) + 1] = np.cumsum([len(tl) for _, tl, _, _ in rows])
        cu[len(rows) + 1 :] = cu[len(rows)]
        need_mask = self._count_sampling(temp, top_k, top_p)
        want_lp = any(s.logprobs is not None for s, _, _, _ in rows)

        # Multimodal splice (separate compiled variant): override rows
        # whose prompt position falls inside an image span with the
        # encoder's embedding for that patch. Decode rows sit past the
        # prompt, so the span check never selects them.
        want_mm = any(s.mm_embeds is not None for s, _, _, _ in rows)
        if want_mm:
            mm_embeds = np.zeros((T, self.cfg.hidden_size), np.float32)
            mm_mask = np.zeros(T, bool)
            t0 = 0
            for seq, toks_list, pos0, _ in rows:
                chunk = len(toks_list)
                if seq.mm_embeds is not None:
                    lo, hi = pos0, pos0 + chunk
                    row = 0
                    for start, cnt in seq.mm_positions:
                        for j in range(cnt):
                            p = start + j
                            if lo <= p < hi:
                                mm_embeds[t0 + (p - lo)] = seq.mm_embeds[row]
                                mm_mask[t0 + (p - lo)] = True
                            row += 1
                t0 += chunk
        else:  # tiny dummies: the want_mm=False variant never reads them
            mm_embeds = np.zeros((1, 1), np.float32)
            mm_mask = np.zeros(1, bool)

        return _RaggedBatch(
            T=T, R=R, tokens=tokens, positions=positions,
            write_pages=write_pages, write_offs=write_offs,
            kv_lens=kv_lens, tables=tables, cu=cu, last_rows=last_rows,
            gather=gather,
            counters=counters, seeds=seeds, temp=temp, top_k=top_k,
            top_p=top_p, feed_idx=feed_idx, mm_embeds=mm_embeds,
            mm_mask=mm_mask, need_mask=need_mask, want_lp=want_lp,
            want_mm=want_mm,
        )

    def _dispatch_ragged(
        self, rows: list[tuple[Sequence, list[int], int, int]], S: int,
        n_sample: list[int] | None = None,
        feed_rows: list[int | None] | None = None,
        kind: str = "prefill",
        **attrs: Any,
    ) -> _PendingFetch:
        """Assemble and run ONE ragged forward + fused sampling over
        arbitrary rows. Each row is ``(seq, tokens, pos_start, kv_len)``:
        a prefill chunk (tokens sliced from the prompt), a decode row
        (the single pending token at position ``processed``), or a
        speculative verify row (pending + drafted tokens). Prefill waves,
        chunked mixed steps, and verify steps all funnel here — mixed
        batches are exactly what the unified ragged forward was built for
        (a decode row is q_len=1, a verify row is a q_len=k+1 "prefill
        chunk" of already-chosen tokens). Programs compile per (token
        bucket, S, sample width, sampling-variant); S is the caller's
        static row width.

        ``n_sample`` (aligned with rows) marks verify rows: entry > 1
        samples that row's FIRST n positions (the per-drafted-token
        target choices), everything else samples only the last position.
        The sample gather widens to the engine's static ``spec_k + 1``
        whenever any row speculates — short drafts pad with duplicate
        reads — so draft length never mints new compiled programs.

        ``feed_rows`` (aligned with rows) carries the device-resident
        token feedback: a non-None entry is the flat index of that row's
        FIRST token in the in-flight step's sampled-token output, and the
        host placeholder at that slot is overridden by an on-device
        gather — the just-sampled id never round-trips through the host.

        Returns a :class:`_PendingFetch`; ``land()`` yields the legacy
        shapes — 2-D ([S, R] tokens, [S, R, ...] logprobs) with
        ``n_sample``, 1-D without. ``kind`` names the dispatch on the
        step clock's annotation (a prefill wave, or a mixed step) and
        ``attrs`` go on it too (a wave's ``cover``: the buckets its
        planner chose for the waiting tokens, empty without a table)."""
        self.clock.mark("assemble")
        b = self._assemble_ragged(rows, S, n_sample, feed_rows)
        R = b.R
        tokens, positions = b.tokens, b.positions
        write_pages, write_offs = b.write_pages, b.write_offs
        kv_lens, tables, cu, gather = b.kv_lens, b.tables, b.cu, b.gather
        last_rows, counters, seeds = b.last_rows, b.counters, b.seeds
        temp, top_k, top_p = b.temp, b.top_k, b.top_p
        feed_idx, mm_embeds, mm_mask = b.feed_idx, b.mm_embeds, b.mm_mask
        need_mask, want_lp, want_mm = b.need_mask, b.want_lp, b.want_mm

        if self.pp_mesh is not None:
            # want_mm cannot be true here: add_request rejects mm
            # requests on pp engines at admission.
            from dynamo_tpu.parallel.pipeline import plan_microbatches

            plan = plan_microbatches(
                tokens, positions, write_pages, write_offs, kv_lens, cu,
                len(rows), last_rows, self._pp_micro,
                self.engine.garbage_block,
            )
            self.clock.mark("h2d")
            mb_tok = jnp.asarray(plan.tokens)
            if feed_idx is not None:
                # Device-resident feedback under pp: the microbatch plan
                # only PADS the flat token buffer (row order is
                # preserved), so the flat feed indices apply verbatim to
                # the flattened [M, Tm] buffer — gather on device, then
                # fold back to microbatch shape.
                fi = np.full(plan.tokens.size, -1, np.int32)
                fi[: feed_idx.shape[0]] = feed_idx
                mb_tok = self._fed(mb_tok.reshape(-1), fi).reshape(
                    plan.tokens.shape
                )
            args = (
                mb_tok,
                jnp.asarray(plan.positions),
                jnp.asarray(plan.write_pages),
                jnp.asarray(plan.write_offs),
                jnp.asarray(plan.kv_lens),
                jnp.asarray(tables),
                jnp.asarray(plan.cu_q_lens),
                jnp.asarray(np.array([len(rows)], np.int32)),
                jnp.asarray(plan.last_local),
                jnp.asarray(plan.last_mask),
                jnp.asarray(seeds),
                jnp.asarray(counters[:, 0]),
                jnp.asarray(temp),
                jnp.asarray(top_k),
                jnp.asarray(top_p),
            )
            self._mark_dispatch(
                kind, len(rows), S, 1, int(cu[len(rows)]), b.T, **attrs
            )
            stats = None
            toks, lps, self.cache = self._prefill_pp(
                self.params,
                self.cache,
                *args,
                need_mask=need_mask,
                want_logprobs=want_lp,
            )
        else:
            # Sample-slot arrays flatten [S, R] -> [S*R] row-major; the
            # ragged forward gathers R hidden rows per sequence and the
            # fused sampler treats them as S*R independent lanes (with
            # R == 1 these are bit-for-bit the legacy shapes, so the
            # no-speculation program cache is untouched).
            self.clock.mark("h2d")
            put = self._to_device
            tok_in = put(tokens)
            if feed_idx is not None:
                # Device-resident feedback: override the placeholder slots
                # with just-sampled ids straight from the in-flight step's
                # output — enqueued on the device stream, never blocking.
                tok_in = self._fed(tok_in, feed_idx)
            args = (
                tok_in,
                put(positions),
                put(write_pages),
                put(write_offs),
                put(kv_lens),
                put(tables),
                put(cu),
                put(np.array([len(rows)], np.int32)),
                put(gather.reshape(-1)),
                put(np.repeat(seeds, R)),
                put(counters.reshape(-1)),
                put(np.repeat(temp, R)),
                put(np.repeat(top_k, R)),
                put(np.repeat(top_p, R)),
                put(mm_embeds),
                put(mm_mask),
            )
            self._mark_dispatch(
                kind, len(rows), S, 1, int(cu[len(rows)]), b.T, **attrs
            )
            toks, lps, self.cache, stats = self._prefill(
                self.params,
                self.cache,
                *args,
                need_mask=need_mask,
                want_logprobs=want_lp,
                want_mm=want_mm,
            )
        self.clock.mark("plan")
        self.exec_stats["single_step_dispatches"] += 1
        return _PendingFetch(
            self, toks, lps, sr=(S, R) if n_sample is not None else None,
            expert_stats=("prefill", stats),
        )

    def _dispatch_fused(
        self,
        rows: list[tuple[Sequence, list[int], int, int]],
        S: int,
        n_sample: list[int],
        feed_rows: list[int | None],
        kinds: list[str],
        drafts: list[list[int]],
        cont: list[bool],
        n_steps: int,
        device: list[bool] | None = None,
    ) -> _PendingFetch:
        """Assemble and enqueue one UNIVERSAL megastep (ISSUE 12): the
        same ragged first iteration :meth:`_dispatch_ragged` would run
        over these rows — prefill chunks, decode rows, verify rows —
        fused with ``n_steps - 1`` scanned decode iterations in ONE
        device dispatch (:func:`_megastep_fused_body`). ``cont``
        (aligned with rows) marks lanes that continue as decode rows
        after iteration 0: decode and verify rows always do; a prefill
        chunk does exactly when it completes its prompt and the planner
        could reserve its continuation headroom. Verify rows resolve
        accept/reject on device, so the continuation restarts from the
        correction token with no host round trip. Returns a pending
        fetch whose ``land()`` yields ([n_steps, S, R] tokens, matching
        logprob arrays or None).

        When any lane in ``device`` drafts on device (ISSUE 18), the
        dispatch runs :func:`_megastep_draft_body` instead: each lane's
        history ring is packed host-side from prompt + out_tokens — with
        the in-flight tail gathered ON DEVICE from the previous
        dispatch's output via :meth:`_feed_series`, so the drafter sees
        tokens the host has not committed yet — and the inner iterations
        are verify-shaped draft→verify→accept rounds. The pending fetch
        then also carries the [3, n_steps, S] per-round accounting
        (``land_aux``)."""
        use_dd = device is not None and any(device)
        self.clock.mark("assemble")
        b = self._assemble_ragged(rows, S, n_sample, feed_rows, force_R=use_dd)
        self.exec_stats["decode_live_lanes"] += len(rows)
        self.exec_stats["decode_padded_lanes"] += S
        if self.cfg.hybrid:
            self.conv_state_reads["same_step"] += int(sum(cont)) * (n_steps - 1)
        R = b.R
        W = MEGASTEP_WATCH_W
        draft = np.full((S, R - 1), -1, np.int32)
        draft_len = np.zeros(S, np.int32)
        cont_a = np.zeros(S, bool)
        base_pos = np.zeros(S, np.int32)
        watch = np.full((S, W), -1, np.int32)
        # Padded / masked lanes never hit their budget. The fused body's
        # deepest lane emits accepted + 1 + (n_steps - 1) <= R + n_steps
        # - 1 tokens; a device-drafting lane can emit up to R tokens per
        # round — n_steps * R worst case — so its padding sits past that.
        budgets = np.full(
            S, (n_steps * R if use_dd else n_steps + R) + 1, np.int32
        )
        min_left = np.zeros(S, np.int32)
        for i, ((seq, toks_list, pos0, _kv), kind) in enumerate(
            zip(rows, kinds)
        ):
            if not cont[i]:
                continue
            cont_a[i] = True
            base_pos[i] = pos0 + (len(toks_list) if kind == "p" else 1)
            d = drafts[i]
            if d:
                draft[i, : len(d)] = d
                draft_len[i] = len(d)
            self._arm_stop_inputs(seq, i, watch, budgets, min_left)
        if use_dd:
            return self._dispatch_drafted(
                rows, b, device, draft, draft_len, cont_a,
                base_pos, watch, budgets, min_left, n_steps, kinds,
            )
        self.clock.mark("h2d")
        args = (
            self._fused_tokens(b),
            jnp.asarray(b.positions),
            jnp.asarray(b.write_pages),
            jnp.asarray(b.write_offs),
            jnp.asarray(b.kv_lens),
            jnp.asarray(b.tables),
            jnp.asarray(b.cu),
            jnp.asarray(np.array([len(rows)], np.int32)),
            jnp.asarray(b.gather.reshape(-1)),
            jnp.asarray(np.repeat(b.seeds, R)),
            jnp.asarray(b.counters.reshape(-1)),
            jnp.asarray(np.repeat(b.temp, R)),
            jnp.asarray(np.repeat(b.top_k, R)),
            jnp.asarray(np.repeat(b.top_p, R)),
            jnp.asarray(b.mm_embeds),
            jnp.asarray(b.mm_mask),
            jnp.asarray(draft),
            jnp.asarray(draft_len),
            jnp.asarray(cont_a),
            jnp.asarray(base_pos),
            jnp.asarray(b.seeds),
            jnp.asarray(b.temp),
            jnp.asarray(b.top_k),
            jnp.asarray(b.top_p),
            jnp.asarray(watch),
            jnp.asarray(budgets),
            jnp.asarray(min_left),
        )
        self._mark_fused_dispatch(rows, S, b, kinds, n_steps)
        out, lps, self.cache = self._fused(
            self.params,
            self.cache,
            *args,
            n_steps=n_steps,
            need_mask=b.need_mask,
            want_logprobs=b.want_lp,
            want_mm=b.want_mm,
        )
        self.clock.mark("plan")
        self.exec_stats["megastep_dispatches"] += 1
        if any(k != "d" for k in kinds):
            # Count as MIXED only when the dispatch actually carried
            # prefill chunks or verify rows — the same condition the
            # mocker's gauge uses, so both engines export comparable
            # series (a batch whose chunks were all skipped is a plain
            # fused decode dispatch).
            self.exec_stats["fused_mixed_dispatches"] += 1
        return _PendingFetch(self, out, lps)  # [n_steps, S, R] on land()

    def _fused_tokens(self, b: "_RaggedBatch") -> jax.Array:
        """The ragged token buffer on device, in-flight lanes' placeholders
        overridden by the previous dispatch's sampled ids."""
        tok_in = jnp.asarray(b.tokens)
        if b.feed_idx is not None:
            tok_in = self._fed(tok_in, b.feed_idx)
        return tok_in

    def _mark_fused_dispatch(self, rows, S: int, b, kinds, n_steps: int) -> None:
        self._mark_dispatch(
            "mixed" if any(k != "d" for k in kinds) else "megastep",
            len(rows), S, n_steps, int(b.cu[len(rows)]), b.T,
        )

    def _dispatch_drafted(
        self,
        rows: list[tuple[Sequence, list[int], int, int]],
        b,
        device: list[bool],
        draft,
        draft_len,
        cont_a,
        base_pos,
        watch,
        budgets,
        min_left,
        n_steps: int,
        kinds: list[str],
    ) -> _PendingFetch:
        """Pack per-lane history rings and enqueue the ON-DEVICE-DRAFTING
        megastep (:func:`_megastep_draft_body`, ISSUE 18). The ring of a
        drafting lane is exactly the tail :meth:`_draft_for` would hand
        the host drafter — last ``window + ngram_max`` tokens of
        prompt + out_tokens, newest right-aligned — except that under
        async execution the in-flight step's emission is gathered ON
        DEVICE from the previous dispatch's output
        (:meth:`_feed_series`), so the drafter matches against history
        the host has not committed yet. Host stop-scans stay the
        authority: the ring is re-packed from host truth every plan, so
        a host-side truncation (stop string, budget clamp) rolls the
        ring back for free."""
        S = int(budgets.shape[0])
        R = b.R
        H = self._ring_H
        hist = np.zeros((S, H), np.int32)
        hlen = np.zeros(S, np.int32)
        dd = np.zeros(S, bool)
        win = np.ones(S, np.int32)
        nmin = np.ones(S, np.int32)
        nmax = np.ones(S, np.int32)
        kmax = np.zeros(S, np.int32)
        ring_src = None
        for i, (seq, _toks, _pos0, _kv) in enumerate(rows):
            if not device[i]:
                continue
            dd[i] = True
            sc = seq.spec
            win[i] = sc.window
            nmin[i] = sc.ngram_min
            nmax[i] = sc.ngram_max
            kmax[i] = min(sc.k, R - 1)
            take = 0
            series = self._feed_series(seq)
            if series is not None:
                start, stride, cnt = series
                take = min(cnt, H)
                if ring_src is None:
                    ring_src = np.full((S, H), -1, np.int32)
                for j in range(take):
                    ring_src[i, H - take + j] = start + (cnt - take + j) * stride
            # Host-visible tail fills the remainder — the same context
            # rule as _draft_for (prompt tail + out_tokens, newest at
            # the right edge), so host and device drafters see the same
            # history whenever nothing is in flight.
            need = H - take
            if need <= 0:
                ctx: list[int] = []
            elif len(seq.out_tokens) >= need:
                ctx = seq.out_tokens[-need:]
            else:
                keep = need - len(seq.out_tokens)
                ctx = (
                    seq.prompt[max(0, len(seq.prompt) - keep):]
                    + seq.out_tokens
                )
            L = len(ctx)
            if L:
                hist[i, H - take - L: H - take] = ctx
            hlen[i] = min(L + take, H)
        self.clock.mark("h2d")
        hist_in = jnp.asarray(hist)
        if ring_src is not None:
            hist_in = self._fed(
                hist_in.reshape(-1), ring_src.reshape(-1)
            ).reshape(S, H)
        args = (
            self._fused_tokens(b),
            jnp.asarray(b.positions),
            jnp.asarray(b.write_pages),
            jnp.asarray(b.write_offs),
            jnp.asarray(b.kv_lens),
            jnp.asarray(b.tables),
            jnp.asarray(b.cu),
            jnp.asarray(np.array([len(rows)], np.int32)),
            jnp.asarray(b.gather.reshape(-1)),
            jnp.asarray(np.repeat(b.seeds, R)),
            jnp.asarray(b.counters.reshape(-1)),
            jnp.asarray(np.repeat(b.temp, R)),
            jnp.asarray(np.repeat(b.top_k, R)),
            jnp.asarray(np.repeat(b.top_p, R)),
            jnp.asarray(b.mm_embeds),
            jnp.asarray(b.mm_mask),
            jnp.asarray(draft),
            jnp.asarray(draft_len),
            jnp.asarray(cont_a),
            jnp.asarray(base_pos),
            jnp.asarray(b.seeds),
            jnp.asarray(b.temp),
            jnp.asarray(b.top_k),
            jnp.asarray(b.top_p),
            jnp.asarray(watch),
            jnp.asarray(budgets),
            jnp.asarray(min_left),
            hist_in,
            jnp.asarray(hlen),
            jnp.asarray(dd),
            jnp.asarray(win),
            jnp.asarray(nmin),
            jnp.asarray(nmax),
            jnp.asarray(kmax),
        )
        self._mark_fused_dispatch(rows, S, b, kinds, n_steps)
        out, aux, lps, self.cache = self._drafted(
            self.params,
            self.cache,
            *args,
            n_steps=n_steps,
            need_mask=b.need_mask,
            want_logprobs=b.want_lp,
            want_mm=b.want_mm,
        )
        self.clock.mark("plan")
        self.exec_stats["megastep_dispatches"] += 1
        if any(k != "d" for k in kinds):
            self.exec_stats["fused_mixed_dispatches"] += 1
        return _PendingFetch(self, out, lps, aux=aux)

    def _plan_prefill_wave(self, seqs: list[Sequence]) -> _PlannedStep | None:
        """Plan one ragged prefill wave: up to ``prefill_batch`` sequences
        under a shared token budget — different chunk lengths pack into
        one token buffer with no per-lane padding, first-token sampling
        fused into the same program, and the last prompt is cut where the
        budget ends. The commit side lands the sampled tokens and emits
        for every sequence whose prompt completed this wave. Chunk
        cursors read through the optimistic overlay, so consecutive waves
        of one long prompt pipeline under async execution.

        The budget. Without a measured table (``prefill_bucket_ms``: an
        engine that skipped warm-up, and warm-up itself, whose waves have
        to fill each bucket to compile it) it is the largest bucket: the
        wave takes every waiting token and pads to the next bucket up.
        With one it is the largest bucket of the cheapest cover of the
        waiting tokens (:meth:`_prefill_cover`): 640 tokens run as a full
        512 wave now and a 128 wave next, on programs that are compiled
        anyway (per bucket, not per cursor), where the measurements say
        that beats one 2,048 wave of 69% padding. The next plan sees what
        is left, and whatever arrived, and covers that again. Prefill
        keeps its priority over decode either way. It is not an option:
        the only parameters are what the engine measured on itself, and
        where a cut does not pay (a small model whose waves the host
        cannot feed faster) the same search keeps the prompt whole."""
        S = self.engine.prefill_batch
        # What this wave may take: the tokens left of the first S prompts.
        waiting: list[tuple[Sequence, int, int]] = []  # (seq, p0, left)
        for seq in seqs:
            if len(waiting) == S:
                break
            p0 = seq.prefilled + self._adv3(seq)[0]
            if seq.wave_len > p0:
                waiting.append((seq, p0, seq.wave_len - p0))
        if not waiting:
            return None
        largest = self.engine.prefill_buckets[-1]
        tokens = sum(left for _, _, left in waiting)
        cover = self._prefill_cover(tokens)
        budget = cover[0] if cover else largest
        chosen: list[tuple[Sequence, int, int]] = []  # (seq, p0, chunk)
        total = 0
        for seq, p0, left in waiting:
            if total >= budget:
                break
            chunk = min(left, budget - total)
            if not self._hold_window(seq, p0, chunk):
                break  # the window pool is short: the wave ends here
            chosen.append((seq, p0, chunk))
            total += chunk
        if not chosen:
            return None  # not even the first prompt's chunk (_plan_waves goes on)
        bucket = self._bucket_for(total)
        self.prefill_waves[bucket] = self.prefill_waves.get(bucket, 0) + 1
        if total < tokens and budget < largest:
            self.exec_stats["prefill_cut_waves"] += 1
        t_disp = time.time()
        rows: list[tuple[Sequence, list[int], int, int]] = []
        for seq, p0, chunk in chosen:
            self._mark_first_sched(seq, t_disp)
            rows.append((seq, seq.prompt[p0 : p0 + chunk], p0, p0 + chunk))
        pend = self._dispatch_ragged(rows, S, cover="+".join(map(str, cover)))
        if self.window_allocator is not None:
            for seq, p0, chunk in chosen:   # the tables are assembled: see the method
                self._release_window_behind(seq, p0 + chunk)
        adv: dict[str, tuple[int, int, int]] = {}
        feed_index: dict[str, int] = {}
        feed_series: dict[str, tuple[int, int, int]] = {}
        blocks = self.cfg.block_length > 0   # its wave samples nothing that is kept
        for i, (seq, p0, chunk) in enumerate(chosen):
            done = p0 + chunk >= seq.wave_len and not blocks
            adv[seq.request_id] = (chunk, chunk, 1 if done else 0)
            if done:
                feed_index[seq.request_id] = i
                feed_series[seq.request_id] = (i, 0, 1)

        # dynalint: holds-lock(_step_lock) — commits run inside the step
        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            toks, lps = pend.land()
            outputs: list[tuple[Sequence, LLMEngineOutput]] = []
            now = time.time()
            live = {id(s) for s in self.running}
            for i, (seq, p0, chunk) in enumerate(chosen):
                if seq.finish is not None or seq.cancelled or id(seq) not in live:
                    continue  # lane left the scheduler while in flight
                tok, lp = self._advance_prefill_chunk(
                    seq, chunk, toks, lps, i, t_disp, now
                )
                if tok is None or blocks:
                    continue  # prompt not finished this wave
                seq.pending = tok
                seq.generated += 1
                outputs.append((seq, self._emit(seq, tok, lp)))
                if seq.finish is not None:
                    self._finish(seq)
            return outputs

        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv,
            feed_tokens=pend.toks, feed_index=feed_index,
            feed_series=feed_series,
        )

    def _advance_prefill_chunk(
        self, seq: Sequence, chunk: int, toks, lps, i: int,
        t0: float, now: float,
    ) -> tuple[int | None, dict | None]:
        """Commit one prefill chunk's bookkeeping — block commits, cursor
        advance, per-chunk trace span. ONE implementation shared by the
        wave and mixed steps so the identical-block-layout and
        greedy-parity guarantees cannot diverge between schedulers.
        Returns (sampled_token, lp_entry); the token is real only when
        this chunk completes the prompt (the ragged program samples every
        row's last-token logits, but mid-prompt samples are noise)."""
        completed = seq.hashed.extend(
            seq.prompt[seq.prefilled : seq.prefilled + chunk]
        )
        self._commit_completed(seq, completed)
        seq.prefilled += chunk
        seq.processed = seq.prefilled
        self._tracer.record(
            "engine_prefill_chunk", t0, now,
            attrs={
                "request_id": seq.request_id, "tokens": chunk,
                "prefilled": seq.prefilled,
                "prompt_tokens": seq.prompt_len,
            },
            stat=True,
        )
        if not seq.prefill_done:
            return None, None
        lp = None
        if lps is not None and seq.logprobs is not None:
            lp = _lp_entry(int(toks[i]), lps[0][i], lps[1][i], lps[2][i], seq.logprobs)
        return int(toks[i]), lp

    # dynalint: holds-lock(_step_lock) — called from _plan_waves on the step path
    def _maybe_ring_prefill(self, prefills: list[Sequence]):
        """Dispatch one eligible long prompt to the sequence-parallel ring
        path (dense ring-attention prefill over the sp mesh; the paged
        cache is written in the same pass, so decode continues normally).
        Returns emitted (seq, chunk) outputs or None to fall through to
        the regular ragged wave."""
        if self._ring is None or self.engine.ring_prefill_threshold <= 0:
            return None
        n_sp = int(self.sp_mesh.shape["sp"])
        for seq in prefills:
            if seq.prefilled or seq.committed_blocks:
                continue  # cached prefix / mid-flight: paged waves own it
            if seq.mm_embeds is not None:
                continue  # multimodal splice is a paged-wave variant only
            if seq.prompt_len < self.engine.ring_prefill_threshold:
                continue
            try:
                T = self._bucket_for(seq.prompt_len)
            except ValueError:
                continue  # longer than the largest bucket: chunked waves
            if T % n_sp:
                continue
            return self._run_ring_prefill(seq, T)
        return None

    # dynalint: holds-lock(_step_lock) — synchronous ring path inside the step
    def _run_ring_prefill(self, seq: Sequence, T: int):
        self.clock.mark("assemble")
        self._mark_first_sched(seq, time.time())
        bs = self.engine.block_size
        P_len = seq.prompt_len
        tokens = np.zeros(T, np.int32)
        tokens[:P_len] = seq.prompt
        pos = np.arange(T, dtype=np.int32)
        write_pages = np.full(T, self.engine.garbage_block, np.int32)
        ids = np.asarray(seq.block_ids, np.int32)  # dynalint: sync-ok — host list, not a device array
        write_pages[:P_len] = ids[pos[:P_len] // bs]
        write_offs = pos % bs
        want_lp = seq.logprobs is not None
        temp = np.array([seq.sampling.temperature], np.float32)
        top_k = np.array([seq.sampling.top_k], np.int32)
        top_p = np.array([seq.sampling.top_p], np.float32)
        need_mask = self._count_sampling(temp, top_k, top_p)
        self.clock.mark("h2d")
        args = (
            jnp.asarray(tokens),
            jnp.asarray(write_pages),
            jnp.asarray(write_offs),
            jnp.asarray(P_len - 1, jnp.int32),
            jnp.asarray([seq.seed], np.int32),
            jnp.asarray([seq.generated], np.int32),
            jnp.asarray(temp),
            jnp.asarray(top_k),
            jnp.asarray(top_p),
        )
        self._mark_dispatch("prefill", 1, 1, 1, P_len, T)
        self._dispatch_no += 1
        toks, lps, self.cache = self._ring(
            self.params,
            self.cache,
            *args,
            need_mask=need_mask,
            want_logprobs=want_lp,
        )
        self.clock.in_flight(self._dispatch_no, toks)
        self.clock.mark("land")
        self._ring_prefills += 1
        if self._ring_prefills == 1:
            log.info(
                "ring prefill active: %d-token prompt over sp=%d",
                P_len, int(self.sp_mesh.shape["sp"]),
            )
        # dynacheck: allow-transitive-blocking(ring prefill is deliberately synchronous — sp engines keep the classic loop, and the single long prompt IS the step)
        tok = int(fetch_replicated(toks)[0])
        self.clock.landed(self._dispatch_no)
        completed = seq.hashed.extend(seq.prompt)
        self._commit_completed(seq, completed)
        seq.prefilled = seq.processed = P_len
        seq.pending = tok
        seq.generated += 1
        lp = None
        if want_lp and lps is not None:
            # dynacheck: allow-transitive-blocking(same synchronous ring path — logprob landing rides the already-landed step)
            lps = tuple(fetch_replicated_many(lps))
            lp = _lp_entry(tok, lps[0][0], lps[1][0], lps[2][0], seq.logprobs)
        out = self._emit(seq, tok, lp)
        if seq.finish is not None:
            self._finish(seq)
        return [(seq, out)]

    def _grow_or_preempt(
        self, decoding: list[Sequence], n_tokens: int
    ) -> list[Sequence]:
        """Ensure every decode lane has blocks for its next ``n_tokens``
        writes, preempting the youngest neighbor under pressure. Shared by
        the fused-chain decode step (n_tokens = chain length) and the
        mixed chunked step (n_tokens = 1) so the two schedulers' victim
        selection can never diverge."""
        ready: list[Sequence] = []
        live = {id(s) for s in self.running}   # no list is walked to ask
        for seq in decoding:
            self.clock.poll()
            if id(seq) not in live:
                continue  # preempted by an earlier lane in this loop
            if self._grow_blocks(seq, n_tokens):
                ready.append(seq)
                continue
            if self._inflight is not None:
                # Block pressure mid-plan with a step in flight: the
                # async loop drains the pipeline and re-plans from
                # settled state, where preemption is safe.
                raise _NeedDrain(seq.request_id)
            victim = next((s for s in reversed(self.running) if s is not seq), None)
            if victim is not None:
                self._preempt(victim)
                live.discard(id(victim))
                if victim in ready:
                    ready.remove(victim)
                if self._grow_blocks(seq, n_tokens):
                    ready.append(seq)
        return ready

    def _grow_blocks(self, seq: Sequence, n_tokens: int) -> bool:
        """Ensure physical blocks exist for the next ``n_tokens`` decode
        writes (positions processed .. processed+n_tokens-1, read through
        the optimistic overlay so an in-flight step's writes are already
        covered)."""
        bs = self.engine.block_size
        base = self._eff_block_start(seq)
        if not self._hold_window(seq, base, n_tokens):
            return False
        # never more than a table holds (a block-diffusion lane's last
        # dispatch may plan blocks that its budget ends before)
        need = min((base + n_tokens - 1) // bs + 1,
                   self.engine.max_blocks_per_seq) - len(seq.block_ids)
        grabbed: list[int] = []
        for _ in range(max(0, need)):
            try:
                grabbed.append(self.allocator.alloc())
            except OutOfBlocksError:
                for b in grabbed:
                    self.allocator.free_partial(b)
                return False
        seq.block_ids.extend(grabbed)
        return True

    def _preempt(self, seq: Sequence) -> None:
        """Token-replay preemption: free everything, re-prefill later.

        A mid-prefill (chunked-scheduling) victim keeps its ORIGINAL
        prompt — its hashed view covers only the chunks already run, and
        replacing the prompt with that truncated prefix would silently
        drop the unprefilled tail. Its committed chunks re-match through
        the prefix cache at re-admission."""
        log.info("preempting %s (generated=%d)", seq.request_id, seq.generated)
        self.sched_stats["preemptions"] += 1
        if self._free_slots is not None:
            # no block holds a linear layer's state: re-admitted, the sequence
            # matches nothing and replays all it had run into a fresh slot
            self.exec_stats["state_replayed_tokens"] += seq.processed
        self._release_blocks(seq)
        if seq.prefill_done:
            # a pending block's tokens were streamed; its K/V were not final
            new_prompt = seq.hashed.all_tokens() + seq.pending_block
            if seq.pending is not None:
                new_prompt.append(seq.pending)
            # a block-diffusion lane that has not run its first block yet
            # keeps the tail that block opens with
            if len(new_prompt) >= seq.prompt_len:
                seq.prompt = new_prompt
            if self.cfg.block_length:
                seq.tail = seq.prompt_len % self.cfg.block_length
        seq.pending = None
        self._drop_pending_block(seq)
        # The rebuilt prompt absorbs every emitted token; keeping
        # out_tokens too would double-count them in the drafter's lookup
        # history after re-admission.
        seq.out_tokens = []
        seq.block_ids = []
        seq.committed_blocks = 0
        seq.prefilled = seq.processed = 0
        seq.hashed = None
        self.running.remove(seq)
        self.waiting.appendleft(seq)

    def _drop_pending_block(self, seq: Sequence) -> None:
        """A block-diffusion lane that ends, is cancelled or is preempted
        leaves its pending block as it is: no pass is spent on K/V no later
        block will read (a resumed lane's wave recomputes them as prompt)."""
        if seq.pending_block:
            seq.pending_block = []
            self.exec_stats["block_pending_dropped"] += 1

    def _release_blocks(self, seq: Sequence) -> None:
        """Release a sequence's block refs EXACTLY once: uncommitted
        partials back to the free list, pinned hashes unpinned. Clearing
        ``pinned_hashes`` makes a second call a no-op — a half-prefilled
        sequence hit by both preemption and a cancel/hold sweep must not
        decrement refcounts twice (that frees blocks other sequences
        still pin)."""
        for bid in seq.block_ids[seq.committed_blocks :]:
            self.allocator.free_partial(bid)
        self.allocator.release(seq.pinned_hashes)
        seq.block_ids = seq.block_ids[: seq.committed_blocks]
        seq.pinned_hashes = []
        for bid in seq.win_ids:   # the window pool's: held by no one else
            self.window_allocator.free_partial(bid)
        seq.win_ids, seq.win_first = [], 0
        if seq.slot >= 0:   # the lane slot: whoever takes it next starts at position 0
            self._free_slots.append(seq.slot)
            seq.slot = -1

    def _arm_stop_inputs(
        self, seq: Sequence, i: int, watch: np.ndarray,
        budgets: np.ndarray, min_left: np.ndarray,
    ) -> None:
        """Fill lane ``i``'s on-device stop inputs — watch ids (EOS +
        stop_token_ids, truncated to the device's slots), remaining
        generation budget, min-tokens floor — ONE implementation shared
        by the decode-only megastep and the fused dispatch, so the two
        scanned bodies can never disagree about stop semantics."""
        W = watch.shape[1]
        wl: list[int] = []
        if not seq.stop.ignore_eos:
            wl.extend(sorted(self.eos_token_ids))
        wl.extend(seq.stop.stop_token_ids)
        watch[i, : min(W, len(wl))] = wl[:W]
        if seq.stop.max_tokens is not None:
            budgets[i] = max(
                1, seq.stop.max_tokens - self._eff_generated(seq)
            )
        if seq.stop.min_tokens:
            min_left[i] = max(
                0, seq.stop.min_tokens - self._eff_generated(seq)
            )

    def _dispatch_megastep(
        self, seqs: list[Sequence], n_steps: int,
        feed_lanes: list[int | None] | None = None,
        opens: list[list[int]] | None = None,
    ) -> _PendingFetch:
        """Assemble and enqueue one decode megastep: ``n_steps`` fused
        decode+sample iterations over these lanes in ONE device dispatch
        (:func:`_megastep_body`). ``feed_lanes`` (aligned with seqs)
        carries device-resident token feedback: a non-None entry is the
        flat index of that lane's pending token in the in-flight step's
        sampled output, gathered on device instead of round-tripping
        through the host. Cursor/counter inputs read through the
        optimistic overlay. Per-lane stop inputs (watch ids, remaining
        generation budget, min-tokens floor) arm the on-device stop
        flags so lanes that finish early run masked no-ops instead of
        writing K/V past their stop. The per-lane inputs cross as ONE
        packed array (:func:`pack_lanes`) beside the block tables: two
        transfers a megastep, and the gather of the fed tokens inside the
        program. Returns a pending fetch whose ``land()`` yields
        ([n_steps, B] tokens, lp arrays or None).

        A block-diffusion model's megastep (:func:`_megastep_blocks`) comes
        with ``opens`` (aligned with seqs): the tokens each lane's first
        block opens with. A lane's position is then its next block's first
        place and its budget the places it may still generate. A block
        starts from mask tokens, so no TOKEN of the step in flight is fed to
        it; what is fed is the lane's PENDING block, whose clean rows ride
        the dispatch's first pass: ``feed_lanes`` names the first place of
        the lane's last block in the in-flight dispatch's output, and where
        that has landed (or the lane rode no such dispatch) the tokens go
        down from the host (``Sequence.pending_block``) beside the known
        places, ``[B, 2, block_length]``, -1 where a place is hidden or a
        lane has no pending block. ``land()`` then yields ``[n_blocks, B,
        block_length]`` tokens and ``land_aux()`` the steps, live lanes and
        reveal counts, flat."""
        self.clock.mark("assemble")
        B = self._decode_width(len(seqs))
        seqs = seqs[:B]
        blk = self.cfg.block_length if opens is not None else 0
        # what a lane can yield in this dispatch: a token an iteration, or
        # the places of its whole blocks
        yields = n_steps // (self.cfg.denoising_steps + 1) * blk if blk else n_steps
        # Occupancy: live lanes against the padded width (the commit side
        # counts the lane-iterations issued and those that gave a client
        # a token, both when the chain lands, so a window sees whole pairs).
        self.exec_stats["decode_live_lanes"] += len(seqs)
        self.exec_stats["decode_padded_lanes"] += B
        if self.cfg.hybrid:
            self.conv_state_reads["earlier_dispatch"] += len(seqs)
            self.conv_state_reads["same_step"] += len(seqs) * (n_steps - 1)
        W = MEGASTEP_WATCH_W
        tokens = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        tables = self._blank_tables(B)
        active = np.zeros(B, bool)
        temp = np.zeros(B, np.float32)   # padding draws nothing (_assemble_ragged)
        top_k = np.zeros(B, np.int32)
        top_p = np.ones(B, np.float32)
        seeds = np.zeros(B, np.int32)
        counters = np.zeros(B, np.int32)
        watch = np.full((B, W), -1, np.int32)
        # Padded lanes never hit their budget (gen <= yields < yields+1).
        budgets = np.full(B, yields + 1, np.int32)
        min_left = np.zeros(B, np.int32)
        feed_idx = None
        if feed_lanes is not None and any(f is not None for f in feed_lanes):
            feed_idx = np.full(B, -1, np.int32)
        for i, seq in enumerate(seqs):
            self.clock.poll()
            if feed_idx is not None and i < len(feed_lanes) and feed_lanes[i] is not None:
                feed_idx[i] = feed_lanes[i]
            elif not blk:
                tokens[i] = seq.pending
            positions[i] = self._eff_block_start(seq)
            self._table_row(tables[i], seq)
            active[i] = True
            temp[i] = seq.sampling.temperature
            top_k[i] = seq.sampling.top_k
            top_p[i] = seq.sampling.top_p
            seeds[i] = seq.seed
            counters[i] = self._eff_generated(seq)
            self._arm_stop_inputs(seq, i, watch, budgets, min_left)
        made = np.full(B, yields, np.int32)
        if blk:
            known = np.full((B, 2, blk), -1, np.int32)
            for i, k in enumerate(opens[:B]):
                known[i, 0, : len(k)] = k
                made[i] -= len(k)
                if seqs[i].pending_block and (feed_idx is None or feed_idx[i] < 0):
                    known[i, 1] = seqs[i].pending_block
        finishing = int(np.count_nonzero(budgets <= made))
        need_mask = self._count_sampling(temp, top_k, top_p)
        want_lp = any(s.logprobs is not None for s in seqs)
        lanes = pack_lanes(
            tokens, feed_idx, positions, active, seeds, counters, temp,
            top_k, top_p, watch, budgets, min_left,
        )
        self.clock.mark("h2d")
        # Two transfers, and the step in flight's output where a lane
        # reads its token (a block model's lane: its pending block) from it
        # (zeros of that shape where none does); a block model's third is
        # its known places and the pending blocks the host holds.
        args = (self._put_batch(lanes), self._put_batch(tables),
                self._no_feed if feed_idx is None else self._feed_source(),
                *((self._put_batch(known),) if blk else ()))
        steps = self.cfg.denoising_steps
        self._mark_dispatch(
            "megastep" if n_steps > 1 else "decode",
            len(seqs), B, n_steps, len(seqs) * yields, B * yields,
            **({"block": blk, "steps": steps, "passes": steps} if blk else {}),
        )
        # On a pp engine the FUSED pp megastep: the whole wavefront chain
        # — stage hops, sampling, stop flags — is one dispatch, armed
        # with the same per-lane stop inputs as the single-chip body.
        program = self._decode_pp if self.pp_mesh is not None else self._decode
        # a block model's program returns its side channel last
        out, lps, self.cache, *rest = program(
            self.params,
            self.cache,
            *args,
            n_steps=n_steps,
            need_mask=need_mask,
            want_logprobs=want_lp,
        )
        self.clock.mark("plan")
        if self.pp_mesh is not None:
            self.exec_stats[
                "pp_fused_dispatches" if n_steps > 1 else "pp_forced_single"
            ] += 1
        self.exec_stats[
            "megastep_dispatches" if n_steps > 1 else "single_step_dispatches"
        ] += 1
        return _PendingFetch(  # [n_steps, B] on land()
            self, out, lps, expert_stats=("decode", rest[0] if rest else None),
            aux=rest[1] if blk else None, finishing=finishing,
        )

    # -- the iteration -----------------------------------------------------

    def step(self) -> list[tuple[Sequence, LLMEngineOutput]]:
        """One engine iteration; returns (sequence, output-chunk) pairs.
        A chunk with ``finish_reason`` set is the sequence's last.

        Pipelined (``self.pipelined``, the engine's choice unless
        ``EngineConfig.async_exec`` pins one), the step plans and
        dispatches iteration N+1 BEFORE committing iteration N
        (one-step-ahead), so the returned outputs lag the dispatch by
        exactly one call. Otherwise it plans, dispatches, and commits in
        place — the classic synchronous loop; the token stream is
        bit-identical either way."""
        with self._step_lock, self.step_scope():
            return self._step_locked()

    # dynalint: holds-lock(_step_lock) — step() locks before dispatching here
    def _step_locked(self) -> list[tuple[Sequence, LLMEngineOutput]]:
        # The step clock's window: the gap since the last step closes
        # here (after the lock was taken), and reopens at the exit as
        # ``between_steps`` or, with nothing pending, ``no_work``.
        self.clock.step_begin()
        try:
            if self.pipelined:
                outputs = self._step_async()
            else:
                self.iterations += 1
                plan = self._plan_step()
                outputs = plan.commit() if plan is not None else []
            if self._shed_outputs:
                # Typed queue-expiry rejections from this step's sweeps ride
                # the same output path as real chunks (the engine facade
                # turns them into the wire-typed DeadlineExceededError).
                outputs = self._shed_outputs + outputs
                self._shed_outputs = []
            if self.flight.capacity and outputs:
                # Flight-recorder step record (counts + cursors only; the
                # dump is redacted by contract): one dict append per
                # committed step, never on the plan/dispatch path.
                self.flight.record_step(
                    i=self.iterations,
                    outputs=[
                        {
                            "rid": s.request_id,
                            "emitted": len(o.token_ids),
                            "generated": s.generated,
                            "finish": o.finish_reason or "",
                        }
                        for s, o in outputs[:64]
                    ],
                    outputs_truncated=len(outputs) > 64,
                    dispatches=self.exec_stats["dispatches"],
                    megastep_dispatches=self.exec_stats["megastep_dispatches"],
                    fused_mixed_dispatches=self.exec_stats[
                        "fused_mixed_dispatches"
                    ],
                    committed_tokens=self.exec_stats["committed_tokens"],
                    shed_total=self.sched_stats["shed_total"],
                    deadline_expired_total=self.sched_stats[
                        "deadline_expired_total"
                    ],
                    running=len(self.running),
                )
            return outputs
        finally:
            self.clock.step_end(self.has_work())

    # dynalint: holds-lock(_step_lock) — only called from _step_locked
    def _step_async(self) -> list[tuple[Sequence, LLMEngineOutput]]:
        """One-step-ahead iteration: plan and enqueue the next step while
        the previous one executes on device, then commit the previous
        step's double-buffered outputs — block-table assembly, stop
        scans, and stream emission overlap device compute instead of
        serializing with it. Steps whose advances are data-dependent
        (verify rows with live drafts) commit before the next plan; block
        pressure mid-plan drains the pipeline and re-plans settled."""
        outputs: list[tuple[Sequence, LLMEngineOutput]] = []
        # One engine iteration per step() call, even when a drain re-plans
        # (a double increment would skew the mixed-step fairness rotation
        # and the iteration trace attrs versus the synchronous schedule).
        self.iterations += 1
        if self._inflight is not None and not self._inflight.deterministic:
            outputs.extend(self._commit_inflight())
        try:
            plan = self._plan_step()
        except _NeedDrain:
            self.exec_stats["drains"] += 1
            outputs.extend(self._commit_inflight())
            plan = self._plan_step()
        prev, self._inflight = self._inflight, plan
        if prev is not None:
            outputs.extend(prev.commit())
        return outputs

    def _commit_inflight(self) -> list[tuple[Sequence, LLMEngineOutput]]:
        prev, self._inflight = self._inflight, None
        return prev.commit() if prev is not None else []

    # dynalint: holds-lock(_step_lock) — step path only (sync and async loops)
    def _plan_step(self) -> _PlannedStep | None:
        """Plan + dispatch one engine iteration (no commit): drain
        intake, admit under the watermark, then assemble and enqueue the
        iteration's device program(s). All cursor reads go through the
        optimistic overlay, so planning over an in-flight step sees the
        state that step will commit. The caller owns the iteration
        counter (a drain calls this twice for one engine step)."""
        self.clock.mark("admit")  # a boundary only for a drain's second plan
        self._sweep_expired_holds()

        for seq in [s for s in self.running if s.cancelled]:
            self.running.remove(seq)
            self._drop_pending_block(seq)
            self._release_blocks(seq)

        self._admit()
        t_plan = self.clock.mark("plan")
        if self._sched_chunked:
            prefills = [
                s for s in self.running if not self._eff_prefill_done(s)
            ]
            self._count_decode_ready(prefills)
            plan = None
            if prefills and self.engine.megastep > 1 and self.pp_mesh is None:
                # Universal megastep (ISSUE 12): prefill chunks, decode
                # rows, and verify rows fuse into one scanned dispatch;
                # None falls back to the bit-identical single-step path.
                plan = self._plan_fused(prefills)
            if plan is None:
                plan = (
                    self._plan_mixed(prefills)
                    if prefills
                    else self._plan_decode()
                )
        else:
            plan = self._plan_waves()
        if plan is not None:
            # Ends at the step clock's next boundary (the landing of the
            # previous step, or this step's exit).
            self.clock.close_at_next(
                "engine_plan", t_plan,
                {
                    "iteration": self.iterations,
                    "pipelined": self._inflight is not None,
                },
            )
        return plan

    def _count_decode_ready(self, prefills: list[Sequence]) -> None:
        """Decode-ready lanes, for the step clock's lane-seconds: running,
        prefill done (under the overlay), and not run to the end of their
        budget by the step in flight. Counted from the planner's own
        lists, no lane walked."""
        ending = self._inflight.finishing if self._inflight is not None else 0
        self._decode_ready = max(0, len(self.running) - len(prefills) - ending)

    # dynalint: holds-lock(_step_lock) — called from _plan_step
    def _plan_waves(self) -> _PlannedStep | None:
        """Prefill-priority scheduling: one monolithic prefill wave
        strictly before any decode (the classic vLLM-default shape)."""
        prefills = [s for s in self.running if not self._eff_prefill_done(s)]
        self._count_decode_ready(prefills)
        if prefills:
            ring_out = self._maybe_ring_prefill(prefills)
            if ring_out is not None:
                # The ring path runs synchronously (sp engines keep the
                # classic loop); wrap its already-committed outputs.
                return _PlannedStep(core=self, commit_fn=lambda: ring_out)
            plan = self._plan_prefill_wave(prefills)
            if plan is not None:
                return plan
            # The window pool could not hold even the first prompt's next
            # chunk. The lanes that decode go on: they end, and give their
            # blocks back. With none to run, settle what is in flight, then
            # take the blocks of the youngest sequence that holds any, as
            # for a decode lane that cannot grow.
            plan = self._plan_decode()
            if plan is None:
                if self._inflight is not None:
                    raise _NeedDrain(prefills[0].request_id)
                victim = next((s for s in reversed(self.running)
                               if s is not prefills[0] and s.win_ids), None)
                if victim is not None:
                    self._preempt(victim)
            return plan
        return self._plan_decode()

    def _decode_candidates(self) -> list[Sequence]:
        """Runnable decode lanes under the optimistic overlay. Lanes whose
        in-flight step is guaranteed to finish them (generation budget or
        context edge reached) are excluded — the synchronous loop would
        have removed them before this iteration, so scheduling them would
        both waste a slot and write past the block table."""
        out: list[Sequence] = []
        for s in self.running:
            self.clock.poll()  # a lane: a 128-lane plan is long (stepclock)
            dpre, dproc, dgen = self._adv3(s)
            if s.pending is None and dgen == 0:
                continue  # no sampled token yet (still prefilling)
            if not self._eff_prefill_done(s):
                continue
            if (
                s.stop.max_tokens is not None
                and s.generated + dgen >= s.stop.max_tokens
            ):
                continue  # finishes (length) in flight
            if self.engine.max_model_len - (s.processed + dproc) < 1:
                continue  # context edge reached in flight
            out.append(s)
        return out

    def _plan_decode(self) -> _PlannedStep | None:
        """Plan one decode iteration. With the universal megastep
        (megastep > 1, ISSUE 12), speculating batches fuse WHOLE: verify
        rows resolve accept/reject on device and ride the scanned body
        next to plain decode lanes in one dispatch (_plan_fused). On the
        k=1 / fallback path, speculating lanes peel off into a batched
        single-step verify dispatch (draft tokens verify as ragged
        q_len=k+1 rows) and the rest ride one decode megastep — both
        dispatches share one planned step, their commits run in order.

        ALL block growth happens before ANY dispatch: block pressure must
        surface (preemption, or _NeedDrain under async) while this plan
        has enqueued nothing, so a drain never abandons an already-
        dispatched device step — and so a megastep can never exhaust
        blocks MID-dispatch: every lane's k tokens of block headroom are
        reserved here, at plan time, by construction."""
        if self.cfg.block_length:
            return self._plan_blocks()
        decoding = self._decode_candidates()
        if not decoding:
            return None
        spec_lanes = [s for s in decoding if s.spec is not None]
        if spec_lanes and self.engine.megastep > 1 and self.pp_mesh is None:
            # Universal megastep (ISSUE 12): verify rows resolve
            # accept/reject on device and fuse with the decode lanes in
            # ONE scanned dispatch — no more forced-k=1 verify steps.
            # None (watch overflow / budget edge) falls back to the
            # legacy merged verify + chain plan below.
            plan = self._plan_fused([], decoding=decoding)
            if plan is not None:
                return plan
        chain_lanes = [s for s in decoding if s.spec is None]
        chain_ready: list[Sequence] = []
        n_steps = 0
        if chain_lanes:
            n_steps = self._chain_length(chain_lanes)
            chain_ready = self._grow_or_preempt(chain_lanes, n_steps)
        parts: list[_PlannedStep] = []
        if spec_lanes:
            # Verify growth (and any preemption it causes) also precedes
            # its dispatch, inside _plan_verify.
            vplan = self._plan_verify(
                [s for s in spec_lanes if s in self.running]
            )
            if vplan is not None:
                parts.append(vplan)
            # A verify preemption may have evicted a chain candidate.
            live = {id(s) for s in self.running}
            chain_ready = [s for s in chain_ready if id(s) in live]
        if chain_ready:
            cplan = self._plan_megastep(chain_ready, n_steps)
            if cplan is not None:
                parts.append(cplan)
        return self._merge_plans(parts)

    def _merge_plans(self, parts: list[_PlannedStep]) -> _PlannedStep | None:
        if not parts:
            return None
        if len(parts) == 1:
            return parts[0]

        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            out: list[tuple[Sequence, LLMEngineOutput]] = []
            for p in parts:
                p.committed = True  # bypass the per-part wrapper
                out.extend(p.commit_fn())
            return out

        adv: dict[str, tuple[int, int, int]] = {}
        for p in parts:
            adv.update(p.adv)
        # A multi-dispatch step (spec + chain lanes in one batch) never
        # feeds the next plan directly: the feedback gather reads ONE
        # device array, and each part has its own — so the merged plan is
        # conservatively non-deterministic and commits before the next
        # plan, even when no drafts were proposed. Mixed spec/non-spec
        # decode batches therefore run unpipelined; pure batches of
        # either kind keep the one-step-ahead overlap. (Lifting this
        # needs a multi-source feed gather — future work.)
        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv,
            deterministic=all(p.deterministic for p in parts)
            and all(not p.feed_index for p in parts),
        )

    def _plan_megastep(
        self, ready: list[Sequence], n_steps: int
    ) -> _PlannedStep | None:
        """Plan one decode megastep over non-speculating lanes: k fused
        decode+sample iterations per dispatch (the caller already grew
        their blocks — _plan_decode front-loads k tokens of headroom per
        lane before any dispatch, so mid-megastep block exhaustion is
        impossible by construction); the commit side scans stops,
        commits K/V bookkeeping, and emits whole-megastep chunks."""
        if not ready:
            return None
        t_decode = time.time()
        feed_lanes = [self._feed_src(s) for s in ready]
        pend = self._dispatch_megastep(ready, n_steps, feed_lanes=feed_lanes)
        adv = {
            s.request_id: (0, n_steps, n_steps) for s in ready
        }
        # Each lane's newest token is the chain's LAST sampled row:
        # flat index (n_steps-1)*B + lane in the [n_steps, B] output.
        B = self._decode_width(len(ready))
        feed_index = {
            s.request_id: (n_steps - 1) * B + i for i, s in enumerate(ready)
        }
        # Full emission series (stream order, one token per inner step):
        # lane i's tokens sit at flat i, B + i, ..., (n_steps-1)*B + i.
        feed_series = {
            s.request_id: (i, B, n_steps) for i, s in enumerate(ready)
        }

        # dynalint: holds-lock(_step_lock) — commits run inside the step
        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            outputs: list[tuple[Sequence, LLMEngineOutput]] = []
            emitted_total = 0
            chained, lps = pend.land()  # [n_steps, len(ready)]
            live = {id(s) for s in self.running}
            for i, seq in enumerate(ready):
                if seq.finish is not None or seq.cancelled or id(seq) not in live:
                    continue  # late finish/preempt: discard the optimistic chain
                toks = chained[:, i]
                k, finish = self._scan_stop(seq, toks)
                # Cache writes this chain: the old pending token plus the
                # first k-1 sampled tokens (each step writes the current
                # token's K/V, then samples the next).
                written = [seq.pending] + [int(t) for t in toks[: k - 1]]
                completed = seq.hashed.extend(written)
                self._commit_completed(seq, completed)
                seq.processed += k
                seq.generated += k
                emitted = [int(t) for t in toks[:k]]
                lp_entries = None
                if lps is not None and seq.logprobs is not None:
                    lp_entries = [
                        _lp_entry(
                            emitted[j], lps[0][j][i], lps[1][j][i], lps[2][j][i],
                            seq.logprobs,
                        )
                        for j in range(k)
                    ]
                outputs.append(
                    (seq, self._emit_chunk(seq, emitted, lp_entries, finish))
                )
                emitted_total += len(emitted)
                if finish is not None:
                    seq.finish = finish
                    self._finish(seq)
                else:
                    seq.pending = emitted[-1]
            self.exec_stats["megastep_issued_lane_iters"] += len(ready) * n_steps
            self.exec_stats["megastep_useful_lane_iters"] += emitted_total
            if n_steps > 1:
                # Megastep observability: one span per multi-iteration
                # dispatch carrying the inner-iteration count — the
                # dispatch-amortization evidence (k iterations, one
                # fixed overhead) bench and /traces consumers read.
                self._tracer.record(
                    "engine_megastep", t_decode, time.time(),
                    attrs={
                        "seqs": len(ready), "inner_steps": n_steps,
                        "tokens": emitted_total,
                        "pp_stages": self._pp,
                        "fused_shapes": {
                            "decode": len(ready), "chunk": 0, "verify": 0,
                        },
                    },
                    stat=True,
                )
            return outputs

        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv,
            feed_tokens=pend.toks, feed_index=feed_index,
            feed_series=feed_series, finishing=pend.finishing,
        )

    # -- generation by blocks (ModelConfig.block_length > 0) ----------------

    def _block_candidates(self) -> list[Sequence]:
        """Lanes whose next block may run, under the optimistic overlay:
        the wave has run the prompt's whole blocks, and the step in flight
        does not spend what is left of the generation budget."""
        out: list[Sequence] = []
        for s in self.running:
            self.clock.poll()
            if not self._eff_prefill_done(s):
                continue
            if (s.stop.max_tokens is not None
                    and self._eff_generated(s) >= s.stop.max_tokens):
                continue  # finishes (length) in flight
            out.append(s)
        return out

    def _plan_blocks(self) -> _PlannedStep | None:
        """Plan one megastep of a block-diffusion model: ``megastep / (steps +
        1)`` whole blocks a lane in ONE dispatch (:func:`_megastep_blocks`),
        ``steps`` passes each. Every lane's headroom for those blocks is
        grown before the dispatch, as :meth:`_plan_decode` grows a chain's.

        **What the cursor promises.** ``num_computed_tokens`` (``processed``)
        moves ``B`` on over a block when the block's CLEAN rows have run,
        which is in the first pass of the lane's next block, and by nothing
        before: below it every K/V is final, and everything that reads or
        publishes a lane's K/V (the hash chain and the allocator's commit,
        KV events, prefix reuse, a transfer) stops there, so a page is
        published only after its last block's clean rows. Past it lie the
        lane's pending block (``Sequence.pending_block``: revealed,
        streamed, its K/V those of a pass with places still masked) and the
        passes of the blocks in flight, which overwrite their K/V in place
        where no sequence that goes on reads (PR 35's invariant). Tokens are
        streamed when revealed. The dispatch's last block of a live lane
        comes back pending; the next dispatch is planned before this one
        lands (the pipelined loop), so it names the block in this one's
        output (``feed_index``) and the device gathers it; no drain is
        added. A lane that ends, is cancelled or is preempted drops its
        pending block (:meth:`_drop_pending_block`).

        The commit side is the authority on what is kept: it scans each
        block's generated places in order for EOS, stop ids and the budget,
        emits what is kept as one chunk, ends the request at a cut and
        counts the places after it as discarded."""
        ready = self._block_candidates()
        if not ready:
            return None
        B, steps = self.cfg.block_length, self.cfg.denoising_steps
        n_steps = self.engine.megastep
        n_blocks = n_steps // (steps + 1)
        ready = self._grow_or_preempt(ready, n_blocks * B)
        if not ready:
            return None
        S = self._decode_width(len(ready))
        ready = ready[:S]
        t_decode = time.time()
        # the places a lane's first block of this dispatch opens with: a
        # prompt's tail, once, before the lane's first block
        known = [s.prompt[s.wave_len:] if self._eff_block_start(s) < s.prompt_len else []
                 for s in ready]
        now = time.time()
        for s in ready:
            self._mark_first_sched(s, now)   # a prompt of no whole block rode no wave
        # a lane's pending block: in the output of the dispatch in flight
        # (its last block there), or with the host, or none yet
        feed_lanes = [self._feed_src(s) for s in ready]
        rides = [f is not None or bool(s.pending_block) for f, s in zip(feed_lanes, ready)]
        pend = self._dispatch_megastep(ready, n_steps, feed_lanes=feed_lanes, opens=known)
        adv = {s.request_id: (0, n_blocks * B, n_blocks * B - len(k))
               for s, k in zip(ready, known)}
        # where the next dispatch finds each lane's last block in ``tokens
        # [n_blocks, S, B]``, flat: by request, so a lane that changes slot
        # gets its own
        feed_index = {s.request_id: ((n_blocks - 1) * S + i) * B for i, s in enumerate(ready)}

        # dynalint: holds-lock(_step_lock) — commits run inside the step
        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            outputs: list[tuple[Sequence, LLMEngineOutput]] = []
            toks, lps = pend.land()                 # [n_blocks, S, B]
            aux = pend.land_aux()
            step_of = aux[: n_blocks * S * B].reshape(n_blocks, S, B)
            ran = aux[n_blocks * S * B: -2].reshape(n_blocks, S)[:, : len(ready)]
            st = self.exec_stats
            st["places_revealed_threshold"] += int(aux[-2])
            st["places_revealed_quota"] += int(aux[-1])
            lane_blocks = int(ran.sum())
            # clean rows ran beside every block a lane ran but its first of the
            # dispatch, and beside that one where the lane brought a pending block
            cleaned = int(ran[1:].sum()) + int(ran[0, np.asarray(rides, bool)].sum())
            st["denoise_forwards"] += lane_blocks * steps
            st["commit_forwards"] += cleaned
            st["block_rows"] += (lane_blocks * steps + cleaned) * B
            st["head_rows"] += lane_blocks * sum(hidden_at_most(B, steps))
            live = {id(s) for s in self.running}
            emitted_total = kept_blocks = 0
            made = 0
            for i, seq in enumerate(ready):
                t = len(known[i])
                made += int(ran[:, i].sum()) * B - (t if ran[0, i] else 0)
                if seq.finish is not None or seq.cancelled or id(seq) not in live:
                    continue  # late finish/preempt: the blocks are discarded
                emitted: list[int] = []
                entries: list[dict] | None = (
                    [] if lps is not None and seq.logprobs is not None else None)
                finish = None
                for b in range(n_blocks):
                    if not ran[b, i]:
                        break   # the device saw the lane end; so will the scan
                    if seq.pending_block:
                        # its clean rows ran in this block's first pass: the
                        # cursor moves over it, and its page may be published
                        self._commit_completed(seq, seq.hashed.extend(seq.pending_block))
                        seq.processed += len(seq.pending_block)
                        seq.pending_block = []
                        st["block_clean_folded"] += 1
                    first = t if b == 0 else 0
                    k, finish = self._scan_stop(seq, toks[b, i, first:])
                    block = [int(x) for x in toks[b, i, : first + k]]
                    if entries is not None:
                        entries += [
                            dict(_lp_entry(block[j], lps[0][b, i, j], lps[1][b, i, j],
                                           lps[2][b, i, j], seq.logprobs),
                                 block=(seq.processed + j) // B, step=int(step_of[b, i, j]),
                                 place=j)
                            for j in range(first, first + k)]
                    seq.generated += k
                    emitted += block[first:]
                    kept_blocks += 1
                    if finish is not None:
                        if entries and first + k < B:
                            # the places after the cut: revealed, sent to no one,
                            # and part of what a later step of the block saw
                            entries[-1]["cut"] = [
                                [j, int(step_of[b, i, j]), int(toks[b, i, j])]
                                for j in range(first + k, B)]
                        # the request's last block: no later block, so no clean rows
                        st["block_pending_dropped"] += 1
                        break
                    seq.pending_block = block
                outputs.append((seq, self._emit_chunk(seq, emitted, entries, finish)))
                emitted_total += len(emitted)
                if finish is not None:
                    seq.finish = finish
                    self._finish(seq)
            st["blocks_committed"] += kept_blocks
            st["block_places_discarded"] += made - emitted_total
            st["megastep_issued_lane_iters"] += len(ready) * n_blocks * steps
            st["megastep_useful_lane_iters"] += kept_blocks * steps
            self._tracer.record(
                "engine_megastep", t_decode, time.time(),
                attrs={
                    "seqs": len(ready), "inner_steps": n_steps,
                    "tokens": emitted_total, "pp_stages": self._pp,
                    "blocks": n_blocks, "block": B, "steps": steps,
                    "fused_shapes": {"decode": len(ready), "chunk": 0, "verify": 0},
                },
                stat=True,
            )
            return outputs

        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv, feed_tokens=pend.toks,
            feed_index=feed_index, finishing=pend.finishing)

    # -- speculative decoding (draft + batched ragged verify) ---------------

    def _draft_for(
        self, seq: Sequence, max_extra: int, reserve: int = 0
    ) -> list[int]:
        """Draft continuation tokens for one speculating sequence, capped
        by the caller's token headroom, the context edge, and the
        remaining generation budget (drafting past ``max_tokens`` is pure
        waste — the stop scan would discard it). ``reserve`` holds back
        context-edge room for a fused megastep's continuation iterations
        (the universal megastep writes up to ``n_steps - 1`` tokens past
        the verify row)."""
        sc = seq.spec
        d_cap = min(
            sc.k, max_extra,
            self.engine.max_model_len - self._eff_processed(seq) - 1 - reserve,
        )
        if seq.stop.max_tokens is not None:
            d_cap = min(d_cap, seq.stop.max_tokens - self._eff_generated(seq) - 1)
        if d_cap <= 0:
            return []
        # out_tokens ends with the pending token, so proposals continue
        # exactly the sequence the verify row will feed. (Under async
        # execution the history lags by the in-flight tokens — the
        # device-fed pending is not host-visible yet; proposals are then
        # one step stale, which can only change WHICH tokens are drafted,
        # never which tokens are emitted.) Only the last
        # window+ngram_max tokens can ever match, so hand the drafter
        # that tail — a full prompt+output concat would be O(context)
        # per lane per step on the decode hot path.
        need = sc.window + sc.ngram_max
        if len(seq.out_tokens) >= need:
            context = seq.out_tokens[-need:]
        else:
            keep = need - len(seq.out_tokens)
            context = seq.prompt[max(0, len(seq.prompt) - keep):] + seq.out_tokens
        return propose_ngram(
            context, d_cap, sc.ngram_min, sc.ngram_max, sc.window
        )

    # dynalint: holds-lock(_step_lock) — verify commits run inside the step
    def _apply_verify_row(
        self, seq: Sequence, draft: list[int], row_toks, lps, i: int
    ) -> tuple[LLMEngineOutput, int, int]:
        """Host side of one verify row: accept the longest drafted prefix
        the target agrees with, emit accepted + 1 tokens (the last is the
        target's own correction — or bonus — choice), advance the
        ``num_computed_tokens`` cursor past exactly the writes that are
        valid. Rejected drafted tokens' K/V writes sit PAST the cursor:
        never attended (kv_lens stop at the cursor) and rewritten by the
        next step — the rollback is the cursor itself. Returns
        (output chunk, drafted, accepted)."""
        d = len(draft)
        a = 0
        while a < d and int(row_toks[a]) == draft[a]:
            a += 1
        emitted_all = [int(row_toks[j]) for j in range(a + 1)]
        if d:
            # No-draft rows are plain decode rows: counting them would
            # drag mean_accepted_len toward 1.0 and diverge from the
            # mocker's gauges (which only count drafted rows).
            self.spec_stats.observe_row(d, a)
        k, finish = self._scan_stop(seq, np.asarray(emitted_all))
        # Valid cache writes this row: the old pending token plus the
        # accepted drafted tokens that stay after the stop scan (same
        # shape as the fused chain's bookkeeping).
        written = [seq.pending] + emitted_all[: k - 1]
        completed = seq.hashed.extend(written)
        self._commit_completed(seq, completed)
        seq.processed += k
        seq.generated += k
        emitted = emitted_all[:k]
        lp_entries = None
        if lps is not None and seq.logprobs is not None:
            lp_entries = [
                _lp_entry(
                    emitted[j], lps[0][i][j], lps[1][i][j], lps[2][i][j],
                    seq.logprobs,
                )
                for j in range(k)
            ]
        out = self._emit_chunk(seq, emitted, lp_entries, finish)
        if finish is not None:
            seq.finish = finish
            self._finish(seq)
        else:
            seq.pending = emitted[-1]
        return out, d, a

    def _plan_verify(self, seqs: list[Sequence]) -> _PlannedStep | None:
        """Plan one batched verify step over speculating decode sequences:
        every row is pending + up to k drafted tokens in the SAME ragged
        program shape the schedulers already dispatch, so k+1 target
        forwards ride one device invocation. Draft tokens count against
        the per-step token budget.

        Under async execution each row CONSUMES the device-resident
        pending token (the verify row's first slot gathers it from the
        in-flight step's output); the drafter proposes from host history,
        which lags by the in-flight tokens — proposal quality dips one
        step, token values never change (verification replays the
        target's own counter-keyed choices). A step carrying live drafts
        advances data-dependently, so it is marked non-deterministic and
        the async loop commits it before planning over it."""
        t0 = time.time()
        ready = self._grow_or_preempt(seqs, 1)
        ready = ready[: self.engine.decode_buckets[-1]]
        if not ready:
            return None
        budget = self.engine.token_budget
        rows: list[tuple[Sequence, list[int], int, int]] = []
        drafts: list[list[int]] = []
        feed_rows: list[int | None] = []
        total = 0
        for idx, seq in enumerate(ready):
            if total + 1 > budget:
                break  # over-budget lanes wait one step
            # Pre-charge the base token of every lane still to come so
            # one greedy drafter cannot push later lanes out of the step.
            lanes_after = len(ready) - idx - 1
            draft = self._draft_for(seq, budget - total - 1 - lanes_after)
            if draft and not self._grow_blocks(seq, 1 + len(draft)):
                draft = []  # block pressure: verify degrades to q_len=1
            cursor = self._eff_processed(seq)
            src = self._feed_src(seq)
            toks = [0 if src is not None else seq.pending] + draft
            rows.append((seq, toks, cursor, cursor + len(toks)))
            drafts.append(draft)
            feed_rows.append(src)
            total += len(toks)
        if not rows:
            return None
        t_draft = time.time()
        n_draft_rows = sum(1 for d in drafts if d)
        if n_draft_rows:
            self._tracer.record(
                "spec_draft", t0, t_draft,
                attrs={
                    "seqs": n_draft_rows,
                    "drafted": sum(len(d) for d in drafts),
                },
                stat=True,
            )
        pend = self._dispatch_ragged(
            rows, self._decode_width(len(rows)),
            n_sample=[len(tl) for _, tl, _, _ in rows],
            feed_rows=feed_rows, kind="mixed",
        )
        # No live drafts -> every row advances exactly one token (a plain
        # decode row in verify clothing): the step pipelines like any
        # decode step, and the sample width is R == 1, so each lane's
        # newest token sits at flat index i.
        deterministic = n_draft_rows == 0
        adv = {seq.request_id: (0, 1, 1) for seq, _, _, _ in rows}
        feed_index = (
            {seq.request_id: i for i, (seq, _, _, _) in enumerate(rows)}
            if deterministic
            else {}
        )
        feed_series = {
            rid: (i, 0, 1) for rid, i in feed_index.items()
        }

        # dynalint: holds-lock(_step_lock) — commits run inside the step
        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            outputs: list[tuple[Sequence, LLMEngineOutput]] = []
            toks, lps = pend.land()
            drafted_total = accepted_total = emitted_total = 0
            live = {id(s) for s in self.running}
            for i, ((seq, _, _, _), draft) in enumerate(zip(rows, drafts)):
                if seq.finish is not None or seq.cancelled or id(seq) not in live:
                    continue  # late finish/preempt: discard the row
                out, d, a = self._apply_verify_row(seq, draft, toks[i], lps, i)
                outputs.append((seq, out))
                drafted_total += d
                accepted_total += a
                emitted_total += len(out.token_ids)
            if n_draft_rows:
                # A step "carried a verify row" only when something was
                # actually drafted — no-match steps are plain decode steps
                # (same accounting as the mocker, so real and mock workers
                # export identical series).
                self.spec_stats.verify_steps += 1
                self._tracer.record(
                    "spec_verify", t_draft, time.time(),
                    attrs={
                        "seqs": n_draft_rows, "drafted": drafted_total,
                        "accepted": accepted_total, "tokens": emitted_total,
                    },
                    stat=True,
                )
            return outputs

        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv,
            feed_tokens=pend.toks, feed_index=feed_index,
            deterministic=deterministic, feed_series=feed_series,
        )

    def _plan_mixed(self, prefills: list[Sequence]) -> _PlannedStep | None:
        """Plan one SINGLE-STEP chunked-scheduling iteration (the k=1 /
        fused-fallback path — with megastep > 1 the universal megastep
        (_plan_fused) runs this same row assembly through the scanned
        body instead): every runnable decode sequence rides as a q_len=1
        row NEXT TO prefill chunks in the same ragged program, under the
        ``max_num_batched_tokens`` budget.
        A long prompt streams through ceil(P/chunk) steps while in-flight
        decodes keep emitting one token per step — prefill waves no
        longer stall decodes, and new arrivals stop queueing behind whole
        waves (PERF.md r5: saturated TTFT is admission shaping, not a
        kernel gap). Under async execution, decode rows gather their
        pending token from the in-flight step's device output and chunk
        cursors read through the optimistic overlay, so mixed steps
        pipeline exactly like pure-decode steps (speculating rows with
        live drafts mark the step non-deterministic)."""
        t_step = time.time()
        budget = self.engine.token_budget
        chunk_cap = self.engine.chunk_size
        bs = self.engine.block_size
        S_max = self.engine.decode_buckets[-1]

        decoding = self._decode_candidates()
        # Reserve one row + headroom for a prefill chunk so a full decode
        # batch can never starve admission; rotate which decode lanes sit
        # out so no single stream stalls repeatedly.
        cap = min(S_max - 1, budget - 1)
        if len(decoding) > cap:
            off = self.iterations % len(decoding)
            decoding = (decoding + decoding)[off : off + cap]
        # Block growth first (a preemption re-queues its victim — possibly
        # a mid-prefill one, which keeps its full prompt; see _preempt).
        ready = self._grow_or_preempt(decoding, 1)

        rows: list[tuple[Sequence, list[int], int, int]] = []
        kinds: list[str] = []
        drafts: list[list[int]] = []
        feed_rows: list[int | None] = []
        total = 0
        # Speculating lanes may draft up to spec_k extra tokens, but the
        # mixed step keeps one block-sized chunk of budget in reserve so
        # drafting can never starve prefill admission — and every draft
        # cap pre-charges the base token of EVERY lane still to come, so
        # the step total stays under the budget no matter how many lanes
        # speculate (the row cap above already bounds base tokens alone
        # at budget - 1, the pre-speculation invariant).
        spec_budget = budget - bs
        for idx, seq in enumerate(ready):
            draft: list[int] = []
            if seq.spec is not None:
                lanes_after = len(ready) - idx - 1
                draft = self._draft_for(
                    seq, spec_budget - total - 1 - lanes_after
                )
                if draft and not self._grow_blocks(seq, 1 + len(draft)):
                    draft = []
            cursor = self._eff_processed(seq)
            src = self._feed_src(seq)
            row_toks = [0 if src is not None else seq.pending] + draft
            rows.append((seq, row_toks, cursor, cursor + len(row_toks)))
            kinds.append("v" if seq.spec is not None else "d")
            drafts.append(draft)
            feed_rows.append(src)
            total += len(row_toks)
        n_decode = len(rows)
        decode_row_tokens = total  # decode + drafted verify tokens
        t_drafted = time.time()
        # Rows that actually drafted: no-match speculating lanes are
        # plain decode rows for accounting (mocker-identical series).
        n_spec_rows = sum(1 for d in drafts if d)
        if n_spec_rows:
            self._tracer.record(
                "spec_draft", t_step, t_drafted,
                attrs={
                    "seqs": n_spec_rows,
                    "drafted": sum(len(d) for d in drafts),
                },
                stat=True,
            )
        for seq in prefills:
            if seq not in self.running:
                continue  # preempted above
            if len(rows) >= S_max:
                break
            room = min(budget - total, chunk_cap)
            if room <= 0:
                break
            p0 = seq.prefilled + self._adv3(seq)[0]
            remaining = seq.prompt_len - p0
            chunk = min(remaining, room)
            if chunk < remaining:
                # Non-final chunks split on block boundaries so both
                # schedulers commit identical block layouts (disagg
                # export/import and prefix-cache hashes line up).
                chunk -= chunk % bs
                if chunk <= 0:
                    continue
            if not self._hold_window(seq, p0, chunk):
                continue  # the window pool is short: this prompt waits
            self._mark_first_sched(seq, t_step)
            rows.append((seq, seq.prompt[p0 : p0 + chunk], p0, p0 + chunk))
            kinds.append("p")
            drafts.append([])
            feed_rows.append(None)
            total += chunk
        if not rows:
            return None

        # Only verify rows sample more than their last position; a
        # prefill chunk's mid-prompt logits stay unsampled noise.
        pend = self._dispatch_ragged(
            rows, self._decode_width(len(rows)),
            n_sample=[
                len(tl) if kind == "v" else 1
                for (_, tl, _, _), kind in zip(rows, kinds)
            ],
            feed_rows=feed_rows, kind="mixed",
        )
        deterministic = n_spec_rows == 0
        adv: dict[str, tuple[int, int, int]] = {}
        feed_index: dict[str, int] = {}
        feed_series: dict[str, tuple[int, int, int]] = {}
        for i, ((seq, toks_list, p0, _kv), kind) in enumerate(zip(rows, kinds)):
            if kind in ("d", "v"):
                adv[seq.request_id] = (0, 1, 1)
                if deterministic:
                    feed_index[seq.request_id] = i  # R == 1: column 0
                    feed_series[seq.request_id] = (i, 0, 1)
            else:
                chunk = len(toks_list)
                done = p0 + chunk >= seq.prompt_len
                adv[seq.request_id] = (chunk, chunk, 1 if done else 0)
                if done and deterministic:
                    feed_index[seq.request_id] = i
                    feed_series[seq.request_id] = (i, 0, 1)

        # dynalint: holds-lock(_step_lock) — commits run inside the step
        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            outputs: list[tuple[Sequence, LLMEngineOutput]] = []
            toks2, lps2 = pend.land()
            # Column 0 is each row's single-sample slot (decode rows and
            # prefill chunks); verify rows read their full sample width.
            toks = toks2[:, 0]
            lps = None if lps2 is None else tuple(a[:, 0] for a in lps2)
            now = time.time()
            drafted_total = accepted_total = spec_emitted = 0
            live = {id(s) for s in self.running}
            for i, ((seq, toks_list, _pos0, _kv), kind) in enumerate(
                zip(rows, kinds)
            ):
                if seq.finish is not None or seq.cancelled or id(seq) not in live:
                    continue  # late finish/preempt: discard the row
                if kind == "v":
                    out, d, a = self._apply_verify_row(
                        seq, drafts[i], toks2[i], lps2, i
                    )
                    outputs.append((seq, out))
                    drafted_total += d
                    accepted_total += a
                    if d:
                        spec_emitted += len(out.token_ids)
                    continue
                if kind == "d":
                    # The row wrote the pending token's K/V and sampled
                    # the next token — the 1-step unrolling of the decode
                    # chain's bookkeeping.
                    completed = seq.hashed.extend([seq.pending])
                    self._commit_completed(seq, completed)
                    seq.processed += 1
                    seq.generated += 1
                    tok = int(toks[i])
                    lp = None
                    if lps is not None and seq.logprobs is not None:
                        lp = _lp_entry(
                            tok, lps[0][i], lps[1][i], lps[2][i], seq.logprobs
                        )
                    outputs.append((seq, self._emit(seq, tok, lp)))
                    if seq.finish is not None:
                        self._finish(seq)
                    else:
                        seq.pending = tok
                    continue
                tok, lp = self._advance_prefill_chunk(
                    seq, len(toks_list), toks, lps, i, t_step, now
                )
                if tok is not None:  # this chunk completed the prompt
                    seq.pending = tok
                    seq.generated += 1
                    outputs.append((seq, self._emit(seq, tok, lp)))
                    if seq.finish is not None:
                        self._finish(seq)
            if n_spec_rows:
                self.spec_stats.verify_steps += 1
                self._tracer.record(
                    "spec_verify", t_drafted, now,
                    attrs={
                        "seqs": n_spec_rows, "drafted": drafted_total,
                        "accepted": accepted_total, "tokens": spec_emitted,
                    },
                    stat=True,
                )

            st = self.sched_stats
            st["mixed_steps"] += 1
            st["last_step_batched_tokens"] = total
            st["last_step_budget_utilization"] = total / budget if budget else 0.0
            st["chunked_prefills_in_flight"] = sum(
                1 for s in self.running if not s.prefill_done and s.t_first_sched
            )
            self._tracer.record(
                "engine_mixed_step", t_step, now,
                attrs={
                    "seqs": len(rows), "decode_rows": n_decode,
                    "prefill_tokens": total - decode_row_tokens,
                    "budget": budget,
                },
                stat=True,
            )
            return outputs

        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv,
            feed_tokens=pend.toks, feed_index=feed_index,
            deterministic=deterministic, feed_series=feed_series,
        )

    def _plan_fused(
        self, prefills: list[Sequence],
        decoding: list[Sequence] | None = None,
    ) -> _PlannedStep | None:
        """Plan one UNIVERSAL megastep (ISSUE 12): every step shape rides
        the scanned device body. Decode rows and speculative verify rows
        fuse with ``n_steps - 1`` on-device decode continuations — verify
        accept/reject resolves inside the dispatch, rejected drafts roll
        back on device via the lane's position cursor — and prefill
        chunks ride the same ragged first iteration, continuing as
        decode rows when they complete their prompt. Returns None when
        fusion cannot apply (watch overflow — the one documented forced-
        k=1 path — or a budget/context edge, or nothing that would
        continue); the caller falls back to the bit-identical legacy
        single-step paths.

        ALL block growth happens before ANY dispatch (the _plan_decode
        contract): each lane's full fused headroom — n_steps tokens per
        decode lane, n_steps + draft per verify lane, chunk + n_steps - 1
        per completing prefill chunk — is reserved at plan time, so
        mid-megastep block exhaustion is impossible by construction;
        pressure surfaces as preemption (or _NeedDrain under async)
        while nothing is enqueued. Draft growth failure degrades that
        row to q_len=1; continuation growth failure degrades a
        completing chunk to the single-step bookkeeping."""
        t_step = time.time()
        budget = self.engine.token_budget
        chunk_cap = self.engine.chunk_size
        bs = self.engine.block_size
        S_max = self.engine.decode_buckets[-1]

        if decoding is None:
            decoding = self._decode_candidates()
        prefills = [s for s in prefills if s in self.running]
        if not decoding and not prefills:
            return None
        if not prefills and not any(s.spec is not None for s in decoding):
            # Pure non-speculating decode: the decode-only scanned body
            # (_plan_megastep) is the cheaper program — no ragged first
            # iteration, no verify width.
            return None
        if not decoding:
            # Pure-prefill step: fusing pays only when a chunk can
            # COMPLETE its prompt this step (and continue decoding on
            # device); a long prompt mid-chunking gains nothing, so
            # skip the doomed assembly — the single-step path is exact.
            room = min(budget, chunk_cap)
            if not any(
                s.prompt_len - (s.prefilled + self._adv3(s)[0]) <= room
                for s in prefills
            ):
                return None
        lanes = decoding or prefills
        n_steps = self._chain_length(lanes)
        if n_steps <= 1:
            return None

        # Decode-lane selection mirrors _plan_mixed: reserve one row plus
        # budget headroom for a prefill chunk, rotate lanes sitting out.
        # With no prefills the budget still bounds base row tokens (the
        # legacy _plan_verify deferred over-budget lanes the same way —
        # a batch of S_max bases must not overflow a small
        # max_num_batched_tokens on a waves engine).
        cap = min(S_max - 1, budget - 1) if prefills else min(S_max, budget)
        if len(decoding) > cap:
            off = self.iterations % len(decoding)
            decoding = (decoding + decoding)[off : off + cap]
        ready = self._grow_or_preempt(decoding, n_steps)

        rows: list[tuple[Sequence, list[int], int, int]] = []
        kinds: list[str] = []
        drafts: list[list[int]] = []
        feed_rows: list[int | None] = []
        cont: list[bool] = []
        device: list[bool] = []
        total = 0
        # The one-block draft reserve exists so drafting can never starve
        # prefill admission (_plan_mixed's invariant); with no prefill
        # rows there is nothing to starve, and the legacy verify path
        # drafted against the full budget — keep that headroom.
        spec_budget = budget - bs if prefills else budget
        # On-device drafting (ISSUE 18) compounds accepted depth: up to
        # 1 + (n_steps - 1) * R tokens per dispatch per lane. Plan-time
        # headroom reserves that worst case — blocks AND context room —
        # or the lane degrades to the host-drafted verify row.
        dd_room = 1 + (n_steps - 1) * self._spec_R
        for idx, seq in enumerate(ready):
            draft: list[int] = []
            dev = False
            if seq.spec is not None:
                if (
                    self._spec_device
                    and seq.spec.device
                    and self.engine.max_model_len - self._eff_processed(seq)
                    >= dd_room
                    and self._grow_blocks(seq, dd_room)
                ):
                    dev = True  # drafts on device; no host proposal
                else:
                    lanes_after = len(ready) - idx - 1
                    draft = self._draft_for(
                        seq, spec_budget - total - 1 - lanes_after,
                        reserve=n_steps - 1,
                    )
                    if draft and not self._grow_blocks(
                        seq, n_steps + len(draft)
                    ):
                        draft = []  # block pressure: verify degrades to q_len=1
            cursor = self._eff_processed(seq)
            src = self._feed_src(seq)
            row_toks = [0 if src is not None else seq.pending] + draft
            rows.append((seq, row_toks, cursor, cursor + len(row_toks)))
            kinds.append("v" if seq.spec is not None and not dev else "d")
            drafts.append(draft)
            feed_rows.append(src)
            cont.append(True)
            device.append(dev)
            total += len(row_toks)
        n_decode = len(rows)
        decode_row_tokens = total
        t_drafted = time.time()
        n_spec_rows = sum(1 for d in drafts if d)
        if n_spec_rows:
            self._tracer.record(
                "spec_draft", t_step, t_drafted,
                attrs={
                    "seqs": n_spec_rows,
                    "drafted": sum(len(d) for d in drafts),
                },
                stat=True,
            )
        for seq in prefills:
            if seq not in self.running:
                continue  # preempted above
            if len(rows) >= S_max:
                break
            room = min(budget - total, chunk_cap)
            if room <= 0:
                break
            p0 = seq.prefilled + self._adv3(seq)[0]
            remaining = seq.prompt_len - p0
            chunk = min(remaining, room)
            if chunk < remaining:
                chunk -= chunk % bs
                if chunk <= 0:
                    continue
            if not self._hold_window(seq, p0, chunk):
                continue  # the window pool is short: this prompt waits
            self._mark_first_sched(seq, t_step)
            # A chunk that completes its prompt continues as a decode
            # row — when its watch fits the device flags, the context
            # edge leaves room for the continuation writes, and the
            # extra block headroom is reservable; otherwise it degrades
            # to the single-step bookkeeping (first token only).
            cont_ok = bool(
                chunk == remaining
                and self._watch_len(seq) <= MEGASTEP_WATCH_W
                and self.engine.max_model_len - (p0 + chunk) >= n_steps - 1
                and self._grow_blocks(seq, chunk + n_steps - 1)
            )
            rows.append((seq, seq.prompt[p0 : p0 + chunk], p0, p0 + chunk))
            kinds.append("p")
            drafts.append([])
            feed_rows.append(None)
            cont.append(cont_ok)
            device.append(False)
            total += chunk
        if not rows or not any(cont):
            return None  # nothing continues on device: plain step is exact

        n_chunk = len(rows) - n_decode
        n_sample = [
            len(tl) if kind == "v" else 1
            for (_, tl, _, _), kind in zip(rows, kinds)
        ]
        S = self._decode_width(len(rows))
        use_dd = any(device)
        pend = self._dispatch_fused(
            rows, S, n_sample, feed_rows, kinds, drafts, cont, n_steps,
            device=device,
        )
        R = self._spec_R if use_dd or any(n > 1 for n in n_sample) else 1
        deterministic = n_spec_rows == 0 and not use_dd
        adv: dict[str, tuple[int, int, int]] = {}
        feed_index: dict[str, int] = {}
        feed_series: dict[str, tuple[int, int, int]] = {}
        last_flat = (n_steps - 1) * S * R
        for i, ((seq, toks_list, p0, _kv), kind) in enumerate(zip(rows, kinds)):
            if kind in ("d", "v"):
                if drafts[i] or device[i]:
                    # Data-dependent advance (live draft — host or
                    # device): the async loop commits before planning
                    # over it; the overlay only needs the guaranteed
                    # lower bound (iteration 0 always emits one token).
                    adv[seq.request_id] = (0, 1, 1)
                else:
                    adv[seq.request_id] = (0, n_steps, n_steps)
                    if deterministic:
                        feed_index[seq.request_id] = last_flat + i * R
                        feed_series[seq.request_id] = (i * R, S * R, n_steps)
            else:
                chunk = len(toks_list)
                if cont[i]:
                    adv[seq.request_id] = (chunk, chunk + n_steps - 1, n_steps)
                    if deterministic:
                        feed_index[seq.request_id] = last_flat + i * R
                        feed_series[seq.request_id] = (i * R, S * R, n_steps)
                else:
                    done = p0 + chunk >= seq.prompt_len
                    adv[seq.request_id] = (chunk, chunk, 1 if done else 0)
                    if done and deterministic:
                        feed_index[seq.request_id] = i * R
                        feed_series[seq.request_id] = (i * R, 0, 1)

        # dynalint: holds-lock(_step_lock) — commits run inside the step
        def commit() -> list[tuple[Sequence, LLMEngineOutput]]:
            outputs: list[tuple[Sequence, LLMEngineOutput]] = []
            toks3, lps3 = pend.land()  # [n_steps, S, R]
            # Device-draft round accounting ([3, n_steps, S]: emitted /
            # drafted / accepted per round) rides its own landing copy.
            aux3 = pend.land_aux() if use_dd else None
            now = time.time()
            drafted_total = accepted_total = spec_emitted = 0
            emitted_total = 0
            # Occupancy, as _plan_megastep counts it: every row holds its
            # lane for n_steps iterations; useful are the iterations
            # that gave the client at least one token.
            issued_iters = len(rows) * n_steps
            useful_iters = 0
            dd_rounds = dd_hits = 0
            live = {id(s) for s in self.running}
            # Iteration-0 single-slot views: the k=1 commit shape the
            # prefill-chunk bookkeeping expects.
            toks0 = toks3[0, :, 0]
            lps0 = None if lps3 is None else tuple(a[0, :, 0] for a in lps3)
            for i, ((seq, toks_list, _pos0, _kv), kind) in enumerate(
                zip(rows, kinds)
            ):
                if seq.finish is not None or seq.cancelled or id(seq) not in live:
                    continue  # late finish/preempt: discard the lane
                if kind == "p":
                    tok, lp = self._advance_prefill_chunk(
                        seq, len(toks_list), toks0, lps0, i, t_step, now
                    )
                    if tok is None:
                        # Mid-prompt: iteration 0 was prefill work, not a
                        # decode iteration; masked no-ops ran after it.
                        issued_iters -= 1
                        continue
                    if not cont[i]:
                        # Degraded lane: exactly the single-step books.
                        seq.pending = tok
                        seq.generated += 1
                        outputs.append((seq, self._emit(seq, tok, lp)))
                        emitted_total += 1
                        useful_iters += 1
                        if seq.finish is not None:
                            self._finish(seq)
                        continue
                    # Fused continuation: E = [t0] + scanned tokens; the
                    # scan wrote E[:-1] past the completed prompt.
                    E = [tok] + [int(t) for t in toks3[1:, i, 0]]
                    k_take, finish = self._scan_stop(seq, np.asarray(E))
                    completed = seq.hashed.extend(E[: k_take - 1])
                    self._commit_completed(seq, completed)
                    seq.processed += k_take - 1
                    seq.generated += k_take
                    emitted = E[:k_take]
                    lp_entries = None
                    if lps3 is not None and seq.logprobs is not None:
                        lp_entries = [lp] + [
                            _lp_entry(
                                emitted[j], lps3[0][j][i][0],
                                lps3[1][j][i][0], lps3[2][j][i][0],
                                seq.logprobs,
                            )
                            for j in range(1, k_take)
                        ]
                    outputs.append(
                        (seq, self._emit_chunk(seq, emitted, lp_entries, finish))
                    )
                    emitted_total += len(emitted)
                    useful_iters += k_take  # one token an iteration
                    if finish is not None:
                        seq.finish = finish
                        self._finish(seq)
                    else:
                        seq.pending = emitted[-1]
                    continue
                if device[i]:
                    # On-device-drafted lane (ISSUE 18): the emission is
                    # data-dependent per ROUND, so the host replays the
                    # device's own per-round accounting — emitted counts
                    # say which [round, slot] cells carry real tokens;
                    # the stop scan then truncates exactly like every
                    # other path (host authority; the device only ever
                    # under-stops, so E always covers the stop point).
                    em = aux3[0, :, i]
                    dl = aux3[1, :, i]
                    ac = aux3[2, :, i]
                    E: list[int] = []
                    lp_at: list[tuple[int, int]] = []
                    for r in range(n_steps):
                        e_r = int(em[r])
                        for j in range(e_r):
                            E.append(int(toks3[r, i, j]))
                            lp_at.append((r, j))
                        if r:
                            if e_r:
                                dd_rounds += 1
                            if int(dl[r]):
                                dd_hits += 1
                                self.spec_stats.observe_row(
                                    int(dl[r]), int(ac[r])
                                )
                                drafted_total += int(dl[r])
                                accepted_total += int(ac[r])
                    k_take, finish = self._scan_stop(seq, np.asarray(E))
                    written = [seq.pending] + E[: k_take - 1]
                    completed = seq.hashed.extend(written)
                    self._commit_completed(seq, completed)
                    seq.processed += k_take
                    seq.generated += k_take
                    emitted = E[:k_take]
                    lp_entries = None
                    if lps3 is not None and seq.logprobs is not None:
                        lp_entries = [
                            _lp_entry(
                                emitted[j],
                                lps3[0][lp_at[j][0], i, lp_at[j][1]],
                                lps3[1][lp_at[j][0], i, lp_at[j][1]],
                                lps3[2][lp_at[j][0], i, lp_at[j][1]],
                                seq.logprobs,
                            )
                            for j in range(k_take)
                        ]
                    outputs.append(
                        (seq, self._emit_chunk(seq, emitted, lp_entries, finish))
                    )
                    emitted_total += len(emitted)
                    spec_emitted += len(emitted)
                    taken = 0
                    for r in range(n_steps):  # rounds that fed the client
                        if taken < k_take and int(em[r]):
                            useful_iters += 1
                        taken += int(em[r])
                    if finish is not None:
                        seq.finish = finish
                        self._finish(seq)
                    else:
                        seq.pending = emitted[-1]
                    continue
                # Decode / verify rows: replay the device accept — the
                # longest drafted prefix matching the target's own
                # per-position choices (deterministic, so host and
                # device can never disagree).
                draft = drafts[i]
                d = len(draft)
                a = 0
                while a < d and int(toks3[0, i, a]) == draft[a]:
                    a += 1
                if d:
                    self.spec_stats.observe_row(d, a)
                E = [int(toks3[0, i, j]) for j in range(a + 1)] + [
                    int(t) for t in toks3[1:, i, 0]
                ]
                k_take, finish = self._scan_stop(seq, np.asarray(E))
                # Valid cache writes: the old pending token, the accepted
                # drafted tokens, and the scanned continuation. Rejected
                # drafts' K/V sits PAST the cursor — never attended, and
                # overwritten in place by the on-device continuation.
                written = [seq.pending] + E[: k_take - 1]
                completed = seq.hashed.extend(written)
                self._commit_completed(seq, completed)
                seq.processed += k_take
                seq.generated += k_take
                emitted = E[:k_take]
                lp_entries = None
                if lps3 is not None and seq.logprobs is not None:
                    def _at(j, a=a, i=i):
                        return (0, i, j) if j <= a else (j - a, i, 0)
                    lp_entries = [
                        _lp_entry(
                            emitted[j], lps3[0][_at(j)], lps3[1][_at(j)],
                            lps3[2][_at(j)], seq.logprobs,
                        )
                        for j in range(k_take)
                    ]
                outputs.append(
                    (seq, self._emit_chunk(seq, emitted, lp_entries, finish))
                )
                emitted_total += len(emitted)
                # Iteration 0 gave a + 1 tokens, each later one gave one.
                useful_iters += 1 + max(0, k_take - a - 1)
                if d:
                    drafted_total += d
                    accepted_total += a
                    spec_emitted += len(emitted)
                if finish is not None:
                    seq.finish = finish
                    self._finish(seq)
                else:
                    seq.pending = emitted[-1]

            self.exec_stats["megastep_issued_lane_iters"] += issued_iters
            self.exec_stats["megastep_useful_lane_iters"] += useful_iters
            t_done = time.time()
            if n_spec_rows or use_dd:
                self.spec_stats.verify_steps += 1
                self.spec_stats.device_rounds += dd_rounds
                self.spec_stats.device_hits += dd_hits
                self._tracer.record(
                    "spec_verify", t_drafted, t_done,
                    attrs={
                        "seqs": n_spec_rows + sum(device),
                        "drafted": drafted_total,
                        "accepted": accepted_total, "tokens": spec_emitted,
                    },
                    stat=True,
                )
            st = self.sched_stats
            if n_chunk:
                st["mixed_steps"] += 1
                st["last_step_batched_tokens"] = total
                st["last_step_budget_utilization"] = (
                    total / budget if budget else 0.0
                )
                st["chunked_prefills_in_flight"] = sum(
                    1 for s in self.running
                    if not s.prefill_done and s.t_first_sched
                )
                self._tracer.record(
                    "engine_mixed_step", t_step, t_done,
                    attrs={
                        "seqs": len(rows), "decode_rows": n_decode,
                        "prefill_tokens": total - decode_row_tokens,
                        "budget": budget,
                    },
                    stat=True,
                )
            self._tracer.record(
                "engine_megastep", t_step, t_done,
                attrs={
                    "seqs": len(rows), "inner_steps": n_steps,
                    "tokens": emitted_total,
                    "draft_rounds": dd_rounds,
                    "fused_shapes": {
                        "decode": kinds.count("d") - sum(device),
                        "chunk": kinds.count("p"),
                        "verify": kinds.count("v"),
                        "device": sum(device),
                    },
                },
                stat=True,
            )
            return outputs

        return _PlannedStep(
            core=self, commit_fn=commit, adv=adv,
            feed_tokens=pend.toks, feed_index=feed_index,
            deterministic=deterministic, feed_series=feed_series,
        )

    def _scan_stop(self, seq: Sequence, toks: np.ndarray) -> tuple[int, str | None]:
        """Vectorized stop scan over a decode chain's sampled tokens:
        returns (tokens emitted, finish reason or None). Token-level
        precedence (eos > stop > length) is decided by check_token on the
        single stopping token — one Python stop-check per CHAIN instead of
        per token (the per-token host loop measured ~150 us/token,
        PERF.md)."""
        stop = seq.stop
        n = len(toks)
        k = n
        watch: list[int] = []
        if not stop.ignore_eos:
            watch.extend(self.eos_token_ids)
        watch.extend(stop.stop_token_ids)
        if watch:
            cand = np.isin(toks, np.asarray(watch, toks.dtype))
            # min_tokens: stop triggers only once the budget floor passes.
            if stop.min_tokens:
                gen_after = seq.generated + np.arange(1, n + 1)
                cand &= gen_after >= stop.min_tokens
            if cand.any():
                k = int(np.argmax(cand)) + 1
        if stop.max_tokens is not None:
            k = min(k, stop.max_tokens - seq.generated)
        k = max(1, k)
        finish = stop.check_token(int(toks[k - 1]), seq.generated + k, self.eos_token_ids)
        return k, finish

    def _watch_len(self, seq: Sequence) -> int:
        """Ids this lane's on-device stop watch would need to hold."""
        n = len(seq.stop.stop_token_ids)
        if not seq.stop.ignore_eos:
            n += len(self.eos_token_ids)
        return n

    def _chain_length(self, seqs: list[Sequence]) -> int:
        """Inner iterations of this megastep: the resolved megastep k
        (``--megastep-k``),
        capped by the context edge (hard limit — no writes past the
        block table) and by the batch's LARGEST remaining generation
        budget (with every lane's budget nearly spent, long megasteps
        are pure overshoot — the short-budget tool-call workload).
        Snapped down to a power of two so the compiled-program count
        stays O(log k); per-lane overshoot within a megastep is masked
        on device by the stop flags and discarded by the host
        stop-scan.

        A lane whose stop watch exceeds the device's MEGASTEP_WATCH_W
        slots forces the batch to k=1 instead of silently truncating the
        watch: at k=1 the host stop-scan (which checks the FULL list)
        runs after every token, so the truncated device flags can never
        cause masked-no-op waste or surprise K/V rollbacks mid-chain."""
        k_cfg = self.engine.megastep
        if k_cfg > 1 and any(
            self._watch_len(s) > MEGASTEP_WATCH_W for s in seqs
        ):
            # The one documented forced-k=1 path: surfaced on /metrics so
            # the mixed-traffic smoke can assert it never fires for
            # ordinary requests (ISSUE 12 acceptance). Counted once per
            # engine iteration — the fused attempt and its legacy
            # fallback both land here for the same forced batch.
            if getattr(self, "_forced_single_iter", -1) != self.iterations:
                self._forced_single_iter = self.iterations
                self.exec_stats["megastep_forced_single"] += 1
            if not getattr(self, "_watch_overflow_warned", False):
                self._watch_overflow_warned = True
                over = next(
                    s for s in seqs if self._watch_len(s) > MEGASTEP_WATCH_W
                )
                log.warning(
                    "request %s watches %d stop ids but the device stop "
                    "watch holds %d: forcing megastep k=1 for its batches "
                    "(host-side stop scan covers the full list)",
                    over.request_id, self._watch_len(over), MEGASTEP_WATCH_W,
                )
            return 1
        ctx_cap = min(
            self.engine.max_model_len - self._eff_processed(s) for s in seqs
        )
        budget_cap = max(
            (
                s.stop.max_tokens - self._eff_generated(s)
                if s.stop.max_tokens is not None
                else k_cfg
            )
            for s in seqs
        )
        n = max(1, min(k_cfg, ctx_cap, budget_cap))
        if n == k_cfg:
            return n
        # Snap to a power of two (bounded compiled-program count). Round
        # UP when the overshoot is small (<=1/3): a budget of 127 should
        # run one 128-step megastep, not a 64+32+16+... cascade of fixed
        # per-invocation overheads.
        up = 1 << (n - 1).bit_length()
        if up <= min(k_cfg, ctx_cap) and up * 3 <= n * 4:
            return up
        return 1 << (n.bit_length() - 1)

    def _emit_chunk(
        self,
        seq: Sequence,
        tokens: list[int],
        lp_entries: list[dict] | None,
        finish: str | None,
    ) -> LLMEngineOutput:
        """One streamed chunk for a whole decode chain or verify row
        (stop already decided by _scan_stop — ``tokens`` is exactly what
        the client gets)."""
        seq.out_tokens.extend(tokens)
        self.exec_stats["committed_tokens"] += len(tokens)
        self.exec_stats["decode_tokens_committed"] += (
            len(tokens) - (not seq.emitted_first)
        )
        out = LLMEngineOutput(token_ids=tokens)
        if lp_entries:
            out.logprobs = lp_entries
        if not seq.emitted_first:
            seq.emitted_first = True
            out.meta = {
                "cached_tokens": seq.num_cached_tokens,
                "iteration": self.iterations,
            }
        if finish is not None:
            out.finish_reason = finish
            out.prompt_tokens = seq.prompt_len
            out.completion_tokens = seq.generated
            if seq.hold_blocks:
                out.kv_transfer_params = {
                    "request_id": seq.request_id,
                    "block_hashes": list(seq.pinned_hashes[: seq.committed_blocks]),
                    "block_size": self.engine.block_size,
                }
        return out

    def _emit(self, seq: Sequence, token: int, lp: dict | None = None) -> LLMEngineOutput:
        """Emit the newest sampled token. ``seq.generated`` already counts
        it, on both the prefill and decode paths."""
        seq.out_tokens.append(token)
        self.exec_stats["committed_tokens"] += 1
        self.exec_stats["decode_tokens_committed"] += int(seq.emitted_first)
        finish = self._check_stop(seq, token)
        out = LLMEngineOutput(token_ids=[token])
        if lp is not None:
            out.logprobs = [lp]
        if not seq.emitted_first:
            seq.emitted_first = True
            out.meta = {
                "cached_tokens": seq.num_cached_tokens,
                "iteration": self.iterations,
            }
        if finish is not None:
            seq.finish = finish
            out.finish_reason = finish
            out.prompt_tokens = seq.prompt_len
            out.completion_tokens = seq.generated
            if seq.hold_blocks:
                out.kv_transfer_params = {
                    "request_id": seq.request_id,
                    "block_hashes": list(seq.pinned_hashes[: seq.committed_blocks]),
                    "block_size": self.engine.block_size,
                }
        return out

    def _check_stop(self, seq: Sequence, token: int) -> str | None:
        return seq.stop.check_token(token, seq.generated, self.eos_token_ids)

    # dynalint: holds-lock(_step_lock) — only called from the step path
    def _finish(self, seq: Sequence) -> None:
        if seq in self.running:
            self.running.remove(seq)
        if seq.hold_blocks:
            self._held[seq.request_id] = seq
            if self.engine.held_block_ttl_s > 0:
                self._held_deadline[seq.request_id] = (
                    time.monotonic() + self.engine.held_block_ttl_s
                )
            if self.on_chunk_commit is not None:
                # Final cursor: the hold is complete, only the tail (if
                # anything) remains for a streaming puller.
                self.on_chunk_commit(
                    seq.request_id, seq.committed_blocks, True
                )
        else:
            self._release_blocks(seq)

    # -- embeddings --------------------------------------------------------

    def embed(self, token_ids: list[int]) -> np.ndarray:
        """Mean-pooled final-hidden embedding of one prompt ([h] f32).

        Runs on a dedicated scratch paged cache (lazily built, reused,
        donated) so the serving cache and allocator are untouched; length
        snaps to the prefill buckets. The /v1/embeddings engine path
        (reference service_v2.rs:277-336 routes embeddings through its
        engines the same way)."""
        T = len(token_ids)
        if T == 0:
            raise ValueError("empty input")
        with self._embed_lock:
            return self._embed_locked(token_ids, T)

    def _embed_locked(self, token_ids: list[int], T: int) -> np.ndarray:
        bucket = self._bucket_for(T)
        bs = self.engine.block_size
        n_pages = -(-bucket // bs)
        if getattr(self, "_embed_scratch", None) is None:
            from dynamo_tpu.engine.model import cache_for_blocks

            # a plane per pass, a page shape per layer kind (model.init_cache)
            blocks = -(-self.engine.prefill_buckets[-1] // bs)
            scratch_engine = dataclasses.replace(self.engine, kv_dtype="bf16")
            embed_engine = self.engine
            if self.cfg.windowed:
                # both of the scratch's pools hold the one prompt whole, and
                # its table is as wide: the window table starts at block 0
                scratch_engine = embed_engine = dataclasses.replace(
                    scratch_engine, num_kv_blocks=blocks, num_window_blocks=blocks,
                    max_model_len=blocks * bs)
            if self.cfg.has_slab:   # the table is the scratch's blocks and the slot's column
                scratch_engine = embed_engine = dataclasses.replace(
                    scratch_engine, num_kv_blocks=blocks, max_model_len=blocks * bs)
            # (a model with linear layers: one lane slot and the garbage slot)
            self._embed_scratch = cache_for_blocks(self.cfg, scratch_engine, blocks, slots=2)
            self._embed_fn = jax.jit(
                _program(embed_forward, cfg=self.cfg, engine=embed_engine, mesh=self.mesh),
                donate_argnums=(1,),
            )
        garbage = -(-self.engine.prefill_buckets[-1] // bs)   # the scratch's blocks
        tokens = np.zeros(bucket, np.int32)
        tokens[:T] = token_ids
        valid = np.zeros(bucket, bool)
        valid[:T] = True
        write_pages = np.full(bucket, garbage, np.int32)
        write_pages[:T] = np.arange(T) // bs
        tables = np.full((1, garbage), garbage, np.int32)
        tables[0, :n_pages] = np.arange(n_pages)
        if self.cfg.windowed:   # [full | first = 0 | window], the same blocks
            tables = np.concatenate(
                [tables, np.zeros((1, 1), np.int32), tables], axis=1)
        if self.cfg.has_slab:   # [blocks | lane slot 0]; position 0 reads zeros
            tables = np.concatenate([tables, np.zeros((1, 1), np.int32)], axis=1)
        pooled, self._embed_scratch = self._embed_fn(
            self.params,
            self._embed_scratch,
            jnp.asarray(tokens),
            jnp.asarray(valid),
            jnp.asarray(write_pages),
            jnp.asarray(tables),
        )
        return fetch_replicated(pooled)

    def clear_kv_cache(self) -> int:
        """Drop every unpinned cached block (admin surface — reference
        clear_kv_blocks.rs). In-flight sequences keep their pinned
        blocks; returns blocks cleared."""
        with self._step_lock:
            return len(self.allocator.clear_cache())

    # -- observability -----------------------------------------------------

    def scheduler_stats(self) -> dict:
        """Point-in-time scheduler gauges (status-server /metrics export):
        queue depth, last mixed-step token-budget utilization, chunked
        prefills in flight, preemption count."""
        st = dict(self.sched_stats)
        st["waiting"] = len(self.waiting) + len(self._inbox)
        st["running"] = len(self.running)
        st["chunked_scheduling"] = 1 if self._sched_chunked else 0
        st["token_budget"] = self.engine.token_budget
        st["async_exec"] = 1 if self.pipelined else 0
        st["queue_limit"] = self._max_waiting
        st["fair_enabled"] = 1 if self.engine.fair_scheduling else 0
        st.update(self.exec_stats)
        # Prefill waves by the bucket they rode, and the measured ms of a
        # wave per bucket that their planner decides by (empty: none).
        st["prefill_waves"] = dict(self.prefill_waves)
        st["prefill_bucket_ms"] = dict(self.prefill_bucket_ms)
        st["megastep_k"] = self.engine.megastep
        st["block_length"] = self.cfg.block_length
        st["denoising_steps"] = self.cfg.denoising_steps
        toks = self.exec_stats["committed_tokens"]
        st["dispatches_per_token"] = (
            self.exec_stats["dispatches"] / toks if toks else 0.0
        )
        # Pipeline parallelism (ISSUE 20): stage count and the steady-
        # state pipe occupancy of a fused chain — k*M work items over
        # k*M + pp - 1 wavefront rounds (1.0 on non-pp engines: the
        # degenerate pp=1 pipe has no bubble).
        st["pp_stages"] = self._pp
        # What the loop costs the cache: planes of K/V per token (layers
        # x passes) and their bytes at the cache's dtype.
        st["kv_cache_layers"] = self.cfg.num_cache_layers
        st["kv_bytes_per_token"] = self.kv_bytes_per_token
        st["cache_layers"] = self.cfg.cache_layer_counts
        st.update(self.cache_by_kind())
        st["state_bytes_per_block"] = self.cfg.state_bytes_per_block()
        st["conv_state_reads"] = dict(self.conv_state_reads)
        st["state_bytes_per_sequence"] = self.cfg.state_bytes_per_sequence()
        if self._free_slots is not None:   # the slab's lane slots, the garbage slot apart
            st["state_slots"] = {"held": self.engine.max_num_seqs - len(self._free_slots),
                                 "free": len(self._free_slots)}
        st["prefix_caching"] = bool(self.engine.enable_prefix_caching)
        if self.window_allocator is not None:
            # The window pool: its size, what is held now and what was given
            # back, a decoding sequence's bytes there, the table's columns.
            st["window_blocks"] = self.window_allocator.capacity
            st["window_blocks_in_use"] = self.window_allocator.used_blocks
            st["window_bytes_per_sequence"] = self.cfg.window_bytes_per_sequence(
                self.engine.block_size)
            st["window_table_blocks"] = self.engine.window_table_blocks(
                self.cfg.sliding_window)
        st["attention"] = self.cfg.attention
        # A sparse model's share and what its router sent it, by the
        # program that counted (decode megasteps, prefill waves): held
        # experts touched and layer steps, pairs on held experts, pairs
        # routed and rows the expert products ran on
        # (model._shared_sparse_mlp).
        st["experts_held"] = self.cfg.num_experts_held if self.cfg.shared_sparse else 0
        st["expert_stats"] = {
            phase: [int(n) for n in counts]
            for phase, counts in self.expert_stats.items()
        }
        k = max(1, self.engine.megastep)
        km = k * self._pp_micro
        st["pp_pipe_occupancy"] = km / (km + self._pp - 1)
        return st

    def cache_by_kind(self) -> dict[str, dict]:
        """``cache_page_shape`` and ``cache_bytes_per_block`` by the kind of
        layer that caches them, as /health and /metrics give them: the page
        of ONE layer (``ModelConfig.kv_page_tail``) and the bytes a block of
        that kind's pool holds over all its layers."""
        bs = self.engine.block_size
        # (a linear layer's slab is indexed by lane slot: no block holds any of it)
        # (nor a feed-forward block's "none")
        kinds = [k for k, n in self.cfg.cache_layer_counts.items()
                 if n and k not in (*SLAB_KINDS, "none")]

        def block_bytes(kind: str) -> int:
            if kind == "conv":
                return self.cfg.state_bytes_per_block()
            if self.engine.kv_quantized:    # int8 pages and their scales
                return self.kv_bytes_per_token * bs
            return self.cfg.bytes_per_block(bs, kind)

        return {
            "cache_page_shape": {k: list(self.cfg.kv_page_tail(bs, k)) for k in kinds},
            "cache_bytes_per_block": {k: block_bytes(k) for k in kinds},
        }

    def step_phase_seconds(self) -> dict[tuple[str, str], float]:
        """Cumulative engine-loop seconds keyed ``(phase, blocks)``, the
        running phase included: the step clock's counters as /metrics
        exports them (``dynamo_engine_step_phase_seconds_total``)."""
        return {
            (phase, PHASES[phase]): seconds
            for phase, seconds in self.clock.seconds().items()
        }

    def device_account(self) -> dict[str, dict]:
        """The step clock's account of the device (device seconds and late
        landings by kind, starved seconds, lane-seconds), as /metrics
        exports it beside the phases (:meth:`StepClock.account`)."""
        return self.clock.account()

    def kv_cache_stats(self) -> dict:
        """Point-in-time prefix-cache gauges (status-server /metrics
        export). Two distinct series, never mixed: ``prefix_*`` are the
        allocator's match_prefix probe counters (router overlap scoring,
        disagg local-vs-remote decisions — counted since the prefix cache
        landed, never surfaced before); ``admitted_*`` count admitted
        sequences and whether their prefix was served from cache."""
        a = self.allocator
        return {
            # Quantized-KV observability (ISSUE 8): the capacity doubling
            # must be visible on /metrics, not just asserted in tests.
            "kv_dtype": self.engine.kv_dtype,
            "kv_dtype_int8": 1 if self.engine.kv_quantized else 0,
            "bytes_per_block": self.kv_bytes_per_token * self.engine.block_size,
            "capacity_blocks": a.capacity,
            "resident_blocks": a.used_blocks,
            "prefix_queries": a.prefix_queries,
            "prefix_hits": a.prefix_hits,
            "prefix_hit_rate": (
                a.prefix_hits / a.prefix_queries if a.prefix_queries else 0.0
            ),
            "admitted_queries": self._admit_prefix_queries,
            "admitted_hits": self._admit_prefix_hits,
            "admitted_hit_rate": (
                self._admit_prefix_hits / self._admit_prefix_queries
                if self._admit_prefix_queries
                else 0.0
            ),
        }

    def spec_decode_stats(self) -> dict:
        """Point-in-time speculation gauges (status-server /metrics export
        + ForwardPassMetrics.spec_decode): acceptance rate, mean accepted
        length, drafted/accepted/wasted token counters."""
        st = self.spec_stats.as_dict()
        st["enabled"] = 1 if self._spec_default is not None else 0
        return st

    def fair_queue_stats(self) -> dict[str, dict[str, float]]:
        """Per-tenant admission-queue depth + DRR deficit snapshot
        (status_server.bind_fair_queue_gauges — dynamic tenant labels)."""
        return self.waiting.stats()

    def metrics(self) -> ForwardPassMetrics:
        alloc = self.allocator
        return ForwardPassMetrics(
            worker=WorkerStats(
                request_active_slots=len(self.running),
                request_total_slots=self.engine.max_num_seqs,
                num_requests_waiting=len(self.waiting) + len(self._inbox),
                queue_limit=self._max_waiting,
                requests_shed_total=(
                    self.sched_stats["shed_total"]
                    + self.sched_stats["deadline_expired_total"]
                ),
                budget_utilization=self.sched_stats[
                    "last_step_budget_utilization"
                ],
            ),
            kv=KvStats(
                kv_active_blocks=alloc.used_blocks,
                kv_total_blocks=alloc.capacity,
                gpu_cache_usage_perc=alloc.usage_perc,
                gpu_prefix_cache_hit_rate=(
                    alloc.prefix_hits / alloc.prefix_queries
                    if alloc.prefix_queries
                    else 0.0
                ),
            ),
            transfer=dict(self.transfer_stats),
            # Populated once speculation is configured or any request used
            # it; None keeps pre-spec consumers byte-compatible.
            spec_decode=(
                self.spec_decode_stats()
                if self._spec_default is not None or self.spec_stats.verify_rows
                else None
            ),
            # Measured per-peer pull cost, installed by PeerKvClient when
            # the cluster-pool role wiring creates one (NetKV routing).
            net=(
                self.net_stats_source() or None
                if getattr(self, "net_stats_source", None) is not None
                else None
            ),
        )
