"""The JAX TPU backend worker: the native engine wired into the runtime.

``python -m dynamo_tpu.backends.jax --model-name tiny --preset tiny``
starts a worker process exactly shaped like the reference's vLLM shim
(`components/backends/vllm/src/dynamo/vllm/main.py:67-247`): connect to
the control plane, build the engine, publish KV events + load metrics,
register the model card, serve the generate endpoint. The engine is the
first-party JAX/Pallas one instead of a GPU subprocess.

Disaggregation (``--role prefill|decode``) follows the reference's vLLM
decode-first pattern (`handlers.py:113-168`, SURVEY.md §3.3): the decode
worker forwards long prefills to the prefill fleet with ``max_tokens=1``
and ``kv_transfer_params={do_remote_decode: true}``; the prefill worker
holds the request's KV blocks and returns descriptors; the decode worker
pulls the blocks over the data plane (`kv_transfer` endpoint — the
NIXL-equivalent host-staged DCN path), imports them into its cache, and
continues decoding against the now-local prefix.

Remote prefills route through a store WORK QUEUE, not a direct call
(reference NATS JetStream queue, `transports/nats.rs:433-600`): decode
pushes {request, reply_key} onto ``prefill:{namespace}``; prefill workers
pop only while they hold admission capacity, so ``queue_len`` is the real
fleet backlog the disagg router's queue-depth condition consults
(`disagg_router.rs:24-100`).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import logging
import math
import threading
import time
import uuid

import msgpack
from typing import Any, AsyncIterator

from dynamo_tpu import knobs
from dynamo_tpu.runtime import wire

from dynamo_tpu.llm.disagg import DisaggConfig, DisaggRouter
from dynamo_tpu.llm.disagg_pool import (
    ChunkCursorPublisher,
    ChunkCursorWatcher,
    StreamingHandoff,
)
from dynamo_tpu.llm.discovery import register_llm
from dynamo_tpu.llm.kv_pool import PeerKvClient
from dynamo_tpu.llm.kv_router.publisher import KvEventPublisher, WorkerMetricsPublisher
from dynamo_tpu.llm.model_card import ModelDeploymentCard, ModelRuntimeConfig
from dynamo_tpu.llm.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime import Context, DistributedRuntime
from dynamo_tpu.runtime import chaos
from dynamo_tpu.runtime.tasks import spawn_logged
from dynamo_tpu.runtime.worker import dynamo_worker
from dynamo_tpu.tracing import startclock

log = logging.getLogger("dynamo_tpu.backends.jax")


def _prefill_queue(namespace: str) -> str:
    """Store work-queue name for a namespace's prefill fleet."""
    return f"prefill:{namespace}"


async def _serve_kv_fetch(runtime, namespace: str, component: str, core) -> None:
    """Peer block server: stream the longest locally-held prefix of the
    requested hash chain (device tier or offload tiers) as raw pages.
    Cross-worker offload-tier visibility — reference KVBM-distributed
    leader/worker (block_manager/distributed/leader.rs:64)."""

    async def kv_fetch_handler(request: Any, context: Context) -> AsyncIterator[Any]:
        import numpy as np

        hashes = list(request.get(wire.KV_HASHES) or [])
        chunk = int(request.get(wire.KV_CHUNK_BLOCKS, 32))
        # Page geometry first (the kv_transfer descriptor pattern): the
        # consumer must parse our bytes with OUR layout, not assume its
        # own (cross-precision fleets).
        yield {
            wire.KV_VERSION: 2,
            wire.KV_SHAPE: list(core.kv_page_shape),
            # "int8" pages ship as the canonical packed buffer (int8 kv
            # bytes + f32 scales, engine/kv_quant.py); a mixed-dtype
            # consumer fails fast at import_blocks.
            wire.KV_DTYPE: core.kv_wire_dtype,
        }
        sent = 0
        for s in range(0, len(hashes), chunk):
            pages = await asyncio.to_thread(
                core.read_cached_pages, hashes[s : s + chunk]
            )
            if pages:
                yield {wire.KV_VERSION: 2, wire.KV_START: sent,
                       wire.KV_PAGES: pages}
                sent += len(pages)
            if len(pages) < min(chunk, len(hashes) - s):
                break  # hash chains are prefixes: first miss ends it
        yield {wire.KV_VERSION: 2, wire.KV_DONE: sent}

    ep = runtime.namespace(namespace).component(component).endpoint("kv_fetch")
    await ep.serve(kv_fetch_handler)


async def _resolve_mm(core, encode_client, embed_fetch_client, request: dict) -> None:
    """Resolve a request's image refs to embedding rows IN PLACE.

    Preferred path: the encoder fleet (reference
    examples/multimodal/encode_worker.py) — encode returns a descriptor,
    the tensor is pulled by id over the data plane. No encoder fleet (or
    a failure) falls back to encoding in-process: single-worker
    deployments stay multimodal."""
    import numpy as np

    from dynamo_tpu.llm.multimodal import image_bytes, patch_embed

    mm = request.get("mm")
    if not mm or mm.get("embeds") is not None or not mm.get("images"):
        return
    h = core.cfg.hidden_size
    use_fleet = encode_client is not None and encode_client.instance_ids()

    async def one(ref: str):
        if use_fleet:
            try:
                async with asyncio.timeout(30.0):
                    desc = None
                    stream = await encode_client.round_robin(
                        {"image": ref, "hidden_size": h}
                    )
                    async for out in stream:
                        desc = out
                    data = None
                    if desc and "embed_id" in desc:
                        fstream = await embed_fetch_client.direct(
                            desc["worker_id"], {"embed_id": desc["embed_id"]}
                        )
                        async for out in fstream:
                            data = out.get("data", data)
                    if data is None:
                        raise ConnectionError("encoder returned no embedding")
                    return np.frombuffer(data, np.float32).reshape(
                        tuple(desc["shape"])
                    )
            except Exception:  # noqa: BLE001 — local encode is equivalent
                log.warning("encoder fleet failed; encoding locally", exc_info=True)
        return await asyncio.to_thread(patch_embed, image_bytes(ref), h)

    # Per-image resolutions are independent: run them concurrently (one
    # fleet round-trip bounds the latency, not one per image).
    embeds = await asyncio.gather(*(one(ref) for ref in mm["images"]))
    allemb = np.concatenate(list(embeds), axis=0).astype(np.float32)
    request["mm"] = dict(
        mm, embeds=allemb.tobytes(), embeds_shape=list(allemb.shape)
    )


class StepKvEvents:
    """The allocator's KV events on their way from the engine thread to
    the loop, where the publisher's bounded buffer lives.

    One raised inside an engine step (``step``: the core's
    ``step_scope``, entered on the engine thread under the step lock) is
    kept, in the order raised, and the step's events cross together when
    it returns: one write to the loop's wake-up socket and one hand-over
    of the GIL a step, where a hop per committed block made dozens while
    the engine thread was between a landing and the next enqueue (PERF.md
    section 6, PR 40). Raised by any other thread, or outside a step (an
    import, a cache clear: they hold the same lock, so never beside a
    step), an event crosses at once as before. ``stored`` and ``removed``
    keep their order; the publisher sees what it saw."""

    def __init__(self, loop, publisher: KvEventPublisher):
        self._loop = loop
        self._pub = publisher
        self._thread: int | None = None   # the engine thread, inside a step
        self._events: list[tuple] = []

    def stored(self, hashes: list[int], parent: int | None) -> None:
        self._send(self._pub.stored_nowait, list(hashes), parent)

    def removed(self, hashes: list[int]) -> None:
        self._send(self._pub.removed_nowait, list(hashes))

    def _send(self, fn, *args) -> None:
        if threading.get_ident() == self._thread:
            self._events.append((fn, args))
        else:
            self._loop.call_soon_threadsafe(fn, *args)

    @contextlib.contextmanager
    def step(self):
        self._thread = threading.get_ident()
        try:
            yield
        finally:
            self._thread = None
            if self._events:
                events, self._events = self._events, []
                self._loop.call_soon_threadsafe(self._deliver, events)

    @staticmethod
    def _deliver(events: list[tuple]) -> None:
        for fn, args in events:
            fn(*args)


def _eos_for(tokenizer: str) -> tuple[int, ...]:
    if tokenizer == "byte":
        from dynamo_tpu.llm.tokenizer import ByteTokenizer

        return (ByteTokenizer.EOS,)
    # No blanket except here: load_tokenizer already degrades gracefully
    # (byte-level fallback) when tokenizer files are genuinely absent, so
    # anything it raises is a real failure (mistyped path, corrupt
    # tokenizer.json, transient I/O). Swallowing it would silently serve
    # without EOS for the worker's lifetime — requests would stop only on
    # max_tokens while the preprocessor happily loads the same tokenizer.
    # Fail worker startup fast instead (ADVICE r5).
    from dynamo_tpu.llm.tokenizer import load_tokenizer

    eos = load_tokenizer(tokenizer).eos_token_id
    return (eos,) if eos is not None else ()


def _model_card(model_name: str, tokenizer: str, core) -> ModelDeploymentCard:
    return ModelDeploymentCard(
        name=model_name,
        tokenizer=tokenizer,
        model_type="chat",
        context_length=core.engine.max_model_len,
        kv_block_size=core.engine.block_size,
        runtime_config=ModelRuntimeConfig(
            total_kv_blocks=core.engine.num_kv_blocks,
            max_num_seqs=core.engine.max_num_seqs,
            max_num_batched_tokens=core.engine.prefill_buckets[-1],
        ),
    )


def _pp_prefill_buckets(
    prefill_buckets: tuple[int, ...], pp: int, block_size: int
) -> tuple[int, ...]:
    """Prefill buckets usable under ``--pp``: every bucket must split into
    pp microbatch groups (EngineCore validates). Keeps the divisible
    subset; when none survives, synthesizes one bucket divisible by both
    pp and block_size, near the largest requested."""
    kept = tuple(b for b in prefill_buckets if b % pp == 0)
    if kept:
        return kept
    step = math.lcm(pp, block_size)
    return (step * max(1, prefill_buckets[-1] // step),)


def build_engine(
    preset: str,
    engine_overrides: dict[str, Any] | None = None,
    seed: int = 0,
    eos_token_ids: tuple[int, ...] = (),
    on_stored=None,
    on_removed=None,
    on_tier_stored=None,
    on_tier_removed=None,
    tp: int = 1,
    dp: int = 1,
    sp: int = 1,
    pp: int = 1,
    quant: str | None = None,
    moe_dispatch: str | None = None,
    model_path: str | None = None,
    core_cls=None,
    core_kwargs: dict[str, Any] | None = None,
):
    """Construct (EngineCore, TpuEngine) for a model preset.

    ``core_cls`` substitutes the engine-core class (multihost LeaderCore
    journals intake for follower replay).

    ``quant='int8'`` serves int8 weight-only-quantized params (the
    capacity mode that fits llama3-8b on one 16 GB chip).

    ``tp``/``dp`` > 1 build a device mesh and shard the engine in-process
    (TP over ICI; the reference's tp plumbing is vllm/args.py:239-258 —
    here the partitioning is first-party, SURVEY.md §2.6).

    ``sp`` > 1 builds a sequence-parallel mesh instead: long prompts (at
    or past ``ring_prefill_threshold``) prefill as one dense
    ring-attention pass over the sp axis (long-context serving — the
    reference has no equivalent, SURVEY.md §5). Mutually exclusive with
    tp/dp for now.

    Imported lazily so the CLI can print --help without touching jax.
    """
    startclock.mark("weights")
    from dynamo_tpu.engine import (
        EngineConfig,
        EngineCore,
        PRESETS,
        TpuEngine,
        tiny_engine,
    )

    loaded_params = None
    if model_path is not None:
        # Serve real weights from an HF checkpoint directory (llama or
        # qwen2 family — engine/loader.py; the reference resolves HF
        # repos the same way, lib/llm/src/local_model.rs:429). The fused
        # layout is built for the serving tp; pp keeps tp=1 layouts.
        # int8 quantizes host-side inside the loader so the device never
        # holds the bf16 footprint (the 8B-on-one-16GB-chip mode).
        from dynamo_tpu.engine.loader import load_hf_llama

        model_cfg, loaded_params = load_hf_llama(model_path, tp=tp, quant=quant)
        quant = None  # handled by the loader; skip the random-init path
    else:
        model_cfg = PRESETS[preset]()
    if moe_dispatch is not None:
        if not model_cfg.is_moe:
            raise ValueError(f"--moe-dispatch set but preset {preset!r} is dense")
        model_cfg = dataclasses.replace(model_cfg, moe_dispatch=moe_dispatch)
    overrides = dict(engine_overrides or {})
    if preset in ("tiny", "tiny-moe", "tiny-loop", "tiny-axk1") and model_path is None:
        engine_cfg = tiny_engine(**overrides)
    else:
        # Checkpoint serving uses the full-size engine defaults (the
        # --preset default of "tiny" selects a MODEL, which --model-path
        # replaces; it must not also shrink the engine limits).
        engine_cfg = EngineConfig(**overrides) if overrides else EngineConfig()
    mesh = None
    sp_mesh = None
    pp_mesh = None
    if pp > 1:
        if tp * dp > 1 or sp > 1:
            raise ValueError("--pp is mutually exclusive with --tp/--dp/--sp for now")
        from dynamo_tpu.parallel.pipeline import make_pp_mesh

        if model_cfg.ut_steps > 1:
            raise ValueError(
                f"--pp {pp} with a looped model (ut_steps="
                f"{model_cfg.ut_steps}): the pipeline stages the layer "
                "axis once through, and a looped stack would have to go "
                "round the stages ut_steps times; serve it with --tp or "
                "on one chip"
            )
        pp_mesh = make_pp_mesh(pp)
        # Fail fast with CLI-pointed errors: these used to surface as a
        # late EngineCore construction failure deep inside shard setup.
        if model_cfg.num_layers % pp:
            raise ValueError(
                f"--pp {pp} must divide the model's num_layers="
                f"{model_cfg.num_layers} (layers stage evenly over the pp "
                "mesh); pick a pp that divides the layer count"
            )
        if model_cfg.vocab_size % pp:
            raise ValueError(
                f"--pp {pp} must divide the model's vocab_size="
                f"{model_cfg.vocab_size} (the lm head splits over stages)"
            )
        # Prefill buckets and decode widths must split into pp microbatch
        # groups (EngineCore validates; pre-trim BOTH here the same way
        # dp trims decode widths below — prefill buckets used to slip
        # through and die at EngineCore construction).
        pbuckets = _pp_prefill_buckets(
            engine_cfg.prefill_buckets, pp, engine_cfg.block_size
        )
        if pbuckets != engine_cfg.prefill_buckets:
            engine_cfg = dataclasses.replace(engine_cfg, prefill_buckets=pbuckets)
        buckets = tuple(b for b in engine_cfg.decode_buckets if b % pp == 0)
        if buckets != engine_cfg.decode_buckets:
            if not buckets:
                buckets = (pp * max(1, engine_cfg.decode_buckets[-1] // pp),)
            engine_cfg = dataclasses.replace(engine_cfg, decode_buckets=buckets)
    if sp > 1:
        if tp * dp > 1:
            raise ValueError("--sp is mutually exclusive with --tp/--dp for now")
        from dynamo_tpu.ops.ring_attention import sequence_parallel_mesh

        sp_mesh = sequence_parallel_mesh(sp)
        if engine_cfg.ring_prefill_threshold <= 0:
            # --sp without an explicit threshold: route every prompt that
            # fills at least half the largest bucket through the ring.
            engine_cfg = dataclasses.replace(
                engine_cfg,
                ring_prefill_threshold=max(
                    engine_cfg.block_size, engine_cfg.prefill_buckets[-1] // 2
                ),
            )
    if tp * dp > 1:
        from dynamo_tpu.parallel.sharding import make_mesh

        mesh = make_mesh(dp=dp, tp=tp)
        # Decode widths must split evenly over dp lanes.
        buckets = tuple(b for b in engine_cfg.decode_buckets if b % dp == 0)
        if buckets != engine_cfg.decode_buckets:
            if not buckets:
                buckets = (dp * max(1, engine_cfg.decode_buckets[-1] // dp),)
            engine_cfg = dataclasses.replace(engine_cfg, decode_buckets=buckets)
    params = loaded_params
    if quant and model_cfg.layer_groups:
        from dynamo_tpu.engine.config import UnsupportedModelOption

        raise UnsupportedModelOption(
            "quant", model_cfg.name,
            "conv, linear-attention and mamba operators, layers of more than one kind "
            "and experts are served unquantised (no int8 init for them)",
        )
    if quant == "int8":
        import jax

        from dynamo_tpu.engine.model import init_params_quantized

        # Under a tp/dp mesh the int8 pytree is built with the mesh's
        # fused-column layout and sharded by EngineCore (shard_params
        # understands {w, scale} leaves — the 70B-int8 serving mode,
        # parallel/placement.py). Random init materializes on the default
        # device first; real checkpoints stream through engine/loader.py.
        params = init_params_quantized(
            jax.random.PRNGKey(seed), model_cfg, tp=tp if mesh is not None else 1
        )
    elif quant:
        raise ValueError(f"unknown quantization {quant!r}")
    core = (core_cls or EngineCore)(
        model_cfg,
        engine_cfg,
        params=params,
        seed=seed,
        eos_token_ids=eos_token_ids,
        on_stored=on_stored,
        on_removed=on_removed,
        on_tier_stored=on_tier_stored,
        on_tier_removed=on_tier_removed,
        mesh=mesh,
        sp_mesh=sp_mesh,
        pp_mesh=pp_mesh,
        **(core_kwargs or {}),
    )
    return core, TpuEngine(core)


async def run_jax_worker(
    runtime: DistributedRuntime,
    model_name: str = "tiny",
    preset: str = "tiny",
    namespace: str = "dynamo",
    component: str | None = None,
    engine_overrides: dict[str, Any] | None = None,
    tokenizer: str | None = None,
    seed: int = 0,
    role: str = "aggregated",   # aggregated | prefill | decode
    disagg_config: DisaggConfig | None = None,
    served_event: asyncio.Event | None = None,
    core_out: list | None = None,
    tp: int = 1,
    dp: int = 1,
    sp: int = 1,
    pp: int = 1,
    quant: str | None = None,
    moe_dispatch: str | None = None,
    model_path: str | None = None,
    nnodes: int = 1,
    node_rank: int = 0,
    obs_publish: bool = True,
    obs_interval_s: float = 1.0,
    warm_up: bool = False,
) -> None:
    """``warm_up`` compiles the serving programs before the model is
    registered (engine/warmup.py). The worker CLI always does; in-process
    callers (tests on the CPU) leave it off and compile on first use."""
    if component is None:
        component = "prefill" if role == "prefill" else "backend"
    if tokenizer is None:
        # Unset: HF checkpoints serve with their own tokenizer; presets
        # default to byte-level. An EXPLICIT --tokenizer byte (or any
        # other spec) always wins.
        tokenizer = model_path if model_path is not None else "byte"
    if nnodes > 1:
        # Multi-host lockstep (backends/jax/multihost.py): the caller has
        # already joined the jax.distributed runtime; here the engine is
        # built over the GLOBAL mesh and the host-side schedulers are
        # kept identical via step-record replication.
        if role != "aggregated":
            raise ValueError("multi-host serving supports role=aggregated only")
        if sp > 1:
            raise ValueError(
                "--sp (ring prefill) is not supported under --nnodes yet"
            )
        if pp > 1:
            raise ValueError(
                "--pp (pipeline parallel) is not supported under --nnodes yet"
            )
        if (engine_overrides or {}).get("held_block_ttl_s", 0) != 0:
            raise ValueError("held_block_ttl_s must be 0 under multi-host")
        engine_overrides = dict(engine_overrides or {}, held_block_ttl_s=0)
    # The start-up clock (tracing/startclock.py) is the one source of the
    # start-up's timings, and the compile log's events are booked on it by
    # stage. /health shows both from here on, not once warm-up is over: the
    # minutes before "serving model" are the ones an operator waits through.
    from dynamo_tpu import device
    from dynamo_tpu.runtime.status_server import bind_startup_gauges

    clock = startclock.running()
    clock.mark("runtime_connect")
    compile_log = device.compile_log()
    compile_log.sink = clock.compile_event
    startup: dict[str, Any] = {}
    if runtime.status is not None:
        runtime.status.health_sections.update(
            startup=lambda: {**startup, "clock": clock.snapshot()},
            compile=compile_log.snapshot,
        )
        bind_startup_gauges(runtime.status, clock)
    if nnodes > 1:
        return await _run_multihost(
            runtime, model_name, preset, namespace, component,
            engine_overrides, tokenizer, seed, served_event, core_out,
            tp, dp, quant, moe_dispatch, model_path, nnodes, node_rank, clock,
        )
    worker_id = runtime.primary_lease_id
    kv_pub = KvEventPublisher(runtime.store, namespace, component, worker_id)
    loop = asyncio.get_running_loop()

    # KV events fire from the engine thread (core.step under to_thread)
    # and the offload worker thread (tier demotions); hop them onto the
    # loop where the publisher's bounded buffer lives. Device-tier events
    # come from the allocator callbacks (a step's in one hop when it
    # returns: StepKvEvents), host/disk-tier events from the offload
    # engine — the router's global index composes them back to
    # worker-level residency.
    kv_events = StepKvEvents(loop, kv_pub)
    on_stored, on_removed = kv_events.stored, kv_events.removed

    def on_tier_stored(hashes: list[int], parent: int | None, tier: str) -> None:
        loop.call_soon_threadsafe(
            kv_pub.stored_nowait, list(hashes), parent, tier
        )

    def on_tier_removed(hashes: list[int], tier: str) -> None:
        loop.call_soon_threadsafe(kv_pub.removed_nowait, list(hashes), tier)

    # Off the event loop like the build below: resolving eos for an HF
    # tokenizer reads tokenizer.json, and blocking the loop starves the
    # store lease keepalive.
    eos = await asyncio.to_thread(_eos_for, tokenizer)

    # Build (and compile) off the event loop: on real TPU hardware the
    # first jit takes tens of seconds, and blocking the loop that long
    # starves the store lease keepalive (ttl 10s) — the worker would
    # arrive at registration with its lease already expired.
    def _build():
        # Refuse a fallback device BEFORE minutes of CPU work on a model
        # sized for a chip: JAX falls back to the CPU when libtpu finds
        # no TPU, and only an explicit CPU request makes that a plan.
        clock.mark("backend_init")
        info = device.require_accelerator("jax worker")
        built = build_engine(
            preset,
            engine_overrides,
            seed=seed,
            eos_token_ids=eos,
            on_stored=on_stored,
            on_removed=on_removed,
            on_tier_stored=on_tier_stored,
            on_tier_removed=on_tier_removed,
            tp=tp,
            dp=dp,
            sp=sp,
            pp=pp,
            quant=quant,
            moe_dispatch=moe_dispatch,
            model_path=model_path,
        )
        import jax

        clock.mark("device_settle")
        jax.block_until_ready((built[0].params, built[0].cache))
        clock.mark("inventory")
        startup["build_seconds"] = round(clock.seconds(*startclock.BUILD_STAGES), 2)
        startup["memory_after_init"] = device.memory_stats()
        startup["param_bytes_per_device"] = device.bytes_per_device(
            built[0].params
        )
        startup["cache_bytes_per_device"] = device.bytes_per_device(
            built[0].cache
        )
        return info, built

    device_info, (core, engine) = await asyncio.to_thread(_build)
    core.step_scope = kv_events.step
    log.info(
        "jax worker device: platform=%s device_kind=%r devices=%d "
        "(peak bytes after init %s; bytes per device: params %s, cache %s)",
        device_info["platform"], device_info["kind"], device_info["count"],
        [m["peak_bytes_in_use"] for m in startup["memory_after_init"]],
        startup["param_bytes_per_device"], startup["cache_bytes_per_device"],
    )
    startup["engine_loop"] = "pipelined" if core.pipelined else "synchronous"
    log.info("engine loop: %s", startup["engine_loop"])
    if core.cfg.layer_groups and role != "aggregated":
        # Refused at start-up, by name: the blocks of a hybrid cache, or
        # of one with a window pool, do not leave the device
        # (EngineCore.kv_page_shape).
        from dynamo_tpu.engine.config import UnsupportedModelOption

        raise UnsupportedModelOption(
            "disagg", core.cfg.name,
            f"role={role!r} hands blocks to a peer; "
            + ("one block holds pages of two shapes and [planes, *page] carries one"
               if core.cfg.hybrid else
               "the linear or mamba layers' state lies in a slab a lane and no block holds it"
               if core.cfg.has_slab else
               "the window layers' pages lie in a pool of their own and do not "
               "leave the device"),
        )
    startup["attention"] = core.cfg.attention
    startup["kv_bytes_per_token"] = core.kv_bytes_per_token
    startup["cache_layers"] = core.cfg.cache_layer_counts
    startup.update(core.cache_by_kind())
    startup["state_bytes_per_block"] = core.cfg.state_bytes_per_block()
    startup["prefix_caching"] = bool(core.engine.enable_prefix_caching)
    if core.cfg.has_slab:
        startup["state_bytes_per_sequence"] = core.cfg.state_bytes_per_sequence()
        startup["state_slots"] = core.engine.max_num_seqs
        log.info(
            "%d %s layers: %d B of state a sequence in a slab of %d lane slots "
            "(and a garbage slot); prefix caching off",
            core.cfg.cache_layers(core.cfg.slab_kind), core.cfg.slab_kind,
            startup["state_bytes_per_sequence"], startup["state_slots"])
    if core.cfg.windowed:
        startup["window_blocks"] = core.engine.num_window_blocks
        startup["sliding_window"] = core.cfg.sliding_window
        startup["block_size"] = core.engine.block_size
        startup["megastep_k"] = core.engine.megastep
        startup["window_table_blocks"] = core.engine.window_table_blocks(
            core.cfg.sliding_window)
        startup["window_bytes_per_sequence"] = core.cfg.window_bytes_per_sequence(
            core.engine.block_size)
        log.info(
            "window %d: %d window layers in a pool of %d blocks (%d B a decoding "
            "sequence), tables of %d columns; prefix caching off",
            core.cfg.sliding_window, core.cfg.cache_layers("window"),
            core.engine.num_window_blocks, startup["window_bytes_per_sequence"],
            startup["window_table_blocks"])
    if core.cfg.block_length:
        startup["block_length"] = core.cfg.block_length
        startup["denoising_steps"] = core.cfg.denoising_steps
        startup["megastep_k"] = core.engine.megastep
        log.info(
            "blocks of %d places, %d denoising passes and a clean one; %d forwards "
            "(%d blocks) a dispatch", core.cfg.block_length, core.cfg.denoising_steps,
            core.engine.megastep,
            core.engine.megastep // (core.cfg.denoising_steps + 1))
    if core.cfg.shared_sparse:
        startup["experts_held"] = list(core.cfg.experts_held_range)
    log.info(
        "attention %s, %d B of cache a token%s", core.cfg.attention,
        core.kv_bytes_per_token,
        (", routed experts held [%d, %d) of %d"
         % (*core.cfg.experts_held_range, core.cfg.num_experts))
        if core.cfg.shared_sparse else "",
    )
    if warm_up:
        from dynamo_tpu.engine.warmup import warm_up as _warm_up

        startup["warmup_phases"] = await asyncio.to_thread(_warm_up, core)
        startup["warmup_seconds"] = round(clock.seconds(*startclock.WARMUP_STAGES), 2)
        # What the waves planner decides by (JSON keys are strings).
        startup["prefill_bucket_ms"] = {
            str(b): ms for b, ms in core.prefill_bucket_ms.items()
        }
    clock.mark("register")
    if runtime.status is not None:
        runtime.status.health_sections.update(
            device=lambda: device_info,
            memory=device.memory_stats,
        )

    if core_out is not None:
        core_out.append(core)

    # Cluster KV pool plumbing (ISSUE 11): the publisher can answer
    # indexer resync requests with the engine's full tier inventory, and
    # a graceful drain retracts the whole published inventory (cleared +
    # flush) so routers stop serving stale hints the moment we leave —
    # not at lease expiry.
    kv_pub.inventory_source = core.kv_inventory
    await kv_pub.start()

    async def _retract_kv_inventory() -> None:
        kv_pub.cleared_nowait()
        await kv_pub.flush(timeout=5.0)

    runtime.on_drain.append(_retract_kv_inventory)

    metrics_pub = WorkerMetricsPublisher(
        runtime.store, namespace, component, worker_id, engine.metrics, interval_s=0.5
    )
    await metrics_pub.start()

    # Scheduler + speculation + prefix-cache gauges on this worker's
    # /metrics (queue depth, budget utilization, acceptance rate, hit
    # rate, ...) — evaluated at scrape time against the live core.
    from dynamo_tpu.runtime.status_server import (
        bind_disagg_gauges,
        bind_engine_counters,
        bind_fair_queue_gauges,
        bind_kv_cache_gauges,
        bind_kv_pool_gauges,
        bind_scheduler_gauges,
        bind_spec_gauges,
        bind_store_gauges,
    )

    # Control-plane connectivity (ISSUE 15): same store_connected /
    # outage / keepalive series as the mocker — /health reports degraded
    # (not unhealthy) while the store is dark and serving continues on
    # cached discovery state.
    bind_store_gauges(runtime.status, runtime.store)
    bind_scheduler_gauges(runtime.status, core.scheduler_stats)
    bind_engine_counters(
        runtime.status, core.step_phase_seconds, core.scheduler_stats,
        core.device_account,
    )
    bind_spec_gauges(runtime.status, core.spec_decode_stats)
    bind_kv_cache_gauges(runtime.status, core.kv_cache_stats)
    bind_fair_queue_gauges(runtime.status, core.fair_queue_stats)

    # kv_pool_* gauges: publisher inventory/drop counters always; the
    # peer-pull counters once the role wiring below creates the client
    # (prefill workers serve blocks but never pull).
    _peer_clients: list = []

    def _kv_pool_stats() -> dict:
        st = kv_pub.stats()
        if _peer_clients:
            st.update(_peer_clients[0].pool_stats())
        return st

    bind_kv_pool_gauges(runtime.status, _kv_pool_stats)

    # Fleet observability (ISSUE 13): periodic metric snapshots over the
    # event plane — the same stats callables the gauges above bind, plus
    # cumulative phase totals and finished-request SLO records. The
    # publish path is a loop task reading host dicts: nothing is added
    # to plan/dispatch, no host sync, no step-lock hold. A graceful
    # drain publishes the `retired` retraction (series leave the fleet
    # view NOW, like the KV-inventory clear above).
    core.flight.name = f"worker-{worker_id}"
    if obs_publish:
        from dynamo_tpu import tracing as _tracing
        from dynamo_tpu.obs.slo import PhaseScanner
        from dynamo_tpu.obs.snapshot import SnapshotPublisher

        snap_pub = SnapshotPublisher(
            runtime.store, namespace, worker_id,
            role="worker", component=component, interval_s=obs_interval_s,
        )
        snap_pub.collectors = {
            "scheduler": core.scheduler_stats,
            "spec": core.spec_decode_stats,
            "kv_cache": core.kv_cache_stats,
            "kv_pool": _kv_pool_stats,
        }
        snap_pub.tenant_source = core.fair_queue_stats
        _obs_collector = _tracing.get_collector()
        snap_pub.phase_source = _obs_collector.phase_totals
        snap_pub.request_source = PhaseScanner(_obs_collector).scan
        await snap_pub.start()

        async def _retire_snapshot() -> None:
            await snap_pub.retire(timeout=5.0)

        runtime.on_drain.append(_retire_snapshot)

    # Multimodal: encoder-fleet clients (idle watches when no encoder
    # component is deployed; _resolve_mm falls back to local encode).
    encode_client = await (
        runtime.namespace(namespace).component("encoder").endpoint("encode").client()
    )
    embed_fetch_client = await (
        runtime.namespace(namespace).component("encoder").endpoint("embed_fetch").client()
    )

    endpoint = runtime.namespace(namespace).component(component).endpoint("generate")

    if role == "prefill":
        # Remote-prefill server: tag descriptors with our identity so the
        # decode side can pull directly, and serve the block-transfer
        # endpoint (the NIXL-equivalent data path).
        async def handler(request: Any, context: Context) -> AsyncIterator[Any]:
            async for out in engine.generate(request, context):
                if out.get("kv_transfer_params"):
                    out["kv_transfer_params"]["worker_id"] = worker_id
                yield out

        # Streaming handoff (ISSUE 17): advertise committed chunks on the
        # cursor plane as they land, so decode pullers overlap transfer
        # with this worker's remaining prefill compute.
        cursor_pub = ChunkCursorPublisher(runtime.store, namespace, worker_id)
        await cursor_pub.start()
        core.on_chunk_commit = cursor_pub.engine_callback(
            asyncio.get_running_loop()
        )

        async def kv_transfer_handler(request: Any, context: Context) -> AsyncIterator[Any]:
            # v2 streamed transfer: descriptors first (cheap), then page
            # data in chunks — the engine keeps prefilling while pages
            # stage out (reference nixl_connect descriptor flow,
            # disagg_serving.md:88-96).
            rid = request[wire.KV_REQUEST_ID]
            # 32-block chunks balance device-invocation count (each chunk
            # is one gather at a fixed dispatch cost) against streaming
            # overlap with the consumer's imports.
            chunk = int(request.get(wire.KV_CHUNK_BLOCKS, 32))
            # Windowed request (streaming handoff): serve only the asked
            # committed-block window, and keep the hold unless this is
            # the FINAL window — the puller streams windows while the
            # prefill is still running, then releases with the tail.
            windowed = wire.KV_WINDOW_START in request
            ws = int(request.get(wire.KV_WINDOW_START, 0))
            wc = request.get(wire.KV_WINDOW_COUNT)
            wc = int(wc) if wc is not None else None
            release = (not windowed) or bool(request.get(wire.KV_WINDOW_FINAL))
            try:
                descs = core.export_descriptors(rid, start=ws, count=wc)
            except KeyError:
                yield {wire.KV_ERROR: f"no held blocks for {rid}"}
                return
            yield {wire.KV_VERSION: core.KV_WIRE_VERSION,
                   wire.KV_BLOCKS: descs}
            try:
                for s in range(0, len(descs), chunk):
                    pages = await asyncio.to_thread(
                        core.read_held_pages, rid, ws + s,
                        min(chunk, len(descs) - s),
                    )
                    yield {
                        wire.KV_VERSION: core.KV_WIRE_VERSION,
                        wire.KV_START: s,
                        wire.KV_PAGES: pages,
                    }
            finally:
                if release:
                    core.release_held(rid)

        transfer_ep = (
            runtime.namespace(namespace).component(component).endpoint("kv_transfer")
        )
        await transfer_ep.serve(kv_transfer_handler)
        await endpoint.serve(handler)

        # Work-queue consumer: pop a prefill task only while holding
        # admission capacity, so queue_len reflects work the fleet has
        # not yet absorbed (reference JetStream queue semantics,
        # transports/nats.rs:433-600; dequeue loop in the arch doc's
        # disagg flow, disagg_serving.md:28-66).
        qname = _prefill_queue(namespace)
        sem = asyncio.Semaphore(core.engine.max_num_seqs)
        _inflight: set[asyncio.Task] = set()

        async def _serve_queued(task: dict) -> None:
            try:
                req = task["request"]
                # The queued task carries the decode side's traceparent:
                # spans this worker records (engine prefill phase) stitch
                # into the originating request's trace.
                tp = task.get("traceparent")
                ctx = Context(
                    req.get("request_id") or f"qprefill-{uuid.uuid4().hex[:8]}",
                    headers={"traceparent": tp} if tp else None,
                )
                last: dict | None = None
                async for out in engine.generate(req, ctx):
                    last = out
                if last is None:
                    last = {"error": "prefill produced no output"}
                if last.get("kv_transfer_params"):
                    last["kv_transfer_params"]["worker_id"] = worker_id
                # Short-TTL non-keepalive lease: if the decode side timed
                # out and already kv_del'd (or never reads), the reply key
                # expires instead of living in the store forever.
                lease = await runtime.store.lease_grant(ttl=60.0, keepalive=False)
                await runtime.store.kv_put(
                    task["reply_key"],
                    msgpack.packb(last, use_bin_type=True),
                    lease=lease
                )
            except Exception:
                log.exception("queued prefill failed")
                try:
                    lease = await runtime.store.lease_grant(ttl=60.0, keepalive=False)
                    await runtime.store.kv_put(
                        task["reply_key"],
                        msgpack.packb(
                            {"error": "remote prefill failed"}, use_bin_type=True
                        ),
                        lease=lease,
                    )
                except Exception:  # noqa: BLE001 — store down; caller times out
                    log.warning(
                        "could not publish prefill-failure reply for %r",
                        task.get("reply_key"), exc_info=True,
                    )
            finally:
                sem.release()

        async def _consume_queue() -> None:
            while True:
                await sem.acquire()
                try:
                    payload = await runtime.store.queue_pop(qname, timeout=1.0)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — store closed on shutdown
                    log.debug("prefill queue pop failed; consumer exiting",
                              exc_info=True)
                    sem.release()
                    return
                if payload is None:
                    sem.release()
                    continue
                try:
                    task = msgpack.unpackb(payload, raw=False)
                except (ValueError, msgpack.UnpackException):
                    log.warning("dropping malformed prefill task")
                    sem.release()
                    continue
                # Hold a strong reference: the loop keeps only weak refs
                # to tasks, and a GC'd task would leak its semaphore slot.
                t = asyncio.create_task(_serve_queued(task))
                _inflight.add(t)
                t.add_done_callback(_inflight.discard)

        consumer = asyncio.create_task(_consume_queue())
        log.info("jax prefill worker %d ready (model %r)", worker_id, model_name)
        if served_event is not None:
            served_event.set()
        try:
            await runtime.wait_for_shutdown()
        finally:
            consumer.cancel()
        return

    if role == "decode":
        disagg = DisaggRouter(disagg_config)
        spawn_logged(
            disagg.watch_store(runtime.store, namespace),
            name="disagg-watch-store", logger=log,
        )
        prefill_client = await (
            runtime.namespace(namespace).component("prefill").endpoint("generate").client()
        )
        transfer_client = await (
            runtime.namespace(namespace).component("prefill").endpoint("kv_transfer").client()
        )
        await _serve_kv_fetch(runtime, namespace, component, core)
        fetch_client = await (
            runtime.namespace(namespace).component(component).endpoint("kv_fetch").client()
        )
        peer_kv = PeerKvClient(core, fetch_client)
        _peer_clients.append(peer_kv)

        # Streaming handoff (ISSUE 17): follow prefill chunk cursors and
        # pull committed windows while the remote prefill is still
        # chunking. Gated by DYN_DISAGG_STREAMING; a dark cursor plane
        # (old prefill fleet, store hiccup) degrades to the reply-gated
        # pull via the cursor timeout.
        handoff: StreamingHandoff | None = None
        if knobs.get_bool("DYN_DISAGG_STREAMING"):
            cursor_watch = ChunkCursorWatcher(runtime.store, namespace)
            await cursor_watch.start()
            handoff = StreamingHandoff(peer_kv, cursor_watch, transfer_client)
            bind_disagg_gauges(runtime.status, handoff.stats.as_dict)

        qname = _prefill_queue(namespace)

        async def handler(request: Any, context: Context) -> AsyncIterator[Any]:
            if request.get("embed") or request.get("clear_kv_blocks"):
                # Embeddings and admin clears never disaggregate: run
                # locally (a clear falling into from_wire would KeyError
                # and report -1 for every decode worker).
                async for out in engine.generate(request, context):
                    yield out
                return
            await _resolve_mm(core, encode_client, embed_fetch_client, request)
            pre = PreprocessedRequest.from_wire(request)
            pre.request_id = pre.request_id or context.id
            hint = (pre.kv_transfer_params or {}).get("peer_prefix")
            if hint and hint.get("worker_id") != worker_id:
                await peer_kv.pull_prefix(hint, list(pre.token_ids))
            cached = await asyncio.to_thread(core.cached_prefix_tokens, pre.token_ids)
            uncached = len(pre.token_ids) - cached
            fallback_replayed = 0  # tokens replayed by an in-worker disagg fallback
            depth = 0
            if prefill_client.instance_ids():
                try:
                    depth = await runtime.store.queue_len(qname)
                except Exception:  # noqa: BLE001 — store hiccup: stay local
                    log.debug("queue_len failed; treating prefill queue as "
                              "full (local prefill)", exc_info=True)
                    depth = disagg.config.max_prefill_queue_size + 1
            if (
                prefill_client.instance_ids()
                and disagg.decide(
                    uncached, depth,
                    headers=context.headers, request_id=pre.request_id,
                )
            ):
                # Track what already reached the client: a mid-stream
                # failure must resume by token replay (migration.py
                # semantics), never replay tokens the client has seen.
                emitted: list[int] = []
                try:
                    async for out in _remote_prefill_then_decode(
                        core, engine, pre, context, runtime.store, qname,
                        transfer_client, emitted, tracer=disagg.tracer,
                        handoff=handoff,
                    ):
                        yield out
                    return
                except Exception:
                    log.exception(
                        "remote prefill failed for %s; falling back to local",
                        pre.request_id,
                    )
                if emitted:
                    stop = pre.stop.after_replay(len(emitted))
                    if stop.max_tokens is not None:
                        stop.max_tokens = max(1, stop.max_tokens)
                    fallback_replayed = len(emitted)
                    pre = dataclasses.replace(
                        pre,
                        token_ids=list(pre.token_ids) + emitted,
                        stop=stop,
                        kv_transfer_params=None,
                        # ACCUMULATE: an upstream migration may already
                        # have marked replayed tokens on this request.
                        replayed_tokens=pre.replayed_tokens + len(emitted),
                    )
            async for out in engine.generate(pre.to_wire(), context):
                if fallback_replayed and out.get("finish_reason") is not None:
                    # Usage fix-up for the in-worker replay (invisible to
                    # the frontend's migration operator): the engine
                    # counted the replayed tokens as prompt and only its
                    # own output as completion — charge each token once.
                    if out.get("prompt_tokens") is not None:
                        out["prompt_tokens"] -= fallback_replayed
                    if out.get("completion_tokens") is not None:
                        out["completion_tokens"] += fallback_replayed
                yield out

    else:
        peer_kv = None
        if core.cfg.layer_groups:
            # No kv_fetch endpoint and no peer client (option "peer_kv"):
            # a hybrid or two-pool cache's blocks do not leave the device, so a
            # router's peer hint is left unanswered and the prefix is
            # computed here.
            log.info("peer KV pulls are not carried for %r: kv_fetch not "
                     "served, peer_prefix hints ignored", core.cfg.name)
        else:
            await _serve_kv_fetch(runtime, namespace, component, core)
            fetch_client = await (
                runtime.namespace(namespace).component(component).endpoint("kv_fetch").client()
            )
            peer_kv = PeerKvClient(core, fetch_client)
            _peer_clients.append(peer_kv)

        async def handler(request: Any, context: Context) -> AsyncIterator[Any]:
            await _resolve_mm(core, encode_client, embed_fetch_client, request)
            hint = (request.get("kv_transfer_params") or {}).get("peer_prefix")
            if (
                hint
                and peer_kv is not None
                and hint.get("worker_id") != worker_id
                and request.get("token_ids")
            ):
                await peer_kv.pull_prefix(hint, list(request["token_ids"]))
            async for out in engine.generate(request, context):
                yield out

    await endpoint.serve(handler)
    await register_llm(endpoint, _model_card(model_name, tokenizer, core))
    clock.close()
    log.info(
        "jax %s worker %d serving model %r (preset %s, %d kv blocks) on "
        "%s %r x%d",
        role, worker_id, model_name, preset, core.engine.num_kv_blocks,
        device_info["platform"], device_info["kind"], device_info["count"],
    )
    log.info("%s", clock.table())
    if served_event is not None:
        served_event.set()
    await runtime.wait_for_shutdown()


async def _run_multihost(
    runtime: DistributedRuntime,
    model_name: str,
    preset: str,
    namespace: str,
    component: str,
    engine_overrides: dict[str, Any] | None,
    tokenizer: str,
    seed: int,
    served_event: asyncio.Event | None,
    core_out: list | None,
    tp: int,
    dp: int,
    quant: str | None,
    moe_dispatch: str | None,
    model_path: str | None,
    nnodes: int,
    node_rank: int,
    clock: startclock.StartClock,
) -> None:
    """Leader (rank 0) serves; followers replay its step records so every
    process issues identical programs over the global mesh."""
    from dynamo_tpu.backends.jax.multihost import (
        LeaderCore,
        barrier_name,
        run_follower,
        steps_subject,
    )
    from dynamo_tpu.runtime.barrier import LeaderBarrier

    import msgpack

    eos = await asyncio.to_thread(_eos_for, tokenizer)
    loop = asyncio.get_running_loop()
    subject = steps_subject(namespace, component)
    worker_id = runtime.primary_lease_id

    if node_rank == 0:
        def _publish_failed(task: asyncio.Task) -> None:
            if task.cancelled() or task.exception() is None:
                return
            # A lost record desynchronizes every follower; there is no
            # recovering mid-flight — fail the deployment loudly.
            log.error(
                "step-record publish failed; followers will lose lockstep",
                exc_info=task.exception(),
            )
            runtime.signal_shutdown()

        def publish(record: dict) -> None:
            payload = msgpack.packb(record, use_bin_type=True)

            def _send() -> None:
                t = loop.create_task(runtime.store.publish(subject, payload))
                t.add_done_callback(_publish_failed)

            loop.call_soon_threadsafe(_send)

        # KV events fire only on the leader (the router's view of the
        # fleet is the leader's cache — followers mirror it exactly).
        kv_pub = KvEventPublisher(runtime.store, namespace, component, worker_id)

        def on_stored(hashes: list[int], parent: int | None) -> None:
            loop.call_soon_threadsafe(
                lambda: loop.create_task(kv_pub.stored(hashes, parent))
            )

        def on_removed(hashes: list[int]) -> None:
            loop.call_soon_threadsafe(
                lambda: loop.create_task(kv_pub.removed(hashes))
            )

        core, engine = await asyncio.to_thread(
            build_engine, preset, engine_overrides, seed=seed,
            eos_token_ids=eos, on_stored=on_stored, on_removed=on_removed,
            tp=tp, dp=dp, quant=quant, moe_dispatch=moe_dispatch,
            model_path=model_path,
            core_cls=LeaderCore, core_kwargs={"publish": publish},
        )
        if core_out is not None:
            core_out.append(core)
        clock.mark("register")
        # No step record may fire before every follower subscribes.
        await LeaderBarrier(
            runtime.store, barrier_name(namespace, component), nnodes - 1
        ).sync({"model": model_name}, timeout=120.0)

        metrics_pub = WorkerMetricsPublisher(
            runtime.store, namespace, component, worker_id,
            engine.metrics, interval_s=0.5,
        )
        await metrics_pub.start()
        endpoint = (
            runtime.namespace(namespace).component(component).endpoint("generate")
        )

        async def handler(request: Any, context: Context) -> AsyncIterator[Any]:
            mm = request.get("mm") if isinstance(request, dict) else None
            if mm and mm.get("images") and mm.get("embeds") is None:
                # No encoder resolution is wired on the multihost leader
                # yet; running anyway would silently attend unspliced
                # placeholder tokens and ignore the image. Fail the ONE
                # request loudly instead.
                raise ValueError(
                    "multimodal serving under --nnodes is not wired yet "
                    "(route image requests to a single-host worker)"
                )
            async for out in engine.generate(request, context):
                yield out

        await endpoint.serve(handler)
        await register_llm(endpoint, _model_card(model_name, tokenizer, core))
        clock.close()
        log.info(
            "multihost leader %d serving %r over %d nodes (preset %s)",
            worker_id, model_name, nnodes, preset,
        )
        log.info("%s", clock.table())
        if served_event is not None:
            served_event.set()
        await runtime.wait_for_shutdown()
        return

    core, _engine = await asyncio.to_thread(
        build_engine, preset, engine_overrides, seed=seed,
        eos_token_ids=eos, tp=tp, dp=dp, quant=quant,
        moe_dispatch=moe_dispatch, model_path=model_path,
    )
    if core_out is not None:
        core_out.append(core)
    clock.mark("register")
    ready = asyncio.Event()
    follower = asyncio.create_task(
        run_follower(runtime, core, namespace, component, nnodes, ready_event=ready)
    )
    await ready.wait()
    clock.close()
    if served_event is not None:
        served_event.set()
    shutdown = asyncio.create_task(runtime.wait_for_shutdown())
    try:
        # A follower that stops stepping deadlocks the whole pod's
        # collectives — surface its death instead of idling silently.
        done, _ = await asyncio.wait(
            {follower, shutdown}, return_when=asyncio.FIRST_COMPLETED
        )
        if follower in done and follower.exception() is not None:
            log.error("multihost follower failed", exc_info=follower.exception())
            raise follower.exception()
    finally:
        follower.cancel()
        shutdown.cancel()


async def _remote_prefill_then_decode(
    core, engine, pre: PreprocessedRequest, context: Context,
    store, qname: str, transfer_client, emitted: list[int] | None = None,
    tracer=None, reply_timeout: float = 120.0, handoff=None,
) -> AsyncIterator[Any]:
    """Decode-first disaggregation: queued remote prefill, block pull,
    local continuation by token replay (reference handlers.py:113-151;
    queue flow disagg_serving.md:28-66).

    ``emitted`` (if given) collects every token yielded to the caller so a
    mid-stream failure can resume instead of replaying the stream.

    ``handoff`` (a :class:`StreamingHandoff`) overlaps the KV transfer
    with the remote prefill itself: committed chunk windows stream in
    while the prefill is still running, and a fully streamed handoff
    skips the reply-gated pull below entirely. Any streaming failure —
    at any chunk boundary — falls through to that legacy pull, and
    failing that to the caller's local-recompute replay, bit-identically."""
    from dynamo_tpu.llm.protocols.common import LLMEngineOutput
    from dynamo_tpu.runtime.store.client import StoreClient

    prefill_req = dataclasses.replace(
        pre,
        stop=StopConditions(max_tokens=1, ignore_eos=True),
        kv_transfer_params={"do_remote_decode": True},
    )
    reply_key = f"/dynamo/prefill-reply/{pre.request_id}-{uuid.uuid4().hex[:8]}"
    sub = await store.kv_watch(reply_key, with_initial=False)
    # Start following the chunk cursor BEFORE the queue push: the first
    # committed chunks may land within the reply round-trip.
    stream_task: asyncio.Task | None = None
    if handoff is not None:
        stream_task = asyncio.create_task(handoff.run(pre.request_id))
    first: dict | None = None
    t_handoff = time.time()
    try:
        # msgpack, not json: multimodal requests carry raw embedding
        # bytes which json cannot represent (and the data plane is
        # msgpack everywhere else).
        # The traceparent rides the queue task so the prefill worker's
        # spans (its engine prefill phase) join this request's trace even
        # though the work queue, unlike the dataplane, has no header map.
        await store.queue_push(
            qname,
            msgpack.packb(
                {
                    "request": prefill_req.to_wire(),
                    "reply_key": reply_key,
                    "traceparent": (context.headers or {}).get("traceparent"),
                },
                use_bin_type=True,
            ),
        )
        ev = await sub.get(timeout=reply_timeout)
        event = StoreClient.as_watch_event(ev)
        if event.value is not None:
            first = msgpack.unpackb(event.value, raw=False)
    finally:
        if first is None and stream_task is not None:
            # Reply timeout / push failure: don't leak a streaming task
            # that would keep pulling for an abandoned handoff.
            stream_task.cancel()
        await sub.unsubscribe()
        await store.kv_del(reply_key)
        if tracer is not None:
            tracer.record(
                "prefill_handoff", t_handoff, time.time(),
                headers=context.headers,
                attrs={
                    "request_id": pre.request_id,
                    "prefill_tokens": len(pre.token_ids),
                    "ok": first is not None and "error" not in (first or {}),
                },
            )
    if first is None or "error" in first:
        if stream_task is not None:
            stream_task.cancel()
        if first is None:
            raise ConnectionError("prefill worker returned no output")
        raise ConnectionError(f"remote prefill failed: {first['error']}")
    out1 = LLMEngineOutput.from_wire(first)
    xfer = out1.kv_transfer_params or {}
    prefill_worker = xfer.get("worker_id")
    rid = xfer.get("request_id")

    # Streaming handoff resolution: by reply time most chunks should
    # already be local — wait (bounded) for the in-flight tail. A fully
    # streamed handoff sent the FINAL window (hold released server-side)
    # and skips the legacy pull entirely.
    streamed = False
    if stream_task is not None:
        if stream_task.done():
            streamed = bool(stream_task.result())
        elif rid is None or handoff.watcher.cursor(rid) is None:
            # No cursor ever arrived (old prefill fleet, dark event
            # plane): don't hold TTFT hostage — legacy pull now.
            stream_task.cancel()
        else:
            try:
                streamed = bool(await asyncio.wait_for(
                    stream_task, handoff.peer_kv.total_timeout_s
                ))
            except asyncio.TimeoutError:
                streamed = False  # wait_for cancelled the tail

    if prefill_worker is not None and rid is not None and streamed:
        if tracer is not None:
            tracer.record(
                "kv_stream", t_handoff, time.time(), headers=context.headers,
                attrs={
                    "request_id": pre.request_id,
                    "prefill_worker": prefill_worker,
                    "chunks": handoff.stats.chunks_pulled,
                    "streamed": True,
                },
            )
    if prefill_worker is not None and rid is not None and not streamed:
        descs: list[dict] | None = None
        imported = total = dropped = 0
        t_xfer = time.time()
        if chaos.active():
            # Disagg block pull: a severed pull surfaces as ConnectionError,
            # which the decode handler degrades to local recompute + replay.
            await chaos.inject("kv_transfer.pull", str(prefill_worker))
        bstream = await transfer_client.direct(
            prefill_worker, {wire.KV_REQUEST_ID: rid}
        )
        async for frame in bstream:
            if wire.KV_ERROR in frame:
                log.warning(
                    "kv transfer aborted for %s: %s", rid, frame[wire.KV_ERROR]
                )
                break
            ver = frame.get(wire.KV_VERSION)
            if ver != 2:
                raise ConnectionError(
                    f"unsupported KV transfer wire version {ver!r} "
                    "(mixed-version prefill/decode pair?)"
                )
            if wire.KV_BLOCKS in frame:
                descs = frame[wire.KV_BLOCKS]
                continue
            if descs is None:
                raise ConnectionError("KV transfer data frame before descriptors")
            s = frame[wire.KV_START]
            batch = [
                {**descs[s + j], wire.IMP_KV: kv}
                for j, kv in enumerate(frame[wire.KV_PAGES])
            ]
            total += len(batch)
            # Import chunk-by-chunk, concurrent with the engine's own
            # admission/decode (the step lock is only held per splice).
            res = await asyncio.to_thread(core.import_blocks, batch)
            imported += res.imported
            dropped += res.dropped
        if dropped > 0:
            log.warning(
                "KV transfer for %s: %d/%d blocks dropped (allocator full); "
                "the local prefill will recompute them", rid, dropped, total,
            )
        else:
            log.debug("imported %d/%d transferred blocks for %s", imported, total, rid)
        if tracer is not None:
            tracer.record(
                "kv_transfer", t_xfer, time.time(), headers=context.headers,
                attrs={
                    "request_id": pre.request_id,
                    "prefill_worker": prefill_worker,
                    "blocks": total,
                    "imported": imported,
                    "dropped": dropped,
                },
            )

    token1 = out1.token_ids[0]
    first_chunk = LLMEngineOutput(
        token_ids=[token1], meta=dict(out1.meta, remote_prefill=True)
    )
    # Remote prefill ran with ignore_eos=True: evaluate token1 against the
    # *original* stop conditions before continuing the stream.
    finish = _first_token_finish(core, pre.stop, token1)
    if finish is None and pre.stop.max_tokens is not None and pre.stop.max_tokens <= 1:
        finish = out1.finish_reason or "length"
    if finish is not None:
        first_chunk.finish_reason = finish
        first_chunk.prompt_tokens = len(pre.token_ids)
        first_chunk.completion_tokens = 1
        if emitted is not None:
            emitted.append(token1)
        yield first_chunk.to_wire()
        return
    if emitted is not None:
        emitted.append(token1)
    yield first_chunk.to_wire()

    cont = dataclasses.replace(
        pre,
        token_ids=list(pre.token_ids) + [token1],
        stop=pre.stop.after_replay(1),
        kv_transfer_params=None,
    )
    async for out in engine.generate(cont.to_wire(), context):
        if emitted is not None:
            emitted.extend(LLMEngineOutput.from_wire(out).token_ids)
        yield out


def _first_token_finish(core, stop: StopConditions, token: int) -> str | None:
    """Stop-condition check for a remotely-prefilled first token (the
    prefill ran with ignore_eos and no stop set; see migration.py for the
    same replay-boundary problem). max_tokens is handled by the caller."""
    reason = stop.check_token(token, 1, core.eos_token_ids)
    return None if reason == "length" else reason


def main() -> None:
    ap = argparse.ArgumentParser(description="dynamo-tpu JAX engine worker")
    ap.add_argument("--model-name", default="tiny")
    from dynamo_tpu.engine.config import PRESETS

    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--component", default=None, help="defaults by role")
    ap.add_argument("--tokenizer", default=None,
                    help="'byte' or an HF tokenizer path (default: the "
                         "checkpoint's with --model-path, else byte)")
    ap.add_argument("--num-kv-blocks", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--max-num-seqs", type=int, default=None)
    ap.add_argument("--max-model-len", type=int, default=None)
    ap.add_argument(
        "--scheduling", default=None, choices=["waves", "chunked"],
        help="step scheduler: 'waves' = monolithic prefill waves before "
             "decode (default); 'chunked' = mixed prefill-chunk + decode "
             "steps under a per-step token budget (cuts saturated TTFT "
             "and decode stalls)",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=None,
        help="prompt chunk size for --scheduling chunked (block-aligned; "
             "0/unset = auto from the prefill buckets)",
    )
    ap.add_argument(
        "--max-num-batched-tokens", type=int, default=None,
        help="per-step token budget for mixed prefill+decode steps "
             "(0/unset = the largest prefill bucket)",
    )
    ap.add_argument(
        "--spec-decode", default=None, choices=["off", "ngram"],
        help="speculative decoding: 'ngram' drafts via prompt-lookup and "
             "batch-verifies pending+draft as one ragged row (greedy and "
             "seeded-sampling output stay bit-identical to 'off')",
    )
    ap.add_argument(
        "--spec-k", type=int, default=None,
        help="max draft tokens per verify step (also clamps per-request "
             "dyn.spec_decode k)",
    )
    ap.add_argument(
        "--spec-device-draft", action="store_true", default=None,
        help="draft ON DEVICE between megastep inner iterations: the "
             "history ring lives in the scanned dispatch and each inner "
             "iteration re-drafts from it — draft->verify->accept loops "
             "without leaving the device (needs --megastep-k >= 2; "
             "stream stays bit-identical)",
    )
    ap.add_argument(
        "--async-exec", default=None, choices=["on", "off"],
        help="pin the engine loop: 'on' = one-step-ahead pipelined (plan+"
             "enqueue step N+1 while N executes, device-resident token "
             "feedback, double-buffered host fetch), 'off' = synchronous; "
             "the token stream is bit-identical. Unset (default): the "
             "engine chooses — pipelined, except on an sp mesh or with "
             "host-drafted speculation",
    )
    ap.add_argument(
        "--megastep-k", type=int, default=None,
        help="universal megastep: fuse this many decode iterations into "
             "ONE device dispatch (on-device sampling + per-lane stop "
             "flags; host drains outputs every k steps). Prefill chunks "
             "ride the fused dispatch and continue as decode rows; spec "
             "verify rows resolve accept/reject on device. 1 = off (one "
             "dispatch per token); unset = the preset's (8). Token "
             "stream is bit-identical "
             "for any k; only a stop watch wider than 8 ids forces a "
             "batch back to single-step",
    )
    ap.add_argument(
        "--fair-scheduling", default=None, choices=["on", "off"],
        help="per-tenant deficit-round-robin admission over prompt token "
             "cost (x-tenant-id keys the queues; off = strict FIFO — "
             "single-tenant streams are bit-identical either way)",
    )
    ap.add_argument(
        "--fair-quantum", type=int, default=None,
        help="tokens a tenant earns per DRR rotation visit (0/unset = "
             "the per-step token budget)",
    )
    ap.add_argument(
        "--max-waiting", type=int, default=None,
        help="bounded admission queue: at this many waiting requests new "
             "submits get a typed retryable shed error that migration "
             "replays on another instance. 0/unset = unbounded",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant", default=None, choices=["int8"],
                    help="int8 weight-only quantization")
    ap.add_argument(
        "--kv-dtype", default=None, choices=["bf16", "int8"],
        help="paged KV cache storage dtype: 'int8' stores per-block "
             "quantized pages with f32 scale metadata (~1.94x resident "
             "blocks at a fixed HBM budget, ~0.52x decode KV bytes; "
             "quantized ONCE at block-write time, bit-stable across "
             "host/disk tiers and peer transfers). Default bf16 — the "
             "classic path, byte-for-byte untouched. Align across any "
             "fleet that transfers KV",
    )
    ap.add_argument("--model-path", default=None,
                    help="HF checkpoint directory (llama/qwen2 family); "
                         "overrides --preset and defaults the tokenizer "
                         "to the checkpoint's")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["replicated", "alltoall"],
                    help="EP dispatch mode for MoE presets (alltoall = "
                         "wide-EP token all-to-all)")
    ap.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel degree (shards heads/mlp over the mesh's tp axis)",
    )
    ap.add_argument(
        "--dp", type=int, default=1,
        help="in-engine data-parallel degree (decode batch splits over dp)",
    )
    ap.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel degree: long prompts prefill as one dense "
             "ring-attention pass over an sp-device mesh (exclusive with tp/dp)",
    )
    ap.add_argument(
        "--ring-prefill-threshold", type=int, default=None,
        help="prompts at least this long take the ring-prefill path "
             "(default with --sp: half the largest prefill bucket)",
    )
    ap.add_argument(
        "--pp", type=int, default=1,
        help="pipeline-parallel degree: layers stage over a pp-device mesh "
             "(GPipe prefill waves + wavefront decode chains; exclusive "
             "with tp/dp/sp)",
    )
    ap.add_argument("--obs-publish", default="on", choices=["on", "off"],
                    help="publish periodic metric snapshots on the event "
                         "plane for the fleet aggregator (a loop task "
                         "reading host stats dicts — nothing added to "
                         "the plan/dispatch hot path)")
    ap.add_argument("--obs-interval-s", type=float, default=1.0,
                    help="metric-snapshot publish interval")
    ap.add_argument("--role", default="aggregated", choices=["aggregated", "prefill", "decode"])
    # Multi-host (reference parity: sglang multinode flags dist-init-addr/
    # nnodes/node-rank, multinode-examples.md:10). Rank 0 serves; other
    # ranks follow in lockstep over the global mesh.
    ap.add_argument("--dist-init-addr", default=None,
                    help="jax.distributed coordinator host:port (multi-host)")
    ap.add_argument("--nnodes", type=int, default=1)
    ap.add_argument("--node-rank", type=int, default=0)
    ap.add_argument("--local-cpu-devices", type=int, default=None,
                    help="validation mode: force the CPU platform with N "
                         "virtual devices per process (cluster-free multi-host)")
    ap.add_argument(
        "--max-local-prefill-length", type=int, default=50,
        help="decode role: prefills longer than this go to the prefill fleet",
    )
    args = ap.parse_args()

    overrides = {
        k: v
        for k, v in {
            "num_kv_blocks": args.num_kv_blocks,
            "block_size": args.block_size,
            "max_num_seqs": args.max_num_seqs,
            "max_model_len": args.max_model_len,
            "ring_prefill_threshold": args.ring_prefill_threshold,
            "scheduling": args.scheduling,
            "prefill_chunk": args.prefill_chunk,
            "max_num_batched_tokens": args.max_num_batched_tokens,
            "spec_decode": args.spec_decode,
            "spec_k": args.spec_k,
            "spec_device_draft": args.spec_device_draft,
            "megastep_k": args.megastep_k,
            "kv_dtype": args.kv_dtype,
            "async_exec": (
                None if args.async_exec is None else args.async_exec == "on"
            ),
            "fair_scheduling": (
                None
                if args.fair_scheduling is None
                else args.fair_scheduling == "on"
            ),
            "fair_quantum": args.fair_quantum,
            "max_waiting": args.max_waiting,
        }.items()
        if v is not None
    }

    if args.nnodes > 1:
        if not args.dist_init_addr:
            ap.error("--nnodes > 1 requires --dist-init-addr")
        from dynamo_tpu.parallel.multihost import init_multihost

        # Must precede every other jax touch (build_engine imports jax
        # lazily, so doing it here is early enough).
        init_multihost(
            args.dist_init_addr, args.nnodes, args.node_rank,
            local_cpu_devices=args.local_cpu_devices,
        )
    elif args.local_cpu_devices:
        from dynamo_tpu.parallel.multihost import force_cpu_devices

        force_cpu_devices(args.local_cpu_devices)

    from dynamo_tpu.device import enable_compile_cache

    cache_dir = enable_compile_cache()

    @dynamo_worker()
    async def entry(runtime: DistributedRuntime) -> None:
        log.info("persistent compile cache: %s", cache_dir)
        await run_jax_worker(
            runtime,
            model_name=args.model_name,
            preset=args.preset,
            namespace=args.namespace,
            component=args.component,
            engine_overrides=overrides,
            tokenizer=args.tokenizer,
            seed=args.seed,
            role=args.role,
            disagg_config=DisaggConfig(
                max_local_prefill_length=args.max_local_prefill_length
            ),
            tp=args.tp,
            dp=args.dp,
            sp=args.sp,
            pp=args.pp,
            quant=args.quant,
            moe_dispatch=args.moe_dispatch,
            model_path=args.model_path,
            nnodes=args.nnodes,
            node_rank=args.node_rank,
            obs_publish=args.obs_publish == "on",
            obs_interval_s=args.obs_interval_s,
            warm_up=True,
        )

    entry()


if __name__ == "__main__":
    main()
