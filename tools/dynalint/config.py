"""dynalint configuration: rule tables and the GUARDED_BY registry.

Everything here is data, not code — the linter (``linter.py``) is generic
and this file pins it to the dynamo-tpu codebase.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# Rule ids (used in pragmas: `# dynalint: allow-<rule>(<reason>)`)
# ---------------------------------------------------------------------------

RULE_FIRE_AND_FORGET = "fire-and-forget-task"
RULE_BLOCKING_IN_ASYNC = "blocking-in-async"
RULE_BROAD_EXCEPT = "broad-except"
RULE_LOCK_DISCIPLINE = "lock-discipline"
RULE_JAX_PITFALL = "jax-pitfall"
RULE_UNCLOSED_SPAN = "unclosed-span"
RULE_HOST_SYNC = "blocking-host-sync"
RULE_UNBOUNDED_AWAIT = "unbounded-await"

ALL_RULES = (
    RULE_FIRE_AND_FORGET,
    RULE_BLOCKING_IN_ASYNC,
    RULE_BROAD_EXCEPT,
    RULE_LOCK_DISCIPLINE,
    RULE_JAX_PITFALL,
    RULE_UNCLOSED_SPAN,
    RULE_HOST_SYNC,
    RULE_UNBOUNDED_AWAIT,
)

# ---------------------------------------------------------------------------
# blocking-in-async: dotted call names that block the event loop.
# Key is the full dotted name as written at the call site (after resolving
# the attribute chain textually — no import tracking; these modules are
# conventionally imported under their own names in this repo).
# ---------------------------------------------------------------------------

BLOCKING_CALLS = {
    "time.sleep": "time.sleep() blocks the event loop; use await asyncio.sleep()",
    "subprocess.run": "subprocess.run() blocks; use asyncio.create_subprocess_exec or asyncio.to_thread",
    "subprocess.call": "subprocess.call() blocks; use asyncio.create_subprocess_exec or asyncio.to_thread",
    "subprocess.check_call": "subprocess.check_call() blocks; use asyncio.to_thread",
    "subprocess.check_output": "subprocess.check_output() blocks; use asyncio.to_thread",
    "os.system": "os.system() blocks; use asyncio.create_subprocess_shell",
    "socket.create_connection": "sync socket connect blocks; use asyncio.open_connection",
    "socket.getaddrinfo": "sync DNS resolution blocks; use loop.getaddrinfo",
    "urllib.request.urlopen": "sync HTTP blocks; use an async client or asyncio.to_thread",
}

# Any call rooted at `requests.` (requests.get/post/Session()...) blocks.
BLOCKING_ROOTS = {
    "requests": "requests.* is synchronous HTTP; use asyncio.to_thread or an async client",
}

# ---------------------------------------------------------------------------
# lock-discipline: the GUARDED_BY registry.
#
# Maps repo-relative file -> {(scope, attr): lock}.
#   scope  — class name owning the attribute, or None for module globals.
#   lock   — name of the lock attribute (`self.<lock>` for class scopes,
#            bare `<lock>` for module scope) that must be held (lexically
#            inside `with`/`async with`, or declared via a
#            `# dynalint: holds-lock(<lock>)` pragma on the enclosing def)
#            when the attribute is MUTATED. Reads are not checked.
#            The sentinel EXTERNAL documents attributes synchronized by a
#            lock the owning object cannot see (checked by convention and
#            review, not by this linter).
#
# `__init__` (and module top level for module globals' initial binding) is
# exempt: nothing else can hold a reference during construction.
# ---------------------------------------------------------------------------

EXTERNAL = "<external>"

# Held-block bookkeeping and the import's counters are touched by the disagg
# transfer endpoints (server thread, engine/kv_transfer.py) and by step()
# (engine thread): EngineCore inherits KvTransfer, one object, one lock.
_HELD_BLOCKS = ("_held", "_held_deadline", "transfer_stats")

GUARDED_BY = {
    "dynamo_tpu/engine/core.py": {
        # add_request() is documented as callable from any thread.
        ("EngineCore", "_req_counter"): "_lock",
        **{("EngineCore", attr): "_step_lock" for attr in _HELD_BLOCKS},
    },
    "dynamo_tpu/engine/kv_transfer.py": {
        ("KvTransfer", attr): "_step_lock" for attr in _HELD_BLOCKS
    },
    "dynamo_tpu/engine/block_allocator.py": {
        # DeviceBlockAllocator is externally synchronized: every caller
        # reaches it through EngineCore under _step_lock (engine/core.py).
        ("DeviceBlockAllocator", "_free"): EXTERNAL,
        ("DeviceBlockAllocator", "_by_hash"): EXTERNAL,
        ("DeviceBlockAllocator", "_inactive"): EXTERNAL,
        ("DeviceBlockAllocator", "_partials"): EXTERNAL,
    },
    "dynamo_tpu/engine/fair_queue.py": {
        # The per-tenant DRR admission queue (ISSUE 10) is externally
        # synchronized like the allocator: EngineCore reaches it only
        # under _step_lock (intake goes through the thread-safe _inbox
        # deque), the mocker only from its single sim loop.
        ("FairQueue", "_queues"): EXTERNAL,
        ("FairQueue", "_deficits"): EXTERNAL,
        ("FairQueue", "_order"): EXTERNAL,
    },
    "dynamo_tpu/llm/kv_router/native_radix.py": {
        # One-shot lazy .so build+load, raced by every router thread.
        (None, "_lib"): "_lock",
        (None, "_load_failed"): "_lock",
    },
    "dynamo_tpu/llm/kv_pool/global_index.py": {
        # Single-writer discipline like the radix tree it wraps: only the
        # indexer's event task mutates the tier ledger; readers share its
        # event loop (kv_router/indexer.py docstring).
        ("GlobalKvIndex", "_tiers"): EXTERNAL,
        ("GlobalKvIndex", "_last_event_id"): EXTERNAL,
        ("GlobalKvIndex", "_fwd_id"): EXTERNAL,
    },
    "dynamo_tpu/llm/kv_router/publisher.py": {
        # Bounded event buffer: every mutation is loop-affine (engine
        # threads hop in via call_soon_threadsafe; one drain task pops).
        ("KvEventPublisher", "_buf"): EXTERNAL,
    },
    "dynamo_tpu/obs/snapshot.py": {
        # Bounded snapshot buffer (ISSUE 13): loop-affine like the KV
        # event publisher — the tick task enqueues, the single drain
        # task pops, both on one event loop.
        ("SnapshotPublisher", "_snapbuf"): EXTERNAL,
    },
    "dynamo_tpu/runtime/component.py": {
        # Degraded-mode quarantine buffer (ISSUE 15): lease-expiry
        # deletes held while the data plane answers. Loop-affine — the
        # watch loop, the quarantine sweep, and the reconnect reconcile
        # all run on the client's one event loop.
        ("EndpointClient", "_quarantine"): EXTERNAL,
    },
    "dynamo_tpu/llm/discovery.py": {
        # Deferred last-instance model removals (ISSUE 15): same
        # loop-affinity as the quarantine buffer (watch loop + sweep).
        ("ModelWatcher", "_deferred"): EXTERNAL,
    },
}

# Mutating method names: `x.<name>(...)` counts as a mutation of `x`.
MUTATOR_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault",
    "pop", "popleft", "popitem", "remove", "discard", "clear",
    "appendleft", "rotate", "sort", "reverse",
}

# ---------------------------------------------------------------------------
# blocking-host-sync: device->host synchronization points flagged inside
# step-loop HOT PATHS (the plan/dispatch side of the async pipelined
# engine, PERF.md r8). A blocking sync there serializes host work with
# device compute — exactly the overhead the one-step-ahead loop removes;
# landings belong on the commit side. Suppress an intentional sync with a
# `# dynalint: sync-ok` pragma on the line (or the line above) — e.g. the
# double-buffered landing point itself, or np.asarray over a host list.
# ---------------------------------------------------------------------------

# Call names (last dotted component) that block on device state.
HOST_SYNC_FNS = {"fetch_replicated", "fetch_replicated_many", "device_get"}

# Method-style syncs: `x.item()` / `x.block_until_ready()` on any receiver.
HOST_SYNC_METHODS = {"item", "block_until_ready"}

# `np.asarray` / `numpy.asarray` (D2H landing when handed a device array).
HOST_SYNC_ASARRAY_ROOTS = {"np", "numpy"}

# Hot-path registry: repo-relative file suffix -> function names whose
# bodies must stay sync-free. Nested defs (commit closures) are NOT hot —
# the commit side is where landings belong.
HOT_STEP_FUNCS: dict[str, set[str]] = {
    "dynamo_tpu/engine/core.py": {
        "_plan_step", "_plan_waves", "_plan_prefill_wave", "_plan_decode",
        "_plan_megastep", "_plan_verify", "_plan_mixed", "_plan_fused",
        "_merge_plans", "_dispatch_ragged", "_dispatch_megastep",
        "_dispatch_fused", "_assemble_ragged", "_grow_or_preempt",
        "_admit", "land",
    },
    # pp fast path (ISSUE 20): the fused pipeline device bodies — a
    # host sync inside either would land INSIDE the traced wavefront
    # scan and serialize every stage hop.
    "dynamo_tpu/engine/programs.py": {
        "_pp_prefill_and_sample", "_pp_decode_chain",
    },
    # pp microbatch planning (ISSUE 20): runs on the plan side of every
    # pipelined step — a device sync here stalls the stage ring exactly
    # like one in _plan_megastep would.
    "dynamo_tpu/parallel/pipeline.py": {"plan_microbatches"},
    # Detector fixtures (linted directly by tests; excluded from the tree).
    "tests/fixtures/dynalint/host_sync_bad.py": {"plan_step", "dispatch"},
    "tests/fixtures/dynalint/host_sync_ok.py": {"plan_step", "dispatch"},
}

# ---------------------------------------------------------------------------
# unbounded-await: network awaits with no deadline. An `await` of one of
# these calls is a point where a wedged peer can park a coroutine forever
# — the failure mode ISSUE 6's stall deadlines exist for. Bounded shapes
# pass: `await asyncio.wait_for(<call>, t)` (the call itself is not
# awaited) and any await lexically inside `async with asyncio.timeout(t)`.
# A deliberately unbounded await (server read loops idling between
# frames, engine-local queues whose producer is in-process) carries a
# `# dynalint: unbounded-ok` pragma on the line or the line above.
# ---------------------------------------------------------------------------

# Last-dotted-component call names that hit the network.
UNBOUNDED_AWAIT_FNS = {"open_connection", "read_frame"}

# `.get()` on a stream-queue receiver: the consumer side of a network-fed
# queue. Matched when the receiver's last dotted component (sans leading
# underscores) is one of these (`self._queue.get()`, `sub.queue.get()`,
# `seq.out.get()`); `msg.get(...)`/`dict.get(...)` receivers don't match.
UNBOUNDED_QUEUE_RECEIVERS = {"queue", "out"}

# Context managers that bound every await inside them.
TIMEOUT_SCOPES = {"asyncio.timeout", "asyncio.timeout_at", "async_timeout.timeout"}

# Wrappers that bound the coroutine they are handed.
TIMEOUT_WRAPPERS = {"asyncio.wait_for", "wait_for"}

# ---------------------------------------------------------------------------
# jax-pitfall: module roots whose use is flagged in __del__/signal handlers.
# ---------------------------------------------------------------------------

JAX_ROOTS = {"jax", "jnp"}

# Call names that register a signal handler (first arg: signum, second: fn).
SIGNAL_REGISTRARS = {"signal.signal", "loop.add_signal_handler"}

# Call/decorator names that enter a traced context.
JIT_WRAPPERS = {"jax.jit", "jit", "jax.pmap", "shard_map", "jax.shard_map"}

# ---------------------------------------------------------------------------
# unclosed-span: receivers whose `.span(...)` result must be closed.
# A dotted receiver matching one of these suffixes (tracer, self._tracer,
# disagg.tracer, ...) — or a direct `get_tracer(...).span(...)` chain — is
# treated as a dynamo_tpu.tracing Tracer. The span must be used as a
# context manager, or be bound to a name that is `.finish()`ed in the same
# scope; anything else leaks an open span (it never reaches the collector,
# and its phase silently vanishes from the waterfall).
# ---------------------------------------------------------------------------

TRACER_RECEIVER_SUFFIXES = ("tracer",)

# ---------------------------------------------------------------------------
# File selection.
# ---------------------------------------------------------------------------

# Directories skipped entirely (relative path fragments).
EXCLUDE_PARTS = {
    "__pycache__",
    ".git",
    # Lint fixtures intentionally contain violations.
    "tests/fixtures/dynalint",
    "tests/fixtures/dynacheck",
}
