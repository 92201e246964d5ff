"""dynacheck configuration: rule tables pinning the generic analyses to
the dynamo-tpu codebase.

Everything here is data. Engine A's rules (``interproc.py``) and the call
graph builder (``callgraph.py``) are generic; this file tells them which
functions are hot paths, which attributes are protocol state, and which
entry points are audited. The blocking-call and lock vocabulary is shared
with dynalint (``tools.dynalint.config``) so the two tiers can never
disagree about what "blocking" or "guarded" means.
"""

from __future__ import annotations

from tools.dynalint import config as L

# ---------------------------------------------------------------------------
# Rule ids (used in pragmas: `# dynacheck: allow-<rule>(<reason>)`)
# ---------------------------------------------------------------------------

RULE_TRANSITIVE_BLOCKING = "transitive-blocking"
RULE_LOCK_ORDER = "lock-order"
RULE_HOLDS_LOCK_UNVERIFIED = "holds-lock-unverified"
RULE_CORO_LEAK = "coroutine-leak"
RULE_CURSOR = "cursor-discipline"
RULE_REGISTRY_DRIFT = "registry-drift"
RULE_WIRE_CONTRACT = "wire-contract"
RULE_LOOP_AFFINITY = "loop-affinity"
RULE_CONFIG_KNOB = "config-knob"

ALL_RULES = (
    RULE_TRANSITIVE_BLOCKING,
    RULE_LOCK_ORDER,
    RULE_HOLDS_LOCK_UNVERIFIED,
    RULE_CORO_LEAK,
    RULE_CURSOR,
    RULE_REGISTRY_DRIFT,
    RULE_WIRE_CONTRACT,
    RULE_LOOP_AFFINITY,
    RULE_CONFIG_KNOB,
)

# ---------------------------------------------------------------------------
# Shared vocabulary (single source of truth: dynalint's config).
# ---------------------------------------------------------------------------

# Step-loop hot paths: {file suffix -> set of function names}. dynalint
# flags DIRECT host-sync calls inside these; dynacheck flags TRANSITIVE
# reachability (a sync two or more frames down the call graph).
HOT_STEP_FUNCS = L.HOT_STEP_FUNCS

# Device->host sync call vocabulary (np.asarray / fetch_replicated /
# .item() / .block_until_ready()).
HOST_SYNC_FNS = L.HOST_SYNC_FNS
HOST_SYNC_METHODS = L.HOST_SYNC_METHODS
HOST_SYNC_ASARRAY_ROOTS = L.HOST_SYNC_ASARRAY_ROOTS

# Event-loop blockers (time.sleep, subprocess.*, requests.*, ...): a hot
# step function transitively reaching one of these is flagged too — the
# step loop runs on a worker thread, but a plan-path sleep serializes
# scheduling exactly like a host sync does.
BLOCKING_CALLS = set(L.BLOCKING_CALLS)
BLOCKING_ROOTS = set(L.BLOCKING_ROOTS)

# The GUARDED_BY registry dynacheck cross-references for drift (satellite:
# the registry is hand-maintained since PR 1; dynacheck fails on entries
# that no longer exist or attrs mutated nowhere under their declared lock).
GUARDED_BY = L.GUARDED_BY
EXTERNAL = L.EXTERNAL

# ---------------------------------------------------------------------------
# lock-order: lock recognition + identity.
# ---------------------------------------------------------------------------

# Constructor call names whose assignment target becomes a known lock:
# `self.X = threading.Lock()` / module-level `_lock = threading.Lock()`.
LOCK_CONSTRUCTORS = {
    "threading.Lock", "threading.RLock", "threading.Condition",
    "asyncio.Lock", "asyncio.Condition", "asyncio.Semaphore",
    "Lock", "RLock",
}

# Attribute-name fallback: a `with <expr>.<attr>:` whose attr ends with
# one of these suffixes is treated as a lock acquisition even when the
# constructor was not seen (e.g. the receiver is another instance).
LOCK_NAME_SUFFIXES = ("lock",)

# ---------------------------------------------------------------------------
# coroutine-leak: calls that take ownership of a coroutine object. A call
# to a project-local `async def` must be awaited, handed to one of these,
# returned, or bound to a name that is used again — anything else is a
# created-but-never-scheduled coroutine silently dropped on the floor
# (the body never runs; Python logs "never awaited" at gc time at best).
# ---------------------------------------------------------------------------

CORO_SINKS = {
    "create_task", "ensure_future", "gather", "wait", "wait_for",
    "shield", "run", "run_until_complete", "run_coroutine_threadsafe",
    "as_completed", "spawn_logged", "timeout", "staggered_race",
}

# ---------------------------------------------------------------------------
# cursor-discipline: the audited-writer registry.
#
# CURSOR_ATTRS maps protocol-state attribute names to a short description
# of the protocol they belong to. ANY write to one of these attributes
# (assign / augassign / del / mutator-method call, on any receiver) in the
# scanned tree is an error unless the enclosing function is listed in
# AUDITED_CURSOR_WRITERS for its file — the commit/rollback/release entry
# points whose bookkeeping the engine-parity tests pin. The three shipped
# cross-function bugs (block-refcount double-release, preemption prompt
# truncation, disagg partial-block misalignment) were all writes to this
# state from paths outside the audited set.
# ---------------------------------------------------------------------------

CURSOR_ATTRS = {
    # Sequence progress cursors (engine/core.py): num_computed_tokens is
    # the `processed` property — the rollback cursor every late-stop /
    # rejected-draft path relies on.
    "processed": "num_computed_tokens cursor",
    "prefilled": "prefill progress cursor",
    "pinned_hashes": "pinned-hash block pins",
    "committed_blocks": "committed-block watermark",
    # Allocator bookkeeping (engine/block_allocator.py and the mocker's
    # hash-only sibling): refcount conservation is the allocator model's
    # core invariant, so host code must not touch these out of band.
    "refcount": "block refcount",
    "_free": "allocator free list",
    "_by_hash": "allocator hash index",
    "_inactive": "allocator inactive LRU",
    "_partials": "allocator partial-block count",
    # Fair-queue DRR state (engine/fair_queue.py, ISSUE 10): deficit
    # balances and the tenant rotation decide admission order; a write
    # from outside the queue's own methods would silently skew fairness.
    "_deficits": "DRR per-tenant deficit balances",
    "_order": "DRR tenant rotation",
    # Cluster-pool global index (llm/kv_pool/global_index.py, ISSUE 11):
    # the per-worker tier ledger IS the routing truth — an out-of-band
    # write would desynchronize it from the radix tree it feeds.
    "_tiers": "global-index per-worker tier ledger",
    # Snapshot-publisher buffer (obs/snapshot.py, ISSUE 13): bounded +
    # ordered like the KV event buffer; an out-of-band write could
    # reorder or unbound the fleet view's feed.
    "_snapbuf": "bounded snapshot-publisher buffer",
    # Degraded-mode discovery state (ISSUE 15): the quarantine buffer
    # (runtime/component.py) and the deferred-removal map
    # (llm/discovery.py) decide what keeps serving through a store
    # blackout — an out-of-band write could drop a live instance mid-
    # outage or resurrect a dead one after it.
    "_quarantine": "lease-expiry delete quarantine",
    "_deferred": "deferred model-removal map",
}

# {file suffix -> set of audited writer qualnames}. Nested defs are dotted
# (`EngineCore._plan_megastep.commit` is the megastep commit closure).
AUDITED_CURSOR_WRITERS: dict[str, set[str]] = {
    "dynamo_tpu/engine/core.py": {
        # admission (prefix-cache pins + cached-cursor fast-forward)
        "EngineCore._admit",
        # block commit path (shared by every scheduler)
        "EngineCore._commit_completed",
        # prefill-chunk cursor advance (wave + mixed steps)
        "EngineCore._advance_prefill_chunk",
        # ring-prefill synchronous commit
        "EngineCore._run_ring_prefill",
        # rollback entry points
        "EngineCore._preempt",
        "EngineCore._release_blocks",
        # per-step commit closures / helpers
        "EngineCore._plan_prefill_wave.commit",
        "EngineCore._plan_megastep.commit",
        "EngineCore._plan_mixed.commit",
        # Universal megastep (ISSUE 12): the fused mixed/verify commit
        # closure applies the same cursor algebra — accept-length
        # replay, chunk advance, scanned-continuation rollback.
        "EngineCore._plan_fused.commit",
        "EngineCore._apply_verify_row",
        # Block-diffusion megastep (ISSUE 42): the cursor moves a whole
        # block on at its clean pass, and by the kept places at a cut.
        "EngineCore._plan_blocks.commit",
    },
    # The allocator owns its bookkeeping wholesale: every public method is
    # an audited entry point; the rule guards against OTHER files reaching
    # into `allocator._free` / `blk.refcount` directly.
    "dynamo_tpu/engine/block_allocator.py": {
        "DeviceBlockAllocator.__init__",
        "DeviceBlockAllocator._evict_lru",
        "DeviceBlockAllocator.alloc",
        "DeviceBlockAllocator.alloc_many",
        "DeviceBlockAllocator.alloc_for_import",
        "DeviceBlockAllocator.acquire_cached",
        "DeviceBlockAllocator.commit",
        "DeviceBlockAllocator.free_partial",
        "DeviceBlockAllocator.release",
        "DeviceBlockAllocator.register_inactive",
        "DeviceBlockAllocator.clear_cache",
    },
    # The fair queue owns its DRR bookkeeping wholesale (every mutator
    # is an entry point); the rule guards against OTHER files reaching
    # into `waiting._deficits` / `waiting._order` directly.
    "dynamo_tpu/engine/fair_queue.py": {
        "FairQueue.__init__",
        "FairQueue._queue_for",
        "FairQueue.append",
        "FairQueue.appendleft",
        "FairQueue.head",
        "FairQueue.pop",
        "FairQueue._drop_tenant",
        "FairQueue.remove",
        "FairQueue.sweep",
    },
    # The mocker mirrors the scheduler on its virtual clock; its step loop
    # and hash-only KV manager are the same protocol in miniature.
    "dynamo_tpu/llm/mocker/engine.py": {
        "MockTpuEngine._admit",
        "MockTpuEngine._step",
    },
    "dynamo_tpu/llm/mocker/kv_manager.py": {
        "MockKvManager.__init__",
        "MockKvManager._evict_lru",
        "MockKvManager._ensure_headroom",
        "MockKvManager.acquire_cached",
        "MockKvManager.allocate_partial",
        "MockKvManager.commit_block",
        "MockKvManager.release_partial",
        "MockKvManager.release",
        "MockKvManager.clear_unpinned",
        "MockKvManager.clear",
        # Cluster-pool import (ISSUE 11): register_inactive's mocker twin.
        "MockKvManager.import_block",
    },
    # The snapshot publisher owns its bounded buffer (tick task enqueues,
    # one drain task pops — both loop-affine); the rule guards OTHER
    # files reaching into `pub._snapbuf`.
    "dynamo_tpu/obs/snapshot.py": {
        "SnapshotPublisher.publish_nowait",
        "SnapshotPublisher._drain",
    },
    # Degraded-mode discovery (ISSUE 15): the endpoint client owns its
    # quarantine buffer (watch loop + sweep + reconnect reconcile, all
    # loop-affine); the rule guards OTHER files reaching into
    # `client._quarantine`.
    "dynamo_tpu/runtime/component.py": {
        "EndpointClient.__init__",
        "EndpointClient._watch_loop",
        "EndpointClient._remove_instance",
        "EndpointClient._sweep_quarantine",
        "EndpointClient._reconcile",
    },
    # Same ownership shape for the model watcher's deferred-removal map.
    "dynamo_tpu/llm/discovery.py": {
        "ModelWatcher.__init__",
        "ModelWatcher._on_put",
        "ModelWatcher._on_delete",
        "ModelWatcher._sweep_deferred",
    },
    # The global index owns its tier ledger wholesale (single event-task
    # writer); the rule guards OTHER files reaching into `idx._tiers`.
    "dynamo_tpu/llm/kv_pool/global_index.py": {
        "GlobalKvIndex.__init__",
        "GlobalKvIndex._apply_stored",
        "GlobalKvIndex._apply_removed",
        "GlobalKvIndex._retire",
        "GlobalKvIndex.remove_worker",
    },
}

# ---------------------------------------------------------------------------
# wire-contract: the per-plane frame-key schema lives in
# dynamo_tpu/runtime/wire.py (SCHEMAS / CONTEXTS / VALUES); the rule
# parses that file STATICALLY — Engine A never imports product code.
# WIRE_PLANE_FILES registers which scanned files speak which planes;
# production/consumption is accounted per plane across its files.
# ---------------------------------------------------------------------------

WIRE_SCHEMA_FILE = "dynamo_tpu/runtime/wire.py"

# {file suffix -> planes spoken}. A file's wire.* references must belong
# to one of its planes; raw string-literal keys at send sites matching a
# plane key are backslide findings.
WIRE_PLANE_FILES: dict[str, tuple[str, ...]] = {
    "dynamo_tpu/runtime/dataplane.py": ("dataplane",),
    "dynamo_tpu/runtime/store/client.py": ("store", "store.event"),
    "dynamo_tpu/runtime/store/server.py": ("store", "store.event"),
    "dynamo_tpu/runtime/component.py": ("instance", "store.event"),
    "dynamo_tpu/llm/discovery.py": ("store.event",),
    "dynamo_tpu/obs/snapshot.py": ("snapshot",),
    "dynamo_tpu/llm/kv_pool/peer_client.py": ("kvstream", "kvimport"),
    "dynamo_tpu/backends/jax/main.py": ("kvstream", "kvimport"),
    "dynamo_tpu/backends/mocker/main.py": ("kvstream",),
    "dynamo_tpu/engine/kv_transfer.py": ("kvimport",),
}

# Call names whose dict-literal arguments are frame SEND sites: a raw
# string key there (in a registered plane file, matching a plane key)
# is a backslide to the pre-registry idiom. Directly-yielded dict
# literals in plane files are send sites too (streaming handlers).
WIRE_SEND_FNS = {"pack", "send_frame", "write_frame", "push"}

# Functions producing store-plane keys through KWARG names (the
# ``_request(op, k=..., v=...)`` splice): each keyword name at a call to
# one of these is a produced key for the file's planes.
WIRE_KWARG_PRODUCERS = {"_request"}

# ---------------------------------------------------------------------------
# loop-affinity: state the EXTERNAL/loop-affine convention declares
# single-loop-owned. {file suffix -> {(class, attr): description}}. The
# rule flags any write to one of these reachable (over the call graph)
# from a thread entry point (to_thread / run_in_executor / submit /
# Thread(target=...)).
# ---------------------------------------------------------------------------

LOOP_AFFINE: dict[str, dict[tuple[str, str], str]] = {
    "dynamo_tpu/obs/snapshot.py": {
        ("SnapshotPublisher", "_snapbuf"): "bounded snapshot buffer",
    },
    "dynamo_tpu/llm/kv_router/publisher.py": {
        ("KvEventPublisher", "_buf"): "KV event buffer",
    },
    "dynamo_tpu/runtime/component.py": {
        ("EndpointClient", "_quarantine"): "lease-expiry quarantine map",
    },
    "dynamo_tpu/llm/discovery.py": {
        ("ModelWatcher", "_deferred"): "deferred model-removal map",
    },
    "dynamo_tpu/llm/kv_pool/global_index.py": {
        ("GlobalKvIndex", "_tiers"): "per-worker tier ledger",
        ("GlobalKvIndex", "_last_event_id"): "per-worker event cursor",
        ("GlobalKvIndex", "_fwd_id"): "forwarded-event id counter",
    },
}

# Thread entry vocabulary (callgraph records the spawned callable at
# these sites): asyncio.to_thread(fn), loop.run_in_executor(None, fn),
# executor.submit(fn), threading.Thread(target=fn).
THREAD_SPAWNERS = {"to_thread", "run_in_executor", "submit", "Thread"}

# ---------------------------------------------------------------------------
# config-knob: the central registry lives in dynamo_tpu/knobs.py (KNOBS /
# PREFIXES); the rule parses it statically, collects every env read in
# the tree (os.environ / os.getenv / knobs.* accessors / wrapper
# functions whose body reads the env through a parameter), resolves
# dynamically-built names through module constants and parameter
# defaults, and fails undocumented, unused, duplicate-default, and
# unresolvable reads. `# dynacheck: knob-dynamic(<reason>)` escapes a
# genuinely dynamic name.
# ---------------------------------------------------------------------------

KNOB_REGISTRY_FILE = "dynamo_tpu/knobs.py"
KNOB_DOC_FILE = "README.md"

# Accessor functions on the knobs module (arg 0 is the knob name).
KNOB_ACCESSORS = {
    "raw", "get", "get_str", "get_int", "get_float", "get_bool", "default",
}

# ---------------------------------------------------------------------------
# File selection.
# ---------------------------------------------------------------------------

# Default scan root for the tree run (`python -m tools.dynacheck`).
DEFAULT_PATHS = ("dynamo_tpu",)

# Shared with dynalint (live alias, not a copy): the two tiers must
# scan the same file set, and the dynacheck cache key depends on it.
EXCLUDE_PARTS = L.EXCLUDE_PARTS

# ---------------------------------------------------------------------------
# Engine B exploration bounds. Depths are chosen so the full tree run
# stays well under the CI runtime budget (< 60 s) while every model still
# visits its complete reachable state space (the explorers report when the
# frontier is exhausted before the bound — all three are, at these bounds).
# ---------------------------------------------------------------------------

MODEL_DEPTHS = {
    "allocator": 18,
    "cursor": 12,
    "pp-wavefront": 12,
    "breaker": 18,
    "quarantine": 20,
    "keepalive": 12,
    "planner": 16,
}
