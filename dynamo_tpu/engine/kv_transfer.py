"""What leaves the device and what comes back.

:class:`KvTransfer` is the part of the engine that moves KV pages between
the device cache and everything else: the host and disk tiers (eviction
demotes a block, admission onboards it), the disaggregated hand-off
(descriptors, then pages, then the import on the decode side), peer pulls
and the device-direct copy between two engines of one process. The page
programs (gather, slice, scatter, copy) are functions of this module over
the cache's three layouts.

``EngineCore`` INHERITS it (``class EngineCore(KvTransfer)``), so every
caller calls what it called. A mixin and not a component: the cache array
is rebound by every dispatch (donation) and the step lock is the engine's,
so a component would need a reference back to the engine and draw the same
two-way arrow with more call sites. The arrow that is kept one-way is the
import: this module imports neither ``engine.core`` nor ``Sequence``.

Engine state (what the class reads and writes of the engine that inherits
it, beside what it defines itself; tests/test_engine_layout.py holds the
class to this list):

- ``cfg``, ``engine``, ``mesh``: the resolved configurations and the tp/dp mesh.
- ``cache``: read by every gather, REBOUND by every scatter (donated).
- ``allocator``: pins, imports, prefix matches, the eviction hook.
- ``_step_lock``: taken by every endpoint that another thread calls.
- ``_held``, ``_held_deadline``: a finished prefill's blocks, held for the
  decode side, and when each hold expires.
- ``running``: a hold that is still prefilling is served from here.
- ``transfer_stats``: the import's counters.
- ``_release_blocks``: gives a hold's blocks back.

(End of the list.)
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.block_allocator import OutOfBlocksError
from dynamo_tpu.engine.config import UnsupportedModelOption
from dynamo_tpu.engine.options import _LANE_STATE, _TWO_POOLS, _TWO_SHAPES
from dynamo_tpu.engine.programs import _program
from dynamo_tpu.parallel.multihost import fetch_replicated
from dynamo_tpu.runtime import wire
from dynamo_tpu.tokens import compute_seq_hashes

log = logging.getLogger("dynamo_tpu.engine")


@dataclass
class ImportResult:
    """Per-call KV import outcome (also accumulated in transfer_stats):
    ``dropped`` blocks arrived but found no free device block — the
    decode side will recompute them."""
    imported: int = 0
    skipped: int = 0
    dropped: int = 0

    def __int__(self) -> int:
        return self.imported


# Page movement programs (offload demotion + disagg transfer).
# Slices/gathers are enqueued on the device stream — executions
# are in-order, so they read bytes before any later program can
# rewrite them — and landed host-side off the step path. The
# host/wire layouts stay layer-major ([L, ...] / [n, L, ...]) so
# descriptors, offload tiers, and cross-core transfers are
# byte-compatible across cache layouts (per-layer tuple — plain
# or quantized — vs the pp-stacked array / pp-stacked quantized
# dict): a block sliced from any of them packs to the same
# canonical bytes.
# A layer's array holds ut_steps planes of pages (model.init_cache;
# one plane for every model but a looped one): block b is page
# u*plane + b of plane u, and a block on the host or the wire
# carries all of them, slot u*L + l. The per-layer tuple's
# helpers map over a layer entry's leaves, so a plain array and
# an int8 {"kv", "scale"} pair take the same lines.

def _pages_of(arr, ids, ut):
    """Block ids [n] -> their page in every plane of ``arr``, [ut, n]."""
    plane = arr.shape[0] // ut
    return ids[None, :] + plane * jnp.arange(ut, dtype=ids.dtype)[:, None]


def _gather_pages_fn(cache, ids, ut):
    if isinstance(cache, tuple):
        def slots(*layers):  # L x [pages, ...] -> [n, ut*L, ...]
            rows = jnp.stack([c[_pages_of(c, ids, ut)] for c in layers], axis=1)
            return jnp.moveaxis(rows, 2, 0).reshape(
                ids.shape[0], -1, *layers[0].shape[1:]
            )

        return jax.tree.map(slots, *cache)
    if isinstance(cache, dict):  # pp-stacked int8: same host layout
        return {
            k: jnp.moveaxis(v[:, ids], 1, 0) for k, v in cache.items()
        }  # leaves [n, L, ...]
    return jnp.moveaxis(cache[:, ids], 1, 0)


def _slice_page_fn(cache, bid, ut):  # one block: leaves [ut*L, ps, 2kv, d]
    return jax.tree.map(lambda a: a[0], _gather_pages_fn(cache, bid[None], ut))


def _scatter_pages_fn(cache, ids, pages, ut):
    if isinstance(cache, tuple):
        n, L = ids.shape[0], len(cache)

        def put(l):
            def leaf(c, p):  # p [n, ut*L, ...] -> layer l's [ut, n, ...]
                rows = p.reshape(n, ut, L, *p.shape[2:])[:, :, l]
                return c.at[_pages_of(c, ids, ut)].set(jnp.moveaxis(rows, 1, 0))

            return jax.tree.map(leaf, cache[l], pages)

        return tuple(put(l) for l in range(L))
    if isinstance(cache, dict):
        return {
            k: v.at[:, ids].set(jnp.moveaxis(pages[k], 0, 1))
            for k, v in cache.items()
        }
    return cache.at[:, ids].set(jnp.moveaxis(pages, 0, 1))


def _copy_pages_fn(src, dst, sids, dids, ut):
    if isinstance(dst, tuple):
        return jax.tree.map(
            lambda s, d: d.at[_pages_of(d, dids, ut)].set(s[_pages_of(s, sids, ut)]),
            src, dst,
        )
    if isinstance(dst, dict):
        return {
            k: dst[k].at[:, dids].set(src[k][:, sids]) for k in dst
        }
    return dst.at[:, dids].set(src[:, sids])


class KvTransfer:
    """The engine's methods that move KV pages off and onto the device
    (the module's docstring lists the engine state they reach)."""

    def _init_tiers(self, on_tier_stored, on_tier_removed) -> None:
        """The page programs over this model's planes, the host (G2) and
        disk (G3) pools ``self.engine`` asks for, and the tier callbacks;
        the allocator's eviction hook demotes into the pools."""
        ut = self.cfg.ut_steps
        self._slice_page = jax.jit(_program(_slice_page_fn, ut=ut))
        self._gather_pages = jax.jit(_program(_gather_pages_fn, ut=ut))
        self._scatter_pages = jax.jit(_program(_scatter_pages_fn, ut=ut), donate_argnums=(0,))
        # Device-direct cache->cache block copy (one program: gather from
        # the source cache, scatter into ours — no host staging and no
        # intermediate buffer). Requires matching layouts on both cores.
        self._copy_pages_from = jax.jit(_program(_copy_pages_fn, ut=ut), donate_argnums=(1,))
        self.host_pool = None
        self.disk_pool = None
        self.offload = None
        # Cluster-pool tier events (ISSUE 11): when both tier callbacks
        # are wired, offload-tier transitions publish tier-tagged events
        # (the composing global index folds them back to worker-level
        # residency); without them, behavior is the legacy worker-level
        # contract byte for byte.
        self._tier_aware = on_tier_stored is not None and on_tier_removed is not None
        self._on_tier_stored = on_tier_stored
        self._on_tier_removed = on_tier_removed
        if self.engine.host_kv_blocks > 0:
            from dynamo_tpu.engine.host_cache import HostKvPool
            from dynamo_tpu.engine.offload import DiskKvPool, OffloadEngine

            def _pool_removed(tier: str) -> Callable[[list[int]], None]:
                # Tier-aware: the pool's eviction retracts THAT tier (the
                # index drops the worker only when its last tier empties).
                # Legacy: the worker-level removed, exactly as before.
                if self._tier_aware:
                    return lambda hashes: self._on_tier_removed(hashes, tier)
                return lambda hashes: self.allocator.on_removed(hashes)

            self.host_pool = HostKvPool(
                self.engine.host_kv_blocks, on_removed=_pool_removed("host")
            )
            if self.engine.disk_kv_dir:
                self.disk_pool = DiskKvPool(
                    self.engine.disk_kv_dir,
                    self.engine.disk_kv_blocks,
                    on_removed=_pool_removed("disk"),
                )
            self.offload = OffloadEngine(
                self.host_pool,
                self.disk_pool,
                on_tier_stored=on_tier_stored if self._tier_aware else None,
                on_tier_removed=on_tier_removed if self._tier_aware else None,
            )
            self.allocator.on_evict = self._offload_block

    # -- tiered KV offload (G2 host / G3 disk) ------------------------------

    def _offload_block(self, block_id: int, block_hash: int, parent: int | None) -> None:
        """Device eviction hook: enqueue an async demotion of the block's
        combined KV page ``[L, page_size, 2*n_kv, d]``. The slice program
        is enqueued here (device executions are in-order, so it reads the
        page before any later step reuses the physical block); the
        blocking device->host landing happens on the offload worker
        thread (reference offload.rs runs transfer engines off the
        critical path the same way)."""
        page = self._slice_page(self.cache, jnp.int32(block_id))
        self.offload.submit(block_hash, parent, page)

    @property
    def kv_wire_dtype(self) -> str:
        """The dtype name KV pages carry on every tier and wire: "int8"
        for quantized caches (packed pages — engine/kv_quant.py), else
        the model dtype's numpy name."""
        if self.engine.kv_quantized:
            return "int8"
        return np.dtype(self.cfg.jax_dtype).name

    def _refuse_leaving_the_device(self, option: str) -> None:
        """A block of a hybrid cache (K/V pages beside conv state pages)
        or of a two-pool cache (full layers beside window layers) does not
        leave the device: every way out names its option."""
        if self.cfg.hybrid:
            raise UnsupportedModelOption(option, self.cfg.name, _TWO_SHAPES)
        if self.cfg.windowed:
            raise UnsupportedModelOption(option, self.cfg.name, _TWO_POOLS)
        if self.cfg.has_slab:
            raise UnsupportedModelOption(option, self.cfg.name, _LANE_STATE)

    @property
    def kv_page_shape(self) -> tuple[int, ...]:
        """One block as it leaves the device (host and disk tiers, the
        disagg payload, peer pulls): ``[planes, *page]`` with the page of
        ``ModelConfig.kv_page_tail``: ``(block_size, 2 kv, d)``, or the
        latent page's ``(rows, lanes)``. A hybrid cache has no such shape
        and refuses."""
        self._refuse_leaving_the_device("disagg")
        return (
            self.cfg.num_cache_layers,
            *self.cfg.kv_page_tail(self.engine.block_size),
        )

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes of cache one token holds over all planes, scales of an
        int8 cache included."""
        from dynamo_tpu.engine.kv_quant import kv_page_bytes

        if self.cfg.latent or self.cfg.layer_groups:
            return (self.cfg.num_cache_layers * self.cfg.kv_unit_values
                    * np.dtype(self.cfg.jax_dtype).itemsize)
        return kv_page_bytes(
            self.cfg.num_cache_layers, 1, self.cfg.num_kv_heads,
            self.cfg.head_dim, self.engine.kv_dtype,
            np.dtype(self.cfg.jax_dtype).itemsize,
        )

    def _page_geometry(self) -> tuple[int, int, int, int]:
        return (
            self.cfg.num_cache_layers,
            self.engine.block_size,
            self.cfg.num_kv_heads,
            self.cfg.head_dim,
        )

    def _stage_page(self, kv: np.ndarray):
        """One host-side page (the canonical tier/wire representation —
        packed uint8 for int8, a plain [L, ps, 2kv, d] array otherwise)
        as the device pytree `_scatter_pages` expects, leading axis [1]."""
        if self.engine.kv_quantized:
            from dynamo_tpu.engine.kv_quant import unpack_kv_page

            q8, sc = unpack_kv_page(kv, *self._page_geometry())
            return {"kv": q8[None], "scale": sc[None]}
        return np.asarray(kv)[None]  # dynalint: sync-ok — host tier page, not a device array

    def _stack_staged(self, pages: list):
        """Stack per-block staged pytrees ([1, L, ...] leaves) into one
        scatter batch ([n, L, ...] leaves)."""
        if self.engine.kv_quantized:
            return {
                "kv": jnp.asarray(np.concatenate([p["kv"] for p in pages])),
                "scale": jnp.asarray(
                    np.concatenate([p["scale"] for p in pages])
                ),
            }
        return jnp.asarray(np.concatenate(pages))

    def _fetch_page_bytes(self, pages_dev, n: int) -> list[bytes]:
        """Land a device gather of ``n`` pages and serialize each block to
        its canonical wire bytes (packed int8+scales for quantized caches
        — BIT-stable across every hop by construction)."""
        if isinstance(pages_dev, dict):
            from dynamo_tpu.engine.kv_quant import pack_kv_page

            kv_h = fetch_replicated(pages_dev["kv"])
            sc_h = fetch_replicated(pages_dev["scale"])
            return [
                pack_kv_page(kv_h[i], sc_h[i]).tobytes() for i in range(n)
            ]
        pages = fetch_replicated(pages_dev)
        return [np.ascontiguousarray(pages[i]).tobytes() for i in range(n)]

    def _onboard_from_host(
        self, hashes: list[int], cached_ids: list[int], ncached: int, cap: int
    ) -> tuple[list[int], int]:
        """Extend a device-cached prefix with offload-tier hits: promote
        each consecutive host/disk block back to HBM and pin it. The
        staged bytes scatter back EXACTLY as stored (int8 pages are
        unpacked, never re-quantized)."""
        while ncached < cap and self.offload.contains(hashes[ncached]):
            h = hashes[ncached]
            got = self.offload.fetch_tiered(h)
            if got is None:
                break  # evicted between contains() and fetch()
            parent_hash, kv, src_tier = got
            try:
                bid = self.allocator.alloc_for_import()
            except OutOfBlocksError:
                self.offload.reinsert(h, parent_hash, kv)  # undo the pop
                break
            self.cache = self._scatter_pages(
                self.cache, jnp.asarray([bid], jnp.int32),
                self._stack_staged([self._stage_page(kv)]),
            )
            # Tier-aware: the promotion publishes stored(device) via the
            # allocator callback, then retracts the source tier — stored
            # first, so the composed index never sees the worker empty.
            # Legacy (emit=False): the block never left the worker, so
            # the router already counts it as stored.
            self.allocator.register_inactive(
                bid, h, parent_hash, emit=self._tier_aware
            )
            if self._tier_aware:
                self._on_tier_removed([h], src_tier)
            cached_ids.extend(self.allocator.acquire_cached([h]))
            ncached += 1
        return cached_ids, ncached

    # dynalint: holds-lock(_step_lock) — called at the top of _step_locked
    def _sweep_expired_holds(self) -> None:
        """Release held prefills whose decode side never came (timeout,
        crash): without this, abandoned holds pin device blocks until the
        allocator starves (advisor r4)."""
        if not self._held_deadline:
            return
        now = time.monotonic()
        for rid in [r for r, d in self._held_deadline.items() if d < now]:
            self._held_deadline.pop(rid, None)
            seq = self._held.pop(rid, None)
            if seq is not None:
                log.warning(
                    "releasing expired held blocks for %s (ttl %.0fs)",
                    rid, self.engine.held_block_ttl_s,
                )
                self._release_blocks(seq)

    # -- disaggregated KV transfer (export on prefill, import on decode) ---
    #
    # v2 protocol (reference NIXL descriptor flow,
    # nixl_connect/__init__.py:501-629, disagg_serving.md:88-96):
    # descriptors first (hash chain + layout, no data, cheap and under
    # the step lock), then page data streamed in chunks — the device
    # gathers are enqueued and landed WITHOUT the step lock, because held
    # blocks are pinned and cannot be rewritten by concurrent steps. The
    # engine keeps decoding while blocks stage out.

    KV_WIRE_VERSION = 2

    def _streaming_seq(self, request_id: str) -> "Sequence | None":
        """The RUNNING hold_blocks sequence for ``request_id``, if any —
        the streaming-handoff source while prefill is still chunking
        (once it finishes, the sequence moves to ``_held``). Resolved by
        scanning ``running`` so release paths need no delisting: cancel,
        preemption, and finish all remove the sequence from ``running``,
        which makes a mid-stream puller see KeyError and fall back to
        local recompute. Callers must hold ``_step_lock``."""
        for seq in self.running:
            if seq.request_id == request_id and seq.hold_blocks:
                return seq
        return None

    def export_descriptors(
        self, request_id: str, start: int = 0, count: int | None = None
    ) -> list[dict]:
        """Phase 1: descriptor snapshot of a held prefill's committed
        blocks. The hold stays until :meth:`release_held` (the caller
        releases after the data phase).

        ``start``/``count`` select a committed-block window for the
        streaming handoff (chunk-pipelined pulls while the prefill is
        still running — the sequence serves from ``running`` before it
        ever reaches ``_held``). Defaults describe the whole committed
        prefix, the legacy pull-after-prefill shape."""
        self._refuse_leaving_the_device("disagg")
        with self._step_lock:
            seq = self._held.get(request_id) or self._streaming_seq(request_id)
            if seq is None:
                raise KeyError(f"no held blocks for request {request_id}")
            self._touch_hold(request_id)
            shape = list(self.kv_page_shape)
            dtype = self.kv_wire_dtype
            # Producer layout version: staged pages are always the FULL
            # combined [L, bs, 2kv, d] page regardless of the producer's
            # mesh (read_held_pages gathers across shards), so a consumer
            # on a different tp relayouts for free at scatter time — its
            # own cache sharding re-splits the page. The reference needs a
            # CUDA transpose kernel for the same P<->D mesh mismatch
            # (disagg_serving.md:96-98); here the host staging plus GSPMD
            # subsume it. block_size mismatches are NOT relayoutable: the
            # chained block hashes are computed over block_size-token
            # groups, so the hash domains are disjoint (import validates).
            layout = {
                "kind": "latent_kv_page" if self.cfg.latent else "combined_kv_page",
                "block_size": self.engine.block_size,
                "tp": int(self.mesh.shape["tp"]) if self.mesh is not None else 1,
                # int8 pages travel as the canonical packed buffer: int8
                # kv bytes then f32 per-slot-per-head scales
                # (engine/kv_quant.py). Mixed-dtype consumers fail fast
                # at import — re-quantizing would break the
                # quantize-once bit-stability invariant.
                "kv_dtype": self.engine.kv_dtype,
            }
            if self.engine.kv_quantized:
                layout["scale_dtype"] = "float32"
                layout["scale_shape"] = shape[:-1]
            lo = max(0, start)
            hi = seq.committed_blocks
            if count is not None:
                hi = min(hi, lo + max(0, count))
            descs: list[dict] = []
            parent: int | None = (
                seq.pinned_hashes[lo - 1] if lo > 0 else None
            )
            for i in range(lo, hi):
                # pinned_hashes tracks every committed block in order —
                # including generated-token blocks past the prompt, which
                # prompt_hashes would miss (IndexError at large max_tokens).
                h = seq.pinned_hashes[i]
                descs.append(
                    {
                        wire.IMP_HASH: h, wire.IMP_PARENT: parent,
                        wire.IMP_SHAPE: shape, wire.IMP_DTYPE: dtype,
                        wire.IMP_LAYOUT: layout,
                    }
                )
                parent = h
            return descs

    def read_held_pages(self, request_id: str, start: int, count: int) -> list[bytes]:
        """Phase 2: stage a chunk of a held prefill's pages to host as raw
        bytes ([L, block_size, 2*n_kv, d] each). The step lock is held
        only to DISPATCH the gather (concurrent steps donate self.cache,
        so the handle must not be consumed between read and dispatch);
        the blocking device->host landing runs unlocked — held blocks are
        pinned, and device executions are in-order."""
        with self._step_lock:
            seq = self._held.get(request_id) or self._streaming_seq(request_id)
            if seq is None:
                raise KeyError(f"no held blocks for request {request_id}")
            self._touch_hold(request_id)
            # COMMITTED blocks only: export_descriptors describes exactly
            # seq.committed_blocks entries, and the consumer zips data
            # frames against them — shipping the trailing uncommitted
            # partial block (opened by the held request's first generated
            # token) used to misalign the two and fail the whole import.
            ids = seq.block_ids[: seq.committed_blocks][start : start + count]
            if not ids:
                return []
            pages_dev = self._gather_pages(self.cache, jnp.asarray(ids, jnp.int32))
        return self._fetch_page_bytes(pages_dev, len(ids))

    def read_cached_pages(self, hashes: list[int]) -> list[bytes]:
        """Non-destructive read of the longest locally-held prefix of a
        hash chain, for PEER serving (cross-worker offload-tier
        visibility: another worker pulls this worker's cached prefix
        instead of recomputing it — reference KVBM-distributed
        leader/worker, block_manager/distributed/leader.rs:64).

        Device-resident blocks are pinned under ONE step-lock
        acquisition and gathered in ONE program (the kv_transfer path's
        batching); offload-tier blocks read from host RAM / disk with no
        device involvement. Stops at the first hash held nowhere."""
        self._refuse_leaving_the_device("peer_kv")
        where: list[tuple[str, int]] = []  # ("dev", block_idx) | ("off", hash)
        dev_hashes: list[int] = []
        pages_dev = None
        with self._step_lock:
            dev_ids: list[int] = []
            for h in hashes:
                if self.allocator.is_cached(h):
                    got = self.allocator.acquire_cached([h])  # pins
                    if got:
                        where.append(("dev", len(dev_ids)))
                        dev_ids.append(got[0])
                        dev_hashes.append(h)
                        continue
                if self.offload is not None and self.offload.contains(h):
                    where.append(("off", h))
                    continue
                break
            if dev_ids:
                # Pad the gather to the requested chunk width so XLA
                # compiles one program per chunk size, not per prefix
                # length (duplicate indices are benign reads).
                padded = dev_ids + [dev_ids[0]] * (len(hashes) - len(dev_ids))
                pages_dev = self._gather_pages(
                    self.cache, jnp.asarray(padded, jnp.int32)
                )
        try:
            dev_bytes = (
                self._fetch_page_bytes(pages_dev, len(dev_hashes))
                if pages_dev is not None
                else None
            )
            out: list[bytes] = []
            for kind, ref in where:
                if kind == "dev":
                    out.append(dev_bytes[ref])
                else:
                    kv = self.offload.peek(ref)
                    if kv is None:
                        break  # evicted between contains() and peek()
                    # Offload tiers store the canonical wire buffer
                    # (packed int8+scales when quantized) — ship verbatim.
                    out.append(np.ascontiguousarray(kv).tobytes())
            return out
        finally:
            # A raise anywhere above must not leave pins behind — leaked
            # refcounts would gradually pin the whole pool.
            if dev_hashes:
                with self._step_lock:
                    self.allocator.release(dev_hashes)

    def cached_prefix_tokens(self, token_ids: list[int]) -> int:
        """Locally cached leading tokens (disagg local-vs-remote decision)."""
        hashes = compute_seq_hashes(token_ids, self.engine.block_size)
        with self._step_lock:
            return self.allocator.match_prefix(hashes) * self.engine.block_size

    def kv_inventory(self) -> list[tuple[str, int, int | None]]:
        """Full (tier, hash, parent) snapshot across device + offload
        tiers — the anti-entropy resync payload the KV event publisher
        re-publishes after a gap (KvEventPublisher.inventory_source)."""
        with self._step_lock:
            out: list[tuple[str, int, int | None]] = [
                ("device", h, parent) for h, parent in self.allocator.snapshot()
            ]
        if self.offload is not None:
            out.extend(self.offload.snapshot())
        return out

    # dynalint: holds-lock(_step_lock) — transfer endpoints lock first
    def _touch_hold(self, request_id: str) -> None:
        """Refresh a hold's expiry — an in-flight transfer must not lose
        its blocks between chunks."""
        if self.engine.held_block_ttl_s > 0 and request_id in self._held_deadline:
            self._held_deadline[request_id] = (
                time.monotonic() + self.engine.held_block_ttl_s
            )

    def release_held(self, request_id: str) -> None:
        with self._step_lock:
            self._held_deadline.pop(request_id, None)
            seq = self._held.pop(request_id, None)
            if seq is not None:
                self._release_blocks(seq)
                return
            # Still running (streaming handoff abandoned early): drop
            # the hold intent so _finish releases the blocks immediately
            # instead of pinning them until the TTL sweep. Clearing
            # hold_blocks also stops _streaming_seq from serving windows.
            seq = self._streaming_seq(request_id)
            if seq is not None:
                seq.hold_blocks = False

    def import_blocks(self, blocks: list[dict]) -> ImportResult:
        """Write transferred KV pages into the local cache as inactive
        cached content; a following admission prefix-matches them. Returns
        blocks actually imported (already-cached hashes are skipped). One
        batched scatter per call — the step lock is held only to splice
        the device write and allocator state, never during host staging
        (the caller already has the bytes in hand).

        Quantized (int8) pages arrive as the canonical packed buffer and
        scatter bit-for-bit — NEVER re-quantized. A dtype mismatch where
        either side is int8 fails fast: silently casting would either
        re-quantize (generational drift) or serve garbage scales. Pure
        float mismatches (bf16 producer, fp32 debug consumer) keep the
        existing host-side cast."""
        import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

        expected = self.kv_page_shape
        local_dtype = np.dtype(self.cfg.jax_dtype)
        staged: list[tuple[int, int | None, Any]] = []
        for blk in blocks:
            shape = tuple(blk[wire.IMP_SHAPE])
            if shape != expected:
                kind = (blk.get(wire.IMP_LAYOUT) or {}).get(
                    "kind", "combined_kv_page"
                )
                if kind not in ("combined_kv_page", "latent_kv_page"):
                    raise ValueError(
                        f"unknown producer KV layout {kind!r}; cannot relayout"
                    )
                mine = "latent_kv_page" if self.cfg.latent else "combined_kv_page"
                if kind == mine == "combined_kv_page" and shape[1] != expected[1]:
                    # Resegmenting is pointless, not just hard: the chained
                    # block hashes are per-block_size, so relayouted pages
                    # could never prefix-match a local request.
                    raise ValueError(
                        f"producer block_size {shape[1]} != local "
                        f"{expected[1]}: hash domains are disjoint, refusing "
                        "import (align kv_block_size across the P/D fleet)"
                    )
                raise ValueError(
                    f"incompatible KV page geometry {shape} vs local "
                    f"{expected} (different model config?)"
                )
            wire_dtype = str(blk[wire.IMP_DTYPE])
            if (wire_dtype == "int8") != self.engine.kv_quantized:
                raise ValueError(
                    f"KV dtype mismatch: producer pages are {wire_dtype!r} "
                    f"but this worker's kv_dtype is "
                    f"{self.engine.kv_dtype!r} — refusing to import "
                    "(re-quantizing would break the quantize-once "
                    "invariant; align --kv-dtype across the fleet)"
                )
            if self.engine.kv_quantized:
                page = self._stage_page(
                    np.frombuffer(blk[wire.IMP_KV], np.uint8)
                )  # validates the packed size against local geometry
            else:
                dtype = np.dtype(wire_dtype)
                page = np.frombuffer(blk[wire.IMP_KV], dtype=dtype).reshape(shape)
                if dtype != local_dtype:
                    # Cross-precision fleet (e.g. bf16 prefill feeding an
                    # fp32 debug decode): cast on host rather than letting
                    # the scatter silently promote the whole cache.
                    page = page.astype(local_dtype)
                page = page[None]
            staged.append((blk[wire.IMP_HASH], blk[wire.IMP_PARENT], page))

        with self._step_lock:
            ids: list[int] = []
            pages: list = []
            pending: list[tuple[int, int, int | None]] = []
            skipped = 0
            for h, parent, page in staged:
                if self.allocator.is_cached(h):
                    skipped += 1
                    continue
                try:
                    bid = self.allocator.alloc_for_import()
                except OutOfBlocksError:
                    break
                ids.append(bid)
                pages.append(page)
                pending.append((bid, h, parent))
            if ids:
                self.cache = self._scatter_pages(
                    self.cache,
                    jnp.asarray(ids, jnp.int32),
                    self._stack_staged(pages),
                )
                for bid, h, parent in pending:
                    self.allocator.register_inactive(bid, h, parent)
            return self._account_transfer(len(staged), len(ids), skipped)

    # dynalint: holds-lock(_step_lock) — every import endpoint locks first
    def _account_transfer(self, total: int, imported: int, skipped: int) -> ImportResult:
        """Update transfer_stats for one import call (caller holds the
        step lock) and return the per-call outcome."""
        dropped = total - imported - skipped
        st = self.transfer_stats
        st["transfers"] += 1
        st["imported_blocks"] += imported
        st["skipped_cached_blocks"] += skipped
        st["dropped_blocks"] += dropped
        if dropped > 0:
            st["partial_transfers"] += 1
            log.warning(
                "partial KV import: %d/%d transferred blocks dropped "
                "(allocator full) — decode will recompute them",
                dropped, total,
            )
        return ImportResult(imported=imported, skipped=skipped, dropped=dropped)

    def import_blocks_direct(self, src: "EngineCore", request_id: str) -> ImportResult:
        """Device-direct KV pull from a co-located source core: ONE
        program gathers the held pages out of the source cache and
        scatters them into ours — no host staging, no intermediate
        buffer. This is the within-slice ICI analogue of the reference's
        NIXL GPU->GPU RDMA (disagg_serving.md:88-96, which likewise never
        stages through host memory); the read_held_pages/import_blocks
        pair stays as the host-staged cross-host DCN path.

        Both step locks are held for the dispatch (each cache handle is
        donated by that core's concurrent steps); a global id()-ordered
        acquisition makes mutual pulls deadlock-free."""
        self._refuse_leaving_the_device("disagg")
        if src is self:
            raise ValueError("cannot direct-import from self")
        if isinstance(src.cache, tuple) != isinstance(self.cache, tuple):
            raise ValueError(
                "direct import needs matching cache layouts (per-layer "
                "tuple vs pp-stacked); use the staged wire path instead"
            )
        if src.engine.kv_dtype != self.engine.kv_dtype:
            raise ValueError(
                f"KV dtype mismatch: source core stores "
                f"{src.engine.kv_dtype!r} pages but this core is "
                f"{self.engine.kv_dtype!r} — refusing direct import "
                "(align --kv-dtype across the fleet)"
            )
        descs = src.export_descriptors(request_id)
        first, second = (src, self) if id(src) < id(self) else (self, src)
        # dynacheck: allow-lock-order(global id()-ordered acquisition — mutual pulls always take the lower-id core's lock first, so the pair can never deadlock)
        with first._step_lock, second._step_lock:
            seq = src._held.get(request_id)
            if seq is None:
                raise KeyError(f"no held blocks for request {request_id}")
            src._touch_hold(request_id)
            all_src_ids = seq.block_ids[: seq.committed_blocks]
            ids: list[int] = []
            src_ids: list[int] = []
            pending: list[tuple[int, int, int | None]] = []
            skipped = 0
            for row, d in enumerate(descs):
                if self.allocator.is_cached(d[wire.IMP_HASH]):
                    skipped += 1
                    continue
                try:
                    bid = self.allocator.alloc_for_import()
                except OutOfBlocksError:
                    break
                ids.append(bid)
                src_ids.append(all_src_ids[row])
                pending.append((bid, d[wire.IMP_HASH], d[wire.IMP_PARENT]))
            if ids:
                self.cache = self._copy_pages_from(
                    src.cache,
                    self.cache,
                    jnp.asarray(src_ids, jnp.int32),
                    jnp.asarray(ids, jnp.int32),
                )
                for bid, h, parent in pending:
                    self.allocator.register_inactive(bid, h, parent)
            return self._account_transfer(len(descs), len(ids), skipped)
