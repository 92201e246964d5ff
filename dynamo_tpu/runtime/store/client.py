"""Async client for the control-plane store (see server.py for the contract).

One TCP connection multiplexes all requests, watches, subscriptions, and
queue ops for a process. Leases are kept alive by a background task at
ttl/3, mirroring the reference's etcd lease keep-alive
(`lib/runtime/src/transports/etcd.rs:54-128`).
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import random
import time
from dataclasses import dataclass
from typing import Any, AsyncIterator

from dynamo_tpu.runtime import chaos, framing, wire

log = logging.getLogger("dynamo_tpu.store.client")

# Reconnect backoff schedule: exponential ceiling 0.2 -> x2 -> cap 2.0.
RECONNECT_BASE_S = 0.2
RECONNECT_FACTOR = 2.0
RECONNECT_CAP_S = 2.0

# Dial deadline for one store connect/redial attempt: an unreachable (as
# opposed to refusing) store must fail the attempt into the backoff loop,
# not hang it.
CONNECT_TIMEOUT_S = 5.0


def reconnect_delay(attempt: int, rng: random.Random | None = None) -> float:
    """Full-jitter reconnect delay for the given 0-based attempt:
    uniform in [0, min(base * factor**attempt, cap)].

    A store restart disconnects EVERY client in the deployment at the
    same instant; a deterministic schedule would have the whole fleet
    redial in synchronized waves exactly when the store is busiest
    recovering (the thundering-herd shape AWS's backoff-and-jitter note
    measured). Full jitter decorrelates the redials while keeping the
    same ceiling."""
    ceiling = min(RECONNECT_BASE_S * RECONNECT_FACTOR ** attempt, RECONNECT_CAP_S)
    return (rng or random).uniform(0.0, ceiling)


@dataclass(frozen=True)
class WatchEvent:
    type: str  # "put" | "delete"
    key: str
    value: bytes
    revision: int
    # Delete provenance: "del" (explicit retraction) | "lease" (expiry /
    # conn-death revoke — the liveness judgment degraded-mode consumers
    # may second-guess against the data plane). "" on puts.
    reason: str = ""


@dataclass(frozen=True)
class Message:
    subject: str
    payload: bytes


class Subscription:
    """Stream of server-push events for one watch/subscription."""

    _CLOSED = object()

    def __init__(self, client: "StoreClient", sub_id: int):
        self._client = client
        self.sub_id = sub_id
        self.queue: asyncio.Queue[Any] = asyncio.Queue()

    async def __aiter__(self) -> AsyncIterator[Any]:
        while True:
            # Push stream; consumers needing a deadline use .get(timeout).
            # dynalint: unbounded-ok — server-push subscription stream
            item = await self.queue.get()
            if item is self._CLOSED:
                return
            yield item

    async def get(self, timeout: float | None = None) -> Any:
        item = await asyncio.wait_for(self.queue.get(), timeout)
        if item is self._CLOSED:
            raise ConnectionError("subscription closed")
        return item

    def close_nowait(self) -> None:
        self.queue.put_nowait(self._CLOSED)

    async def unsubscribe(self) -> None:
        await self._client.unsubscribe(self)


class StoreClient:
    def __init__(self, address: str):
        self.address = address
        host, _, port = address.rpartition(":")
        self._host, self._port = host or "127.0.0.1", int(port)
        self._reader: asyncio.StreamReader | None = None
        self._writer: asyncio.StreamWriter | None = None
        self._ids = itertools.count(1)
        self._pending: dict[int, asyncio.Future[Any]] = {}
        self._subs: dict[int, Subscription] = {}
        self._reader_task: asyncio.Task | None = None
        self._keepalive_tasks: dict[int, asyncio.Task] = {}
        self._send_lock = asyncio.Lock()
        self._closed = False
        # Reconnect state: enough to rebuild the session after a store
        # restart or connection blip (the reference
        # leans on etcd/NATS client reconnection; this store's client
        # owns the same responsibility). Leases re-attach under their old
        # id (worker identity embeds it) and lease-bound KV is replayed.
        self.auto_reconnect = True
        self._sub_meta: dict[int, tuple[str, dict]] = {}   # sub_id -> (op, params)
        self._lease_meta: dict[int, tuple[float, bool]] = {}  # id -> (ttl, keepalive)
        self._leased_kv: dict[str, tuple[bytes, int]] = {}    # key -> (value, lease)
        # One-shot leases, never replayed; id -> local expiry (pruned on
        # each grant so the map stays bounded).
        self._ephemeral_leases: dict[int, float] = {}
        self.on_reconnect: list = []  # async callbacks, fired after replay
        self._reconnect_task: asyncio.Task | None = None
        # Connection-state surface (ISSUE 15): consumers judge degraded
        # mode off `connected`, operators off the exported counters.
        self._disconnected_since: float | None = None
        self.outage_seconds_total = 0.0
        self.keepalive_failures_total = 0
        self.reconnects_total = 0

    # -- lifecycle ---------------------------------------------------------

    async def connect(self) -> "StoreClient":
        if self._writer is not None:
            return self
        if chaos.active():
            await chaos.inject("store.connect", self.address)
        self._reader, self._writer = await asyncio.wait_for(
            asyncio.open_connection(self._host, self._port), CONNECT_TIMEOUT_S
        )
        self._reader_task = asyncio.create_task(self._recv_loop())
        return self

    @classmethod
    async def open(cls, address: str) -> "StoreClient":
        return await cls(address).connect()

    @property
    def connected(self) -> bool:
        """True while a live session to the store exists. False means the
        control plane is dark for this process: consumers should treat
        discovery state as a last-known-good snapshot, not authority."""
        return self._writer is not None and not self._closed

    @property
    def disconnected_since(self) -> float | None:
        """``time.monotonic()`` of the current outage's start, or None."""
        return self._disconnected_since

    def outage_seconds(self) -> float:
        """Cumulative seconds without a store session, current outage
        included (the `store_outage_seconds` gauge)."""
        total = self.outage_seconds_total
        if self._disconnected_since is not None:
            total += time.monotonic() - self._disconnected_since
        return total

    def stats(self) -> dict:
        """Connection-state payload for /metrics + /health export."""
        now = time.monotonic()
        return {
            "connected": self.connected,
            "outage_seconds": self.outage_seconds(),
            "disconnected_for_s": (
                now - self._disconnected_since
                if self._disconnected_since is not None
                else 0.0
            ),
            "keepalive_failures": self.keepalive_failures_total,
            "reconnects": self.reconnects_total,
        }

    async def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._reconnect_task:
            self._reconnect_task.cancel()
        for task in self._keepalive_tasks.values():
            task.cancel()
        if self._reader_task:
            self._reader_task.cancel()
        if self._writer:
            self._writer.close()
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(ConnectionError("store client closed"))
        for sub in self._subs.values():
            sub.close_nowait()

    async def __aenter__(self) -> "StoreClient":
        return await self.connect()

    async def __aexit__(self, *exc) -> None:
        await self.close()

    async def _recv_loop(self) -> None:
        assert self._reader is not None
        try:
            while True:
                # Long-lived multiplexed session: idle is healthy, death
                # surfaces as EOF and enters the reconnect loop; request
                # futures are the bounded consumer surface.
                # dynalint: unbounded-ok — session read loop idles between pushes
                msg = await framing.read_frame(self._reader)
                if chaos.active() and not await chaos.inject(
                    "store.frame", self.address
                ):
                    continue  # frame dropped by the active chaos plan
                if wire.ST_PUSH_SUB in msg:  # server push
                    sub = self._subs.get(msg[wire.ST_PUSH_SUB])
                    if sub is not None:
                        sub.queue.put_nowait(msg[wire.ST_EVENT])
                    continue
                fut = self._pending.pop(msg[wire.ST_ID], None)
                if fut is None or fut.done():
                    continue
                if msg[wire.ST_OK]:
                    fut.set_result(msg[wire.ST_RESULT])
                else:
                    fut.set_exception(StoreError(msg[wire.ST_ERR]))
        except (asyncio.IncompleteReadError, ConnectionError, asyncio.CancelledError):
            pass
        except OSError:
            pass
        finally:
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(ConnectionError("store connection lost"))
            self._pending.clear()
            if self._closed or not self.auto_reconnect:
                for sub in self._subs.values():
                    sub.close_nowait()
            elif self._reconnect_task is None or self._reconnect_task.done():
                # Subscriptions stay open; their queues resume after the
                # session is rebuilt.
                self._writer = None
                if self._disconnected_since is None:
                    self._disconnected_since = time.monotonic()
                self._reconnect_task = asyncio.create_task(self._reconnect_loop())

    async def _reconnect_loop(self) -> None:
        """Rebuild the session after a lost connection: dial with backoff,
        re-attach leases under their old ids, replay lease-bound KV
        registrations, re-establish subscriptions and watches (the old
        Subscription objects keep their queues — consumers just see a
        gap), then fire ``on_reconnect`` callbacks."""
        if self._writer is not None:
            return  # session already live (duplicate schedule)
        attempt = 0
        while not self._closed:
            try:
                if chaos.active():
                    await chaos.inject("store.connect", self.address)
                self._reader, self._writer = await asyncio.wait_for(
                    asyncio.open_connection(self._host, self._port),
                    CONNECT_TIMEOUT_S,
                )
                break
            except (OSError, asyncio.TimeoutError):
                await asyncio.sleep(reconnect_delay(attempt))
                attempt += 1
        if self._closed:
            return
        self._reader_task = asyncio.create_task(self._recv_loop())
        try:
            # Subscriptions first (so watchers see the lease/KV replay
            # below as live events), drained from a pending list that
            # survives a mid-replay disconnect. Old ids are dropped from
            # the maps up front: a re-issued id may collide with a
            # not-yet-replayed old id, and a half-updated map would
            # cross-wire or silently kill subscriptions.
            pending: list = getattr(self, "_replay_pending", [])
            for old_id in list(self._sub_meta):
                sub = self._subs.pop(old_id, None)
                meta = self._sub_meta.pop(old_id)
                if sub is not None:
                    pending.append((sub, meta))
            self._replay_pending = pending
            while pending:
                sub, (op, params) = pending[0]
                r = await self._request(op, **params)
                sub.sub_id = r[wire.ST_SUB]
                self._subs[r[wire.ST_SUB]] = sub
                self._sub_meta[r[wire.ST_SUB]] = (op, params)
                for ev in r.get(wire.ST_INITIAL) or []:
                    sub.queue.put_nowait(ev)
                pending.pop(0)
            # Leases next: replayed KV entries reference them.
            for lease_id, (ttl, keepalive) in list(self._lease_meta.items()):
                old = self._keepalive_tasks.pop(lease_id, None)
                if old:
                    old.cancel()
                await self._request("lease_grant", ttl=ttl, want=lease_id)
                if keepalive:
                    self._keepalive_tasks[lease_id] = asyncio.create_task(
                        self._keepalive_loop(lease_id, ttl)
                    )
            for key, (value, lease) in list(self._leased_kv.items()):
                try:
                    await self._request("kv_put", k=key, v=value, lease=lease)
                except StoreError:
                    # The lease no longer exists (e.g. an expired ephemeral
                    # lease recorded before its id was pruned): drop the
                    # entry instead of refailing the whole rebuild forever.
                    log.warning("dropping leased key %r (lease %d gone)", key, lease)
                    self._leased_kv.pop(key, None)
            self.reconnects_total += 1
            if self._disconnected_since is not None:
                self.outage_seconds_total += (
                    time.monotonic() - self._disconnected_since
                )
                self._disconnected_since = None
            log.info(
                "store session rebuilt (%d leases, %d registrations, %d subs)",
                len(self._lease_meta), len(self._leased_kv), len(self._sub_meta),
            )
            for cb in self.on_reconnect:
                try:
                    await cb()
                except Exception:  # noqa: BLE001
                    log.exception("on_reconnect callback failed")
        except (ConnectionError, StoreError, OSError):
            # The new connection died mid-replay; try again (the recv
            # loop's finally may have skipped scheduling because this
            # task was still running).
            log.warning("store session replay interrupted; retrying")
            if not self._closed:
                self._writer = None
                self._reconnect_task = asyncio.create_task(self._reconnect_loop())

    async def _request(self, op: str, **params: Any) -> Any:
        if self._writer is None:
            raise ConnectionError("not connected")
        req_id = next(self._ids)
        fut: asyncio.Future[Any] = asyncio.get_running_loop().create_future()
        self._pending[req_id] = fut
        async with self._send_lock:
            await framing.send_frame(
                self._writer,
                {wire.ST_ID: req_id, wire.ST_OP: op, **params},
            )
        return await fut

    # -- KV ----------------------------------------------------------------

    async def kv_put(
        self, key: str, value: bytes, lease: int = 0, create_only: bool = False
    ) -> int:
        r = await self._request("kv_put", k=key, v=value, lease=lease, create_only=create_only)
        if lease and lease not in self._ephemeral_leases:
            # Lease-bound registrations evaporate on a store restart;
            # remember them so the reconnect replay can restore them.
            self._leased_kv[key] = (value, lease)
        else:
            # A permanent overwrite supersedes any earlier lease-bound
            # value; replaying the stale entry would resurrect it.
            self._leased_kv.pop(key, None)
        return r[wire.ST_REV]

    async def kv_get(self, key: str) -> bytes | None:
        r = await self._request("kv_get", k=key)
        return None if r is None else r[wire.ST_VALUE]

    async def kv_del(self, key: str) -> int:
        self._leased_kv.pop(key, None)
        return await self._request("kv_del", k=key)

    async def kv_get_prefix(self, prefix: str) -> dict[str, bytes]:
        r = await self._request("kv_get_prefix", k=prefix)
        return {e[wire.ST_KEY]: e[wire.ST_VALUE] for e in r}

    async def kv_watch(self, prefix: str, with_initial: bool = True) -> Subscription:
        r = await self._request("kv_watch", k=prefix, with_initial=with_initial)
        sub = Subscription(self, r[wire.ST_SUB])
        self._subs[r[wire.ST_SUB]] = sub
        self._sub_meta[r[wire.ST_SUB]] = (
            "kv_watch", {wire.ST_KEY: prefix, wire.ST_WITH_INITIAL: with_initial}
        )
        for ev in r[wire.ST_INITIAL]:
            sub.queue.put_nowait(ev)
        return sub

    @staticmethod
    def as_watch_event(ev: dict) -> WatchEvent:
        return WatchEvent(
            type=ev[wire.EV_TYPE], key=ev[wire.EV_KEY],
            value=ev[wire.EV_VALUE], revision=ev[wire.EV_REV],
            reason=ev.get(
                wire.EV_REASON,
                wire.EV_R_DEL if ev[wire.EV_TYPE] == wire.EV_DELETE else "",
            ),
        )

    # -- leases ------------------------------------------------------------

    async def lease_grant(self, ttl: float = 10.0, keepalive: bool = True) -> int:
        """``keepalive=False`` grants an EPHEMERAL lease: it expires after
        ``ttl`` (deleting its keys) and is deliberately NOT replayed on
        store reconnect — the one-shot reply-key pattern, where replay
        would resurrect a key the consumer already deleted."""
        # conn_bound is the server default, sent explicitly so the wire
        # contract has a producer for the key (dynacheck wire-contract).
        r = await self._request("lease_grant", ttl=ttl, conn_bound=True)
        lease_id = r[wire.ST_LEASE]
        if keepalive:
            self._lease_meta[lease_id] = (ttl, keepalive)
            self._keepalive_tasks[lease_id] = asyncio.create_task(
                self._keepalive_loop(lease_id, ttl)
            )
        else:
            now = time.monotonic()
            self._ephemeral_leases = {
                lid: exp for lid, exp in self._ephemeral_leases.items() if exp > now
            }
            self._ephemeral_leases[lease_id] = now + ttl
        return lease_id

    async def _keepalive_loop(self, lease_id: int, ttl: float) -> None:
        """Keep one lease alive at ttl/3. This loop MUST NOT die on a
        transient failure (the pre-ISSUE-15 bug: the first blip killed it
        silently and the lease expired a TTL later with the process still
        healthy). ConnectionError waits out the outage — the reconnect
        replay re-grants the lease and restarts this task; StoreError
        means the lease vanished server-side while the session stayed up
        (keepalive delayed past TTL, or a restarted store that kept the
        connection), so re-attach it under the same id and re-put its
        keys right here."""
        try:
            while not self._closed and lease_id in self._lease_meta:
                await asyncio.sleep(ttl / 3.0)
                try:
                    await self._request("lease_keepalive", lease=lease_id)
                except ConnectionError:
                    self.keepalive_failures_total += 1
                    # Session down: the reconnect loop owns recovery (it
                    # cancels this task and starts a fresh one after the
                    # lease is re-granted). Keep looping — if the session
                    # comes back under us first, the next beat succeeds.
                except StoreError:
                    self.keepalive_failures_total += 1
                    try:
                        await self._request(
                            "lease_grant", ttl=ttl, want=lease_id
                        )
                        for key, (value, lease) in list(self._leased_kv.items()):
                            if lease == lease_id:
                                await self._request(
                                    "kv_put", k=key, v=value, lease=lease
                                )
                        log.warning(
                            "lease %d re-attached after server-side expiry",
                            lease_id,
                        )
                    except (ConnectionError, StoreError):
                        pass  # retry at the next keepalive beat
        except asyncio.CancelledError:
            pass

    async def lease_revoke(self, lease_id: int) -> bool:
        self._lease_meta.pop(lease_id, None)
        self._leased_kv = {
            k: v for k, v in self._leased_kv.items() if v[1] != lease_id
        }
        task = self._keepalive_tasks.pop(lease_id, None)
        if task:
            task.cancel()
        return await self._request("lease_revoke", lease=lease_id)

    # -- pub/sub -----------------------------------------------------------

    async def subscribe(self, subject: str) -> Subscription:
        r = await self._request("sub", subject=subject)
        sub = Subscription(self, r[wire.ST_SUB])
        self._subs[r[wire.ST_SUB]] = sub
        self._sub_meta[r[wire.ST_SUB]] = ("sub", {wire.ST_SUBJECT: subject})
        return sub

    async def publish(self, subject: str, payload: bytes) -> int:
        return await self._request("pub", subject=subject, p=payload)

    async def unsubscribe(self, sub: Subscription) -> None:
        self._subs.pop(sub.sub_id, None)
        self._sub_meta.pop(sub.sub_id, None)
        sub.close_nowait()
        try:
            await self._request("unsub", sub=sub.sub_id)
        except (ConnectionError, StoreError):
            pass

    @staticmethod
    def as_message(ev: dict) -> Message:
        return Message(subject=ev[wire.EV_SUBJECT], payload=ev[wire.EV_PAYLOAD])

    # -- work queues -------------------------------------------------------

    async def queue_push(self, name: str, payload: bytes) -> int:
        return await self._request("q_push", q=name, p=payload)

    async def queue_pop(self, name: str, timeout: float = 0.0) -> bytes | None:
        return await self._request("q_pop", q=name, timeout=timeout)

    async def queue_len(self, name: str) -> int:
        return await self._request("q_len", q=name)

    # -- object store ------------------------------------------------------

    async def obj_put(self, bucket: str, name: str, payload: bytes) -> None:
        await self._request("obj_put", b=bucket, name=name, p=payload)

    async def obj_get(self, bucket: str, name: str) -> bytes | None:
        return await self._request("obj_get", b=bucket, name=name)

    async def obj_del(self, bucket: str, name: str) -> bool:
        return await self._request("obj_del", b=bucket, name=name)

    async def obj_list(self, bucket: str) -> list[str]:
        return await self._request("obj_list", b=bucket)

    async def ping(self) -> str:
        return await self._request("ping")


class StoreError(RuntimeError):
    pass
