"""Control-plane store: KV/lease/watch/pubsub/queue semantics.

Parity with the reference's reliance on etcd+NATS behavior (SURVEY.md §1 L0):
lease expiry removes keys and notifies watchers; prefix watches see initial
state + live events; queues block on pop; pub/sub matches NATS-style.
"""

import asyncio

import pytest

from dynamo_tpu.runtime.store import StoreClient, StoreServer
from dynamo_tpu.runtime.store.server import subject_matches

pytestmark = [pytest.mark.integration, pytest.mark.pre_merge]


async def test_kv_roundtrip():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c:
            await c.kv_put("/a/b", b"1")
            await c.kv_put("/a/c", b"2")
            assert await c.kv_get("/a/b") == b"1"
            assert await c.kv_get("/missing") is None
            assert await c.kv_get_prefix("/a/") == {"/a/b": b"1", "/a/c": b"2"}
            assert await c.kv_del("/a/b") == 1
            assert await c.kv_get("/a/b") is None


async def test_create_only_conflict():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c:
            await c.kv_put("/x", b"1", create_only=True)
            with pytest.raises(Exception, match="exists"):
                await c.kv_put("/x", b"2", create_only=True)


async def test_watch_sees_initial_and_live_events():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c:
            await c.kv_put("/models/a", b"A")
            watch = await c.kv_watch("/models/")
            ev = StoreClient.as_watch_event(await watch.get(timeout=2))
            assert (ev.type, ev.key, ev.value) == ("put", "/models/a", b"A")
            await c.kv_put("/models/b", b"B")
            ev = StoreClient.as_watch_event(await watch.get(timeout=2))
            assert (ev.type, ev.key) == ("put", "/models/b")
            await c.kv_del("/models/a")
            ev = StoreClient.as_watch_event(await watch.get(timeout=2))
            assert (ev.type, ev.key) == ("delete", "/models/a")


async def test_lease_keys_vanish_on_connection_drop():
    async with StoreServer() as server:
        watcher = await StoreClient.open(server.address)
        watch = await watcher.kv_watch("/instances/")
        worker = await StoreClient.open(server.address)
        lease = await worker.lease_grant(ttl=30.0)
        await worker.kv_put("/instances/w1", b"addr", lease=lease)
        ev = StoreClient.as_watch_event(await watch.get(timeout=2))
        assert (ev.type, ev.key) == ("put", "/instances/w1")
        # Simulate worker death: drop the connection without revoking.
        await worker.close()
        ev = StoreClient.as_watch_event(await watch.get(timeout=2))
        assert (ev.type, ev.key) == ("delete", "/instances/w1")
        assert await watcher.kv_get("/instances/w1") is None
        await watcher.close()


async def test_lease_revoke_deletes_keys():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c:
            lease = await c.lease_grant(ttl=30.0)
            await c.kv_put("/i/x", b"1", lease=lease)
            await c.lease_revoke(lease)
            assert await c.kv_get("/i/x") is None


async def test_pubsub_wildcards():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c:
            sub = await c.subscribe("kv_events.>")
            assert await c.publish("kv_events.worker1", b"e1") == 1
            assert await c.publish("other.worker1", b"nope") == 0
            msg = StoreClient.as_message(await sub.get(timeout=2))
            assert (msg.subject, msg.payload) == ("kv_events.worker1", b"e1")


async def test_queue_blocking_pop():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c1:
            async with await StoreClient.open(server.address) as c2:
                pop = asyncio.create_task(c1.queue_pop("prefill", timeout=5.0))
                await asyncio.sleep(0.05)
                await c2.queue_push("prefill", b"req1")
                assert await pop == b"req1"
                assert await c1.queue_pop("empty", timeout=0.0) is None


async def test_object_store():
    async with StoreServer() as server:
        async with await StoreClient.open(server.address) as c:
            await c.obj_put("mdc", "llama", b"card")
            assert await c.obj_get("mdc", "llama") == b"card"
            assert await c.obj_list("mdc") == ["llama"]
            assert await c.obj_del("mdc", "llama")
            assert await c.obj_get("mdc", "llama") is None


def test_subject_matching():
    assert subject_matches("a.b", "a.b")
    assert not subject_matches("a.b", "a.c")
    assert subject_matches("a.*", "a.b")
    assert not subject_matches("a.*", "a.b.c")
    assert subject_matches("a.>", "a.b.c.d")
    assert not subject_matches("a.>", "a")


@pytest.mark.integration
async def test_client_survives_store_restart():
    """Store restart: the client reconnects with backoff, re-attaches its
    lease under the SAME id (worker identity embeds it), replays
    lease-bound registrations, and resumes subscriptions + watches
    (the reference gets this from etcd/NATS client
    libraries; this store's client owns it)."""
    import asyncio

    server = StoreServer()
    await server.start()
    port = server.port
    client = await StoreClient.open(server.address)
    try:
        lease = await client.lease_grant(ttl=5.0)
        await client.kv_put("/reg/instance-1", b"worker-payload", lease=lease)
        sub = await client.subscribe("events")
        watch = await client.kv_watch("/reg/", with_initial=False)

        await server.stop()
        await asyncio.sleep(0.3)
        # Same address, empty state — as after a crash+restart.
        server2 = StoreServer(port=port)
        await server2.start()
        try:
            # Wait for the session to rebuild.
            for _ in range(100):
                try:
                    if await client.kv_get("/reg/instance-1") == b"worker-payload":
                        break
                except ConnectionError:
                    pass
                await asyncio.sleep(0.1)
            # Lease-bound registration replayed under the same lease id.
            assert await client.kv_get("/reg/instance-1") == b"worker-payload"

            # Old subscription object resumes delivery.
            pub = await StoreClient.open(server2.address)
            try:
                await pub.publish("events", b"hello-again")
                msg = await sub.get(timeout=5)
                assert msg["p"] == b"hello-again"

                # Watch resumed too (replayed with initial state, then live).
                await pub.kv_put("/reg/instance-2", b"x")
                saw = []
                for _ in range(10):
                    ev = await watch.get(timeout=5)
                    saw.append(StoreClient.as_watch_event(ev).key)
                    if "/reg/instance-2" in saw:
                        break
                assert "/reg/instance-2" in saw
            finally:
                await pub.close()

            # The replayed lease still expires if the client dies: revoke
            # and confirm the registration vanishes.
            await client.lease_revoke(lease)
            assert await client.kv_get("/reg/instance-1") is None
        finally:
            await server2.stop()
    finally:
        await client.close()


# -- reconnect backoff jitter (ISSUE 4 satellite) -----------------------------


def test_reconnect_delay_full_jitter_bounds():
    """Full jitter: every delay lands in [0, min(0.2 * 2**attempt, 2.0)]
    and the ceiling caps at 2.0 from attempt 4 on."""
    import random

    from dynamo_tpu.runtime.store.client import (
        RECONNECT_BASE_S,
        RECONNECT_CAP_S,
        RECONNECT_FACTOR,
        reconnect_delay,
    )

    rng = random.Random(7)
    for attempt in range(12):
        ceiling = min(
            RECONNECT_BASE_S * RECONNECT_FACTOR ** attempt, RECONNECT_CAP_S
        )
        for _ in range(200):
            d = reconnect_delay(attempt, rng)
            assert 0.0 <= d <= ceiling, (attempt, d, ceiling)
    assert RECONNECT_BASE_S * RECONNECT_FACTOR ** 4 > RECONNECT_CAP_S


def test_reconnect_delay_decorrelates_clients():
    """Two clients that disconnect at the same instant must not redial in
    lockstep: with jitter the per-attempt delays differ (this is the
    thundering-herd property the deterministic 0.2 -> x2 schedule lacked)."""
    import random

    from dynamo_tpu.runtime.store.client import reconnect_delay

    a = [reconnect_delay(i, random.Random(1)) for i in range(8)]
    b = [reconnect_delay(i, random.Random(2)) for i in range(8)]
    assert a != b
