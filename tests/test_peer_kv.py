"""Cross-worker KV visibility: a prefix cached (or offloaded) on worker
A is PULLABLE by worker B over the data plane instead of recomputed.

Reference parity: KVBM-distributed leader/worker
(`lib/llm/src/block_manager/distributed/leader.rs:64`) — the router's
radix view spans workers; when routing cannot land on the best-overlap
worker, the chosen worker onboards the peer's blocks (device tier or
host/disk offload tiers) through the ``kv_fetch`` endpoint.
"""

import asyncio

import aiohttp
import pytest

from dynamo_tpu.backends.jax.main import run_jax_worker
from dynamo_tpu.frontend.main import run_frontend
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.store import StoreServer

pytestmark = [pytest.mark.e2e, pytest.mark.pre_merge]


class PeerCluster:
    """N aggregated jax workers with tiny device pools + host/disk
    offload tiers, plus a frontend (KV routing). ``kv_dtype`` may be a
    single dtype or a per-worker list (mixed-fleet tests)."""

    def __init__(self, tmp_path, kv_dtype: "str | list[str]" = "bf16", n: int = 2):
        self.tmp_path = tmp_path
        self.n = n
        self.kv_dtypes = (
            list(kv_dtype) if isinstance(kv_dtype, list) else [kv_dtype] * n
        )
        self.store = StoreServer()
        self.runtimes: list[DistributedRuntime] = []
        self.worker_ids: list[int] = []
        self.cores: list = []
        self.tasks: list[asyncio.Task] = []
        self.service = None
        self.base_url = ""

    async def __aenter__(self) -> "PeerCluster":
        await self.store.start()
        for i in range(self.n):
            rt = await DistributedRuntime.create(self.store.address)
            self.runtimes.append(rt)
            served = asyncio.Event()
            self.tasks.append(
                asyncio.create_task(
                    run_jax_worker(
                        rt, model_name="peer", preset="tiny", seed=0,
                        served_event=served, core_out=self.cores,
                        engine_overrides={
                            "num_kv_blocks": 16,
                            "host_kv_blocks": 8,
                            "disk_kv_dir": str(self.tmp_path / f"disk{i}"),
                            "disk_kv_blocks": 64,
                            "kv_dtype": self.kv_dtypes[i],
                        },
                    )
                )
            )
            await asyncio.wait_for(served.wait(), 30)
            self.worker_ids.append(rt.primary_lease_id)
        front_rt = await DistributedRuntime.create(self.store.address)
        self.runtimes.append(front_rt)
        ready = asyncio.Event()
        services: list = []
        self.tasks.append(
            asyncio.create_task(
                run_frontend(
                    front_rt, http_host="127.0.0.1", http_port=0,
                    router_mode="kv", ready_event=ready, service_out=services,
                )
            )
        )
        await asyncio.wait_for(ready.wait(), 10)
        self.service = services[0]
        self.base_url = f"http://127.0.0.1:{self.service.port}"
        async with aiohttp.ClientSession() as s:
            for _ in range(200):
                async with s.get(f"{self.base_url}/v1/models") as r:
                    if (await r.json())["data"]:
                        return self
                await asyncio.sleep(0.05)
        raise TimeoutError("model never appeared")

    async def __aexit__(self, *exc) -> None:
        for rt in self.runtimes:
            rt.signal_shutdown()
        await asyncio.sleep(0.1)
        for t in self.tasks:
            t.cancel()
        for rt in self.runtimes:
            try:
                await rt.shutdown()
            # dynalint: allow-broad-except(best-effort teardown; runtime may already be closed)
            except Exception:
                pass
        await self.store.stop()


def _pre(prompt, rid, max_tokens=4):
    return PreprocessedRequest(
        model="peer", token_ids=list(prompt), request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    )


async def _route(push_router, pre, **kw):
    toks = []
    async for out in push_router.generate(
        pre.to_wire(), pre.request_id, list(pre.token_ids), **kw
    ):
        toks.extend(out.get("token_ids") or [])
    push_router.router.free(pre.request_id)
    return toks


async def test_peer_pull_avoids_recompute_after_offload(tmp_path):
    """Worker A caches a prompt, overflows it down to its offload tiers;
    a request EXCLUDED from A (migration semantics) lands on B, which
    pulls the prefix from A's tiers and prefix-hits instead of
    recomputing."""
    prompt = list(range(1, 90))  # 11 complete 8-token blocks
    async with PeerCluster(tmp_path) as c:
        served = c.service.manager.get("peer")
        assert served is not None and served.push_router is not None
        push = served.push_router
        a_id = c.worker_ids[0]
        a_core = c.cores[0]
        b_core = c.cores[1]

        # 1) Land the prompt on worker A (pinned for determinism).
        want = await _route(
            push, _pre(prompt, "seed"),
            router_overrides={"backend_instance_id": a_id},
        )
        assert len(want) == 4

        # 2) Overflow A's 16-block device pool so the prompt's blocks
        #    demote to host/disk (KV events stay 'stored': the worker can
        #    still serve them).
        for i in range(3):
            filler = list(range(100 + 40 * i, 140 + 40 * i))
            await _route(
                push, _pre(filler, f"fill{i}"),
                router_overrides={"backend_instance_id": a_id},
            )
        a_core.offload.flush()
        assert len(a_core.host_pool) + len(a_core.disk_pool) > 0, (
            "filler never pushed the prompt into the offload tiers"
        )

        # 3) Same prompt, A excluded: B must get the peer hint, pull the
        #    prefix, and answer identically with a prefix-cache hit.
        assert b_core.transfer_stats["imported_blocks"] == 0
        got = []
        cached = 0
        async for out in push.generate(
            _pre(prompt, "reroute").to_wire(), "reroute", list(prompt),
            exclude={a_id},
        ):
            got.extend(out.get("token_ids") or [])
            meta = out.get("meta") or {}
            cached = max(cached, meta.get("cached_tokens", 0))
        push.router.free("reroute")

        assert got == want, "peer-pulled decode diverged"
        assert b_core.transfer_stats["imported_blocks"] > 0, (
            "worker B never pulled the peer prefix"
        )
        assert cached > 0, "pulled prefix was not prefix-cache-hit"
        # The pull is non-destructive: A still holds its tiers.
        assert len(a_core.host_pool) + len(a_core.disk_pool) > 0


async def test_kv_fetch_serves_int8_packed_pages(tmp_path):
    """ISSUE 8: an int8 fleet's ``kv_fetch`` endpoint announces
    dtype="int8" in its geometry frame and streams the canonical packed
    pages (int8 bytes + scales) — byte-identical to the producer's
    device content — and the peer imports them verbatim and serves the
    prefix with the same greedy output. (Exercises the SERVER half of
    the peer pull directly; the asyncio.timeout client half is covered
    by test_peer_pull_avoids_recompute_after_offload on 3.11+.)"""
    from dynamo_tpu.tokens import compute_seq_hashes

    prompt = list(range(1, 90))  # 11 complete 8-token blocks
    async with PeerCluster(tmp_path, kv_dtype="int8") as c:
        served = c.service.manager.get("peer")
        push = served.push_router
        a_id = c.worker_ids[0]
        a_core, b_core = c.cores[0], c.cores[1]
        assert a_core.engine.kv_quantized

        want = await _route(
            push, _pre(prompt, "seed"),
            router_overrides={"backend_instance_id": a_id},
        )
        assert len(want) == 4

        bs = a_core.engine.block_size
        hashes = compute_seq_hashes(prompt, bs)[: (len(prompt) - 1) // bs]
        local = a_core.read_cached_pages(hashes)
        assert len(local) == len(hashes)

        fetch_client = await (
            c.runtimes[0].namespace("dynamo").component("backend")
            .endpoint("kv_fetch").client()
        )
        await fetch_client.wait_for_instances(2)
        stream = await fetch_client.direct(a_id, {"hashes": hashes})
        dtype = None
        pages: list[bytes] = []
        async for frame in stream:
            if "dtype" in frame:
                dtype = frame["dtype"]
            if "kv" in frame:
                pages.extend(frame["kv"])
        assert dtype == "int8", "geometry frame did not announce int8"
        assert [bytes(p) for p in pages] == local, (
            "wire pages diverged from the producer's device bytes"
        )

        # The consumer-side import (what _pull_peer_prefix does with
        # these frames) lands them bit-identically and serves the prefix.
        shape = [
            a_core.cfg.num_layers, bs,
            2 * a_core.cfg.num_kv_heads, a_core.cfg.head_dim,
        ]
        blocks = [
            {
                "hash": h,
                "parent": hashes[i - 1] if i else None,
                "shape": shape, "dtype": "int8", "kv": kv,
            }
            for i, (h, kv) in enumerate(zip(hashes, pages))
        ]
        res = b_core.import_blocks(blocks)
        assert res.imported == len(blocks) and res.dropped == 0
        assert b_core.read_cached_pages(hashes) == local
        got = await _route(
            push, _pre(prompt, "peer-serve"),
            router_overrides={"backend_instance_id": c.worker_ids[1]},
        )
        assert got == want, "int8 peer-served decode diverged"


async def test_three_worker_pool_shared_prefix_e2e(tmp_path):
    """ISSUE 11 three-worker pool: a shared prefix cached on worker A; a
    request EXCLUDED from A lands on one of B/C, which pulls the blocks
    from A over the dataplane and streams BIT-IDENTICALLY to A's cold
    prefill — while the third worker never touches the prefix."""
    prompt = list(range(1, 90))  # 11 complete 8-token blocks
    async with PeerCluster(tmp_path, n=3) as c:
        served = c.service.manager.get("peer")
        push = served.push_router
        a_id = c.worker_ids[0]
        a_core = c.cores[0]

        want = await _route(
            push, _pre(prompt, "seed"),
            router_overrides={"backend_instance_id": a_id},
        )
        assert len(want) == 4

        got = []
        async for out in push.generate(
            _pre(prompt, "reroute").to_wire(), "reroute", list(prompt),
            exclude={a_id},
        ):
            got.extend(out.get("token_ids") or [])
        push.router.free("reroute")
        assert got == want, "cross-worker pooled decode diverged"

        pulled = [
            core for core in c.cores[1:]
            if core.transfer_stats["imported_blocks"] > 0
        ]
        assert len(pulled) == 1, (
            "exactly one of B/C must have pulled the prefix: "
            f"{[core.transfer_stats for core in c.cores]}"
        )
        assert pulled[0].transfer_stats["imported_blocks"] >= 11
        # A still serves its copy (the pull is non-destructive).
        assert a_core.cached_prefix_tokens(prompt) > 0


async def test_mixed_dtype_fleet_pull_fails_fast_and_recomputes(tmp_path):
    """PR 8 dtype contract at the pool layer: a bf16 worker's pages must
    NOT import into an int8 worker (re-quantizing breaks bit-stability).
    The pull fails fast, the request completes via local recompute, and
    the recomputed prefix serves consistently afterwards."""
    prompt = list(range(1, 90))
    async with PeerCluster(tmp_path, kv_dtype=["bf16", "int8"]) as c:
        served = c.service.manager.get("peer")
        push = served.push_router
        a_id = c.worker_ids[0]
        b_core = c.cores[1]
        assert not c.cores[0].engine.kv_quantized
        assert b_core.engine.kv_quantized

        await _route(
            push, _pre(prompt, "seed"),
            router_overrides={"backend_instance_id": a_id},
        )
        got = await _route(push, _pre(prompt, "reroute"), exclude={a_id})
        assert len(got) == 4, "mixed-dtype fallback lost the stream"
        # The fail-fast contract: NOTHING imported across the dtype edge.
        assert b_core.transfer_stats["imported_blocks"] == 0
        # The fallback recompute cached the prefix locally: a pinned
        # repeat on B streams identically (its own quantized decode).
        got2 = await _route(
            push, _pre(prompt, "again"),
            router_overrides={"backend_instance_id": c.worker_ids[1]},
        )
        assert got2 == got, "post-fallback repeat diverged"


async def test_chaos_sever_mid_pull_degrades_to_recompute(tmp_path):
    """Acceptance chaos e2e (jax engines): the peer connection is severed
    MID-PULL (after the first frame); the request completes via local
    recompute with a stream bit-identical to the no-fault run — no
    wedged request, no stall."""
    from dynamo_tpu.runtime import chaos
    from dynamo_tpu.runtime.chaos import ChaosPlan, ChaosRule

    prompt = list(range(1, 90))
    try:
        async with PeerCluster(tmp_path) as c:
            served = c.service.manager.get("peer")
            push = served.push_router
            a_id = c.worker_ids[0]
            b_core = c.cores[1]

            want = await _route(
                push, _pre(prompt, "seed"),
                router_overrides={"backend_instance_id": a_id},
            )
            a_addr = c.runtimes[0].ingress.address
            chaos.install(ChaosPlan(rules=[
                ChaosRule(
                    point="dataplane.recv", action="sever",
                    match=a_addr, after=1,
                ),
            ]))
            got = await _route(push, _pre(prompt, "reroute"), exclude={a_id})
            chaos.uninstall()
            assert got == want, "sever mid-pull broke the stream"
            # At most the pre-sever chunk imported; the rest recomputed.
            assert b_core.transfer_stats["imported_blocks"] < 11
    finally:
        chaos.uninstall()
