"""Pipeline-parallel smoke: a mocker-backed frontend deployed with
``--pp 2`` (two pipeline stages, fused ``--megastep-k 8`` megasteps)
streams BIT-IDENTICAL output to a twin deployment running unpipelined
(pp=1), the worker's ``engine_megastep`` spans carry the ``pp_stages``
attr (the per-dispatch pipelining evidence), and the ``scheduler_pp_*``
gauges export on /metrics.

This is the user-visible contract of pp on the fast path (ISSUE 20):
pipeline stages change WHERE layers live and how iterations wavefront
through the stage ring — ``k*pp + pp - 1`` ppermute hops amortized over
one fused dispatch instead of ``pp`` hops per token on the
host-rollback baseline — never which tokens a request streams. The real
engine's bit-parity + quantization-composition invariants, and the
mocker's fused-against-forced-single counters, are pinned by
tests/test_pp_megastep.py.

CI usage (`.github/workflows/ci.yml` pp-smoke step) and local:

    python tools/pp_smoke.py
"""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

# Runnable straight from a checkout (CI also pip-installs the package).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from tools.kvquant_smoke import _gauge_value  # noqa: E402
from tools.megastep_smoke import stream_text  # noqa: E402


async def run_one(pp: int) -> tuple[list[str], str, list]:
    """Boot store + mocker (pp stages, megastep k=8) + frontend with a
    live status server, stream two greedy requests, and return
    (streamed texts, the worker's /metrics text, engine_megastep spans).
    """
    import aiohttp

    from dynamo_tpu import tracing
    from dynamo_tpu.backends.mocker import run_mocker
    from dynamo_tpu.frontend.main import run_frontend
    from dynamo_tpu.llm.mocker import MockEngineArgs
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.status_server import SystemStatusServer
    from dynamo_tpu.runtime.store import StoreServer

    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()

    store = StoreServer()
    await store.start()
    worker_rt = await DistributedRuntime.create(store.address)
    status = SystemStatusServer(host="127.0.0.1", port=0)
    await status.start()
    worker_rt.status = status  # bind_scheduler_gauges hooks in run_mocker
    served = asyncio.Event()
    worker = asyncio.create_task(
        run_mocker(
            worker_rt,
            model_name="mock",
            engine_args=MockEngineArgs(
                num_kv_blocks=4096,
                block_size=8,
                megastep_k=8,
                pp=pp,
                speedup_ratio=50.0,
            ),
            served_event=served,
        )
    )
    await asyncio.wait_for(served.wait(), 30)
    front_rt = await DistributedRuntime.create(store.address)
    ready = asyncio.Event()
    services: list = []
    frontend = asyncio.create_task(
        run_frontend(
            front_rt, http_host="127.0.0.1", http_port=0,
            router_mode="kv", ready_event=ready, service_out=services,
        )
    )
    await asyncio.wait_for(ready.wait(), 30)
    base = f"http://127.0.0.1:{services[0].port}"

    async with aiohttp.ClientSession() as s:
        for _ in range(200):
            async with s.get(f"{base}/v1/models") as r:
                if (await r.json())["data"]:
                    break
            await asyncio.sleep(0.05)
        else:
            raise TimeoutError("model never appeared on frontend")

        url = f"{base}/v1/chat/completions"
        texts = []
        for content, mt in (("pp smoke test", 32), ("pipeline twin", 48)):
            texts.append(await stream_text(s, url, {
                "model": "mock",
                "messages": [{"role": "user", "content": content}],
                "max_tokens": mt,
                "temperature": 0,
                "stream": True,
            }))
        async with s.get(f"http://127.0.0.1:{status.port}/metrics") as r:
            assert r.status == 200
            metrics = await r.text()

    spans = [sp for sp in collector.stats() if sp.name == "engine_megastep"]
    for task in (worker, frontend):
        task.cancel()
    for rt in (worker_rt, front_rt):
        await rt.shutdown()
    await status.stop()
    await store.stop()
    return texts, metrics, spans


async def run() -> None:
    texts_pp, m_pp, spans_pp = await run_one(2)
    assert all(texts_pp), "pp=2 deployment streamed nothing"
    assert spans_pp, "pp=2 worker recorded no engine_megastep spans"
    assert all(sp.attrs.get("pp_stages") == 2 for sp in spans_pp), (
        "engine_megastep span missing the pp_stages attr"
    )
    assert _gauge_value(m_pp, "dynamo_scheduler_pp_stages") == 2.0
    # k=8 over 2 stages: 16 wavefront items over 16 + 1 rounds.
    occ = _gauge_value(m_pp, "dynamo_scheduler_pp_pipe_occupancy")
    assert abs(occ - 16.0 / 17.0) < 1e-6, occ
    fused = _gauge_value(m_pp, "dynamo_scheduler_pp_fused_dispatches_total")
    assert fused >= 1, "pp=2 worker fused no pp megastep dispatches"
    assert _gauge_value(
        m_pp, "dynamo_scheduler_pp_forced_single_total"
    ) == 0.0, "a decode batch fell back to forced k=1 under pp"

    texts_1, m_1, spans_1 = await run_one(1)
    assert texts_pp == texts_1, (
        f"pp=2 stream diverged from the unpipelined twin:\n"
        f"  pp2: {texts_pp!r}\n  pp1: {texts_1!r}"
    )
    assert all(sp.attrs.get("pp_stages") == 1 for sp in spans_1)
    assert _gauge_value(m_1, "dynamo_scheduler_pp_stages") == 1.0
    assert _gauge_value(m_1, "dynamo_scheduler_pp_pipe_occupancy") == 1.0
    assert _gauge_value(
        m_1, "dynamo_scheduler_pp_fused_dispatches_total"
    ) == 0.0

    print(
        f"pp-smoke OK: {sum(len(t) for t in texts_pp)} chars bit-identical "
        f"pp=2 vs pp=1; {fused:.0f} fused pp dispatches, 0 forced-single, "
        f"pipe occupancy {occ:.4f} on /metrics", flush=True,
    )


def main() -> int:
    asyncio.run(run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
