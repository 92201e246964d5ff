"""Async-execution smoke: a mocker-backed frontend with ``--async-exec on``
streams BIT-IDENTICAL output to a twin deployment with it off, and the
worker's trace collector carries the ``host_gap`` stat the mocker's
pipelined loop reports per dispatch (its cost model's exact idle term).
The JAX engine files no such span: its step clock measures what the span
bounded, and a tiny engine on the pipelined loop must show the counters
(``dynamo_engine_late_landings_total``,
``dynamo_engine_device_starved_seconds_total``) on its ``/metrics`` text.

This is the user-visible contract of the async pipelined execution loop
(ISSUE 5): one-step-ahead scheduling and device-resident token feedback
change WHEN work happens — per-dispatch host overhead hides under device
compute — never which tokens are emitted. The same greedy request runs
against an async-on deployment and an async-off deployment (fresh store +
worker + frontend each, so no state leaks between the two), and the full
streamed text must match byte for byte.

CI usage (`.github/workflows/ci.yml` async-smoke step) and local:

    python tools/async_smoke.py
"""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

# Runnable straight from a checkout (CI also pip-installs the package).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


async def stream_text(session, url: str, body: dict) -> str:
    """POST a streaming chat completion; return the concatenated content."""
    import json

    parts: list[str] = []
    async with session.post(url, json=body) as resp:
        assert resp.status == 200, await resp.text()
        async for raw in resp.content:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:") or "[DONE]" in line:
                continue
            chunk = json.loads(line[len("data:"):])
            for choice in chunk.get("choices", []):
                parts.append((choice.get("delta") or {}).get("content") or "")
    return "".join(parts)


async def run_one(async_exec: bool) -> tuple[str, int]:
    """Boot store + mocker (async on/off) + frontend, stream one greedy
    request, and return (streamed text, host_gap stat-span count)."""
    import aiohttp

    from dynamo_tpu import tracing
    from dynamo_tpu.backends.mocker import run_mocker
    from dynamo_tpu.frontend.main import run_frontend
    from dynamo_tpu.llm.mocker import MockEngineArgs
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.store import StoreServer

    tracing.configure(enabled=True, sample=1.0)
    collector = tracing.get_collector()
    collector.clear()

    store = StoreServer()
    await store.start()
    worker_rt = await DistributedRuntime.create(store.address)
    served = asyncio.Event()
    worker = asyncio.create_task(
        run_mocker(
            worker_rt,
            model_name="mock",
            engine_args=MockEngineArgs(
                num_kv_blocks=8192,
                block_size=8,
                async_exec=async_exec,
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

        text = await stream_text(
            s, f"{base}/v1/chat/completions",
            {
                "model": "mock",
                "messages": [{"role": "user", "content": "async smoke test"}],
                "max_tokens": 32,
                "temperature": 0,
                "stream": True,
            },
        )

    gaps = [sp for sp in collector.stats() if sp.name == "host_gap"]
    if async_exec:
        assert gaps, "host_gap stat missing from the async-on worker"
        assert any(sp.attrs.get("overlapped") for sp in gaps), (
            "async-on worker never reported an overlapped dispatch gap"
        )

    for task in (worker, frontend):
        task.cancel()
    for rt in (worker_rt, front_rt):
        await rt.shutdown()
    await store.stop()
    return text, len(gaps)


def engine_account() -> tuple[float, float, float]:
    """A tiny JAX engine on the one-step-ahead loop, one request: (late
    landings, starved seconds lower, upper) as its worker's /metrics
    would show them."""
    from chipbench.readers import prometheus
    from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters

    core = EngineCore(tiny_model(), tiny_engine(async_exec=True, megastep_k=1), seed=0)
    seq = core.add_request(PreprocessedRequest(
        model="tiny", token_ids=[1, 2, 3], request_id="smoke",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=16, ignore_eos=True),
    ))
    while seq.finish is None or core.has_work():
        core.step()
    registry = MetricsRegistry()
    registry.registry.register(_EngineCounters(
        core.step_phase_seconds, core.scheduler_stats, core.device_account))
    text = [registry.render().decode()]
    busy = prometheus.total(text, "dynamo_engine_device_seconds_total")
    late = prometheus.total(text, "dynamo_engine_late_landings_total")
    lower, upper = (
        prometheus.total(text, "dynamo_engine_device_starved_seconds_total", {"bound": b})
        for b in ("lower", "upper")
    )
    assert busy and busy > 0, "the engine's step clock counted no device seconds"
    assert late is not None and lower is not None and lower <= upper, (late, lower, upper)
    return late, lower, upper


async def run() -> None:
    text_on, gaps_on = await run_one(True)
    text_off, _ = await run_one(False)
    assert text_on, "async-on deployment streamed nothing"
    assert text_on == text_off, (
        f"async-on stream diverged from async-off:\n  on : {text_on!r}\n"
        f"  off: {text_off!r}"
    )
    late, lower, upper = engine_account()
    print(
        f"async-smoke OK: {len(text_on)} chars bit-identical async-on vs "
        f"off; {gaps_on} host_gap stats recorded by the mocker; a tiny JAX "
        f"engine: {late:.0f} late landings, device starved "
        f"{lower * 1e3:.1f}..{upper * 1e3:.1f} ms (compiles included)",
        flush=True,
    )


def main() -> int:
    asyncio.run(run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
