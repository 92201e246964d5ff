"""E2E: OpenAI frontend + the real JAX engine worker (tiny model, CPU).

The full production path with the first-party engine: HTTP → preprocess →
KV router → data plane → EngineCore (jitted prefill/decode + paged cache)
→ detok → SSE. Parity: reference `tests/serve/test_vllm.py` (frontend +
real engine worker, completions asserted), minus the GPU.
"""

import asyncio

import aiohttp
import pytest

from dynamo_tpu.backends.jax.main import run_jax_worker
from dynamo_tpu.frontend.main import run_frontend
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.store import StoreServer

pytestmark = [pytest.mark.e2e, pytest.mark.pre_merge]


class JaxCluster:
    def __init__(
        self,
        num_workers: int = 1,
        router_mode: str = "kv",
        tp: int = 1,
        dp: int = 1,
        sp: int = 1,
        pp: int = 1,
        ring_prefill_threshold: int | None = None,
        model_path: str | None = None,
        engine_overrides: dict | None = None,
    ):
        self.num_workers = num_workers
        self.router_mode = router_mode
        self.tp = tp
        self.dp = dp
        self.sp = sp
        self.pp = pp
        self.model_path = model_path
        self.engine_overrides = engine_overrides
        self.ring_prefill_threshold = ring_prefill_threshold
        self.store = StoreServer()
        self.runtimes: list[DistributedRuntime] = []
        self.tasks: list[asyncio.Task] = []
        self.cores: list = []
        self.base_url = ""

    async def __aenter__(self) -> "JaxCluster":
        await self.store.start()
        for i in range(self.num_workers):
            rt = await DistributedRuntime.create(self.store.address)
            self.runtimes.append(rt)
            served = asyncio.Event()
            self.tasks.append(
                asyncio.create_task(
                    run_jax_worker(
                        rt,
                        model_name="tinyjax",
                        preset="tiny",
                        seed=0,
                        served_event=served,
                        core_out=self.cores,
                        tp=self.tp,
                        dp=self.dp,
                        sp=self.sp,
                        pp=self.pp,
                        model_path=self.model_path,
                        engine_overrides=(
                            self.engine_overrides
                            if self.engine_overrides is not None
                            else {"ring_prefill_threshold": self.ring_prefill_threshold}
                            if self.ring_prefill_threshold is not None
                            else None
                        ),
                    )
                )
            )
            await asyncio.wait_for(served.wait(), 30)
        front_rt = await DistributedRuntime.create(self.store.address)
        self.runtimes.append(front_rt)
        ready = asyncio.Event()
        services: list = []
        self.tasks.append(
            asyncio.create_task(
                run_frontend(
                    front_rt,
                    http_host="127.0.0.1",
                    http_port=0,
                    router_mode=self.router_mode,
                    ready_event=ready,
                    service_out=services,
                )
            )
        )
        await asyncio.wait_for(ready.wait(), 10)
        self.base_url = f"http://127.0.0.1:{services[0].port}"
        async with aiohttp.ClientSession() as s:
            for _ in range(200):
                async with s.get(f"{self.base_url}/v1/models") as r:
                    data = await r.json()
                    if data["data"]:
                        return self
                await asyncio.sleep(0.05)
        raise TimeoutError("model never appeared on frontend")

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


async def _chat(session, base_url, content, max_tokens=6, stream=False, extra=None):
    body = {
        "model": "tinyjax",
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "stream": stream,
        "temperature": 0.0,
    }
    if extra:
        body.update(extra)
    async with session.post(f"{base_url}/v1/chat/completions", json=body) as resp:
        assert resp.status == 200, await resp.text()
        return await resp.json()


async def test_jax_worker_completion_e2e():
    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, "hello tpu", max_tokens=6)
            choice = out["choices"][0]
            assert choice["finish_reason"] == "length"
            assert out["usage"]["completion_tokens"] == 6
            # Greedy determinism end-to-end: same request, same content —
            # and the repeat must hit the prefix cache.
            out2 = await _chat(s, c.base_url, "hello tpu", max_tokens=6)
            assert out2["choices"][0]["message"] == choice["message"]
            cached = out2["usage"].get("prompt_tokens_details", {}).get("cached_tokens", 0)
            assert cached > 0


async def test_jax_worker_tp_dp_sharded_e2e():
    """HTTP → router → TP×DP-sharded EngineCore on the virtual CPU mesh,
    greedy-identical to the unsharded engine."""
    async with JaxCluster(tp=2, dp=2) as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, "sharded hello", max_tokens=6)
            choice = out["choices"][0]
            assert choice["finish_reason"] == "length"
            assert out["usage"]["completion_tokens"] == 6
            sharded_text = choice["message"]["content"]
    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, "sharded hello", max_tokens=6)
            assert out["choices"][0]["message"]["content"] == sharded_text


async def test_jax_worker_concurrent_streams():
    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:

            async def one(i: int):
                return await _chat(s, c.base_url, f"request number {i}", max_tokens=4)

            results = await asyncio.gather(*[one(i) for i in range(8)])
            for out in results:
                assert out["usage"]["completion_tokens"] == 4


async def test_jax_worker_sequence_parallel_serving_e2e():
    """A deployed worker can enable ring prefill (--sp) without touching
    test code: HTTP -> router -> EngineCore with a sequence-parallel mesh,
    long prompt takes the dense ring-attention path, output greedy-
    identical to the unsharded engine (sequence-parallel
    serving must be reachable from the service, not just tests)."""
    # Long enough to clear the ring threshold once chat-templated; the
    # tiny engine's largest bucket is 128 so it must stay under that.
    long_content = "long context please " * 4  # 80 chars

    async with JaxCluster(sp=2, ring_prefill_threshold=96) as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, long_content, max_tokens=6)
            assert out["usage"]["completion_tokens"] == 6
            sp_text = out["choices"][0]["message"]["content"]
        assert c.cores[0]._ring_prefills > 0, (
            "long prompt never took the ring-prefill path"
        )
        # Short prompts stay on the paged ragged waves.
        async with aiohttp.ClientSession() as s:
            await _chat(s, c.base_url, "hi", max_tokens=4)
        assert c.cores[0]._ring_prefills == 1

    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, long_content, max_tokens=6)
            assert out["choices"][0]["message"]["content"] == sp_text


async def test_jax_worker_serves_hf_checkpoint_by_path():
    """--model-path serves real weights from an HF checkpoint directory
    (qwen2 family here: qkv biases + the checkpoint's own tokenizer) —
    the reference's serve-by-model-path surface (local_model.rs:429)."""
    pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import tempfile

    import torch as _torch

    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        use_sliding_window=False,
    )
    _torch.manual_seed(0)
    model = transformers.Qwen2ForCausalLM(cfg)
    with tempfile.TemporaryDirectory() as path:
        model.save_pretrained(path)
        # Weights-only checkpoint: the tokenizer default-resolves to the
        # path, finds no tokenizer files, and degrades to byte-level
        # with a warning (llm/tokenizer.py) — serving still works.
        overrides = dict(
            num_kv_blocks=32, block_size=8, max_num_seqs=4,
            max_model_len=128, prefill_buckets=(32, 64, 128),
            decode_buckets=(4,),
        )
        async with JaxCluster(model_path=path, engine_overrides=overrides) as c:
            async with aiohttp.ClientSession() as s:
                out = await _chat(s, c.base_url, "hi qwen", max_tokens=4)
                assert out["usage"]["completion_tokens"] == 4
        core = c.cores[0]
        assert core.cfg.attn_qkv_bias  # the qwen2 config drove the engine


async def test_jax_worker_pipeline_parallel_serving_e2e():
    """A deployed worker can enable pipeline parallelism (--pp) from the
    CLI surface: HTTP -> router -> EngineCore on a pp=2 mesh (GPipe
    prefill + wavefront decode), greedy-identical to the unsharded
    engine (a parallel mode only
    tests can construct does not count as implemented)."""
    async with JaxCluster(pp=2) as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, "staged hello", max_tokens=6)
            assert out["choices"][0]["finish_reason"] == "length"
            assert out["usage"]["completion_tokens"] == 6
            pp_text = out["choices"][0]["message"]["content"]
        assert c.cores[0]._pp == 2
    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            out = await _chat(s, c.base_url, "staged hello", max_tokens=6)
            assert out["choices"][0]["message"]["content"] == pp_text
