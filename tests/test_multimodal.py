"""Multimodal serving: processor split, patch-embed encoder, engine
splice, encoder-fleet descriptor handoff — e2e through the frontend.

Reference parity: `examples/multimodal/components/{processor,
encode_worker,worker}.py` (processor splits image refs; an encode worker
produces embeddings handed over by descriptor; the LLM worker consumes
them in place of the image's prompt positions).
"""

import asyncio
import base64

import aiohttp
import numpy as np
import pytest

from dynamo_tpu.llm.multimodal import (
    MM_PATCHES,
    image_bytes,
    patch_embed,
    pseudo_tokens,
    splice_pseudo_tokens,
    split_images,
)

pytestmark = [pytest.mark.pre_merge]


def data_url(payload: bytes) -> str:
    return "data:application/octet-stream;base64," + base64.b64encode(payload).decode()


IMG_A = data_url(b"a cat sitting on a red mat" * 9)
IMG_B = data_url(b"a dog running on green grass" * 9)


def test_processor_split_and_splice():
    messages = [
        {"role": "user", "content": [
            {"type": "text", "text": "what is in "},
            {"type": "image_url", "image_url": {"url": IMG_A}},
            {"type": "text", "text": " ?"},
        ]},
    ]
    out, refs = split_images(messages)
    assert refs == [IMG_A]
    assert "\x00img0\x00" in out[0]["content"]

    def encode(s: str) -> list[int]:
        return [b + 3 for b in s.encode()]  # byte-tokenizer-ish

    token_ids = encode(out[0]["content"])
    spliced, positions = splice_pseudo_tokens(token_ids, refs, 259, encode)
    (start, count), = positions
    assert count == MM_PATCHES
    assert spliced[start : start + count] == pseudo_tokens(IMG_A, 259)
    # Text around the image is untouched.
    assert spliced[:start] == encode("what is in ")
    assert spliced[start + count:] == encode(" ?")
    # Content-addressed: same image, same ids; different image, different.
    assert pseudo_tokens(IMG_A, 259) == pseudo_tokens(IMG_A, 259)
    assert pseudo_tokens(IMG_A, 259) != pseudo_tokens(IMG_B, 259)


def test_patch_embed_deterministic_and_content_sensitive():
    ea = patch_embed(image_bytes(IMG_A), hidden_size=64)
    assert ea.shape == (MM_PATCHES, 64) and ea.dtype == np.float32
    assert np.array_equal(ea, patch_embed(image_bytes(IMG_A), 64))
    assert not np.array_equal(ea, patch_embed(image_bytes(IMG_B), 64))


def test_engine_splices_image_embeddings():
    """Same text, different image -> different greedy output; same image
    twice -> identical output AND a prefix-cache hit (content-derived
    pseudo ids make the block hashes content-addressed)."""
    from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
    from tests.test_engine_core import _req, run_to_completion

    cfg = tiny_model()

    def mm_request(rid, img):
        text = [5, 6, 7, 8]
        pseudo = pseudo_tokens(img, cfg.vocab_size)
        pre = _req(text + pseudo + [9, 10], rid, max_tokens=6)
        emb = patch_embed(image_bytes(img), cfg.hidden_size)
        pre.mm = {
            "images": [img],
            "positions": [[len(text), MM_PATCHES]],
            "embeds": emb.astype(np.float32).tobytes(),
            "embeds_shape": list(emb.shape),
        }
        return pre

    core = EngineCore(cfg, tiny_engine(), seed=0)
    a1, _ = run_to_completion(core, [core.add_request(mm_request("a1", IMG_A))])
    b1, _ = run_to_completion(core, [core.add_request(mm_request("b1", IMG_B))])
    assert a1["a1"] != b1["b1"], "image content did not influence output"

    seq = core.add_request(mm_request("a2", IMG_A))
    a2, _ = run_to_completion(core, [seq])
    assert a2["a2"] == a1["a1"]
    assert seq.num_cached_tokens > 0, "identical image missed the prefix cache"


async def _mm_chat(session, base_url, img_url, text="describe ", max_tokens=6):
    body = {
        "model": "tinyjax",
        "messages": [{
            "role": "user",
            "content": [
                {"type": "text", "text": text},
                {"type": "image_url", "image_url": {"url": img_url}},
            ],
        }],
        "max_tokens": max_tokens,
        "temperature": 0.0,
    }
    async with session.post(f"{base_url}/v1/chat/completions", json=body) as resp:
        assert resp.status == 200, await resp.text()
        return await resp.json()


async def test_multimodal_e2e_local_encode():
    """Chat with an image_url through the full stack (no encoder fleet:
    the worker encodes in-process). Different images yield different
    tokens; a repeated image prefix-hits."""
    from tests.test_e2e_jax_worker import JaxCluster

    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            oa = await _mm_chat(s, c.base_url, IMG_A)
            ob = await _mm_chat(s, c.base_url, IMG_B)
            assert oa["usage"]["completion_tokens"] == 6
            assert (
                oa["choices"][0]["message"]["content"]
                != ob["choices"][0]["message"]["content"]
            ), "image content did not influence the completion"
            oa2 = await _mm_chat(s, c.base_url, IMG_A)
            assert oa2["choices"][0]["message"] == oa["choices"][0]["message"]
            cached = oa2["usage"].get("prompt_tokens_details", {}).get(
                "cached_tokens", 0
            )
            assert cached > 0


async def test_multimodal_e2e_encoder_fleet():
    """With an encoder fleet deployed, the worker uses the descriptor
    handoff (encode -> embed_fetch) and the output matches the local-
    encode path exactly (same deterministic vision stand-in)."""
    from dynamo_tpu.backends.encoder.main import run_encode_worker
    from dynamo_tpu.runtime import DistributedRuntime
    from tests.test_e2e_jax_worker import JaxCluster

    async with JaxCluster() as c:
        enc_rt = await DistributedRuntime.create(c.store.address)
        c.runtimes.append(enc_rt)
        served = asyncio.Event()
        stats: list = []
        c.tasks.append(
            asyncio.create_task(
                run_encode_worker(
                    enc_rt, served_event=served, stats_out=stats
                )
            )
        )
        await asyncio.wait_for(served.wait(), 10)
        # The worker's encoder client watch needs a beat to see it.
        await asyncio.sleep(0.3)

        async with aiohttp.ClientSession() as s:
            out = await _mm_chat(s, c.base_url, IMG_A)
            assert out["usage"]["completion_tokens"] == 6
        assert stats[0]["encoded"] >= 1, "encoder fleet never encoded"
        assert stats[0]["fetched"] >= 1, "descriptor was never pulled"

    # Output parity with the local-encode path.
    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            ref = await _mm_chat(s, c.base_url, IMG_A)
            assert (
                ref["choices"][0]["message"]["content"]
                == out["choices"][0]["message"]["content"]
            )


async def test_multimodal_request_through_mocker():
    """CI routing support: the mocker engine serves a multimodal request
    (pseudo tokens + mm fields ride the normal wire) without real
    embeddings — router/caching behavior stays testable GPU/TPU-free."""
    from tests.test_e2e_frontend import Cluster

    async with Cluster(num_workers=1) as c:
        async with aiohttp.ClientSession() as s:
            body = {
                "model": "mock",
                "messages": [{
                    "role": "user",
                    "content": [
                        {"type": "text", "text": "look: "},
                        {"type": "image_url", "image_url": {"url": IMG_A}},
                    ],
                }],
                "max_tokens": 5,
                "temperature": 0.0,
            }
            async with s.post(
                f"{c.base_url}/v1/chat/completions", json=body
            ) as resp:
                assert resp.status == 200, await resp.text()
                out = await resp.json()
            assert out["usage"]["completion_tokens"] == 5


async def test_multimodal_disaggregated_matches_aggregated():
    """Long multimodal prompts survive the P/D split: the work-queue
    payload is msgpack (raw embed bytes cannot ride json), the prefill
    fleet splices the same embeddings, and the output equals the
    aggregated path."""
    from tests.test_disagg import DisaggCluster
    from tests.test_e2e_jax_worker import JaxCluster

    long_text = "look closely at this picture and describe every detail "

    async def ask(base_url, s):
        body = {
            "model": "tinyjax",
            "messages": [{
                "role": "user",
                "content": [
                    {"type": "text", "text": long_text},
                    {"type": "image_url", "image_url": {"url": IMG_A}},
                ],
            }],
            "max_tokens": 6,
            "temperature": 0.0,
        }
        async with s.post(f"{base_url}/v1/chat/completions", json=body) as r:
            assert r.status == 200, await r.text()
            return await r.json()

    async with JaxCluster() as c:
        async with aiohttp.ClientSession() as s:
            want = await ask(c.base_url, s)

    async with DisaggCluster() as c:
        async with aiohttp.ClientSession() as s:
            got = await ask(c.base_url, s)
            assert got["choices"][0]["message"] == want["choices"][0]["message"]
            # The prompt is past the disagg threshold: the prefill fleet
            # actually served it (queue payload survived msgpack transit).
            assert c.prefill_core.iterations > 0
