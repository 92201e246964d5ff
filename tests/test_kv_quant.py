"""Quantized KV cache (ISSUE 8): per-block int8 pages + scale metadata.

The three invariants this file pins:

1. **Quality guard** — int8 KV vs bf16 KV on the model harness: greedy
   next-token agreement (teacher-forced, so one flip cannot cascade) and
   a max-logit-error bound. Bounds measured at 1.0 / 0.064 on the tiny
   preset and pinned with margin.
2. **Bit-stability** — the int8 bytes + scales a block was given at
   write time are IDENTICAL at every place the block ever lives: device
   pages, host tier, disk tier, back on device after onboarding, and on
   a peer after a kv transfer. Quantize once, never re-quantize.
3. **Fail-fast dtype fencing** — a mixed-dtype peer pull (int8 producer,
   bf16 consumer or vice versa) raises instead of silently casting or
   re-quantizing.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine.kv_quant import (
    dequantize_kv,
    kv_byte_ratio,
    kv_page_bytes,
    pack_kv_page,
    quantize_kv,
    unpack_kv_page,
)
from dynamo_tpu.tokens import compute_seq_hashes
from tests.test_engine_core import _req, run_to_completion
from tests.test_host_kv_tier import _fill_with_noise

CFG = tiny_model()

# Quality-guard bounds (measured on the tiny preset: teacher-forced
# agreement 1.0, max logit delta 0.064 — pinned with ~4x margin; a
# regression past these means the quantizer, the scale layout, or the
# dequant path broke, not noise).
GREEDY_MATCH_FLOOR = 0.98
MAX_LOGIT_ERR = 0.25


def make_core(kv_dtype="int8", **kw) -> EngineCore:
    return EngineCore(CFG, tiny_engine(kv_dtype=kv_dtype, **kw), seed=0)


# -- unit: quantizer + packed representation --------------------------------

def test_quantize_dequantize_error_bound():
    rng = np.random.RandomState(0)
    kvn = jnp.asarray(rng.randn(17, 4, 16).astype(np.float32) * 3.0)
    q, sc = quantize_kv(kvn)
    assert q.dtype == jnp.int8 and sc.shape == (17, 4)
    deq = dequantize_kv(q, sc)
    # Symmetric int8: error per element <= scale/2 = amax/254.
    bound = np.abs(np.asarray(kvn)).max(axis=-1, keepdims=True) / 254.0 + 1e-6
    assert (np.abs(np.asarray(deq) - np.asarray(kvn)) <= bound).all()
    # Zero rows stay exactly zero (scale floor, no NaN).
    qz, scz = quantize_kv(jnp.zeros((3, 4, 16)))
    assert np.asarray(qz).any() == False  # noqa: E712
    assert np.isfinite(np.asarray(scz)).all()


def test_pack_unpack_roundtrip_and_size_validation():
    rng = np.random.RandomState(1)
    L, bs, n_kv, d = 2, 8, 2, 16
    kv = rng.randint(-127, 128, size=(L, bs, 2 * n_kv, d)).astype(np.int8)
    sc = np.abs(rng.randn(L, bs, 2 * n_kv)).astype(np.float32)
    buf = pack_kv_page(kv, sc)
    assert buf.dtype == np.uint8 and buf.ndim == 1
    kv2, sc2 = unpack_kv_page(buf, L, bs, n_kv, d)
    assert kv2.tobytes() == kv.tobytes()
    assert sc2.tobytes() == sc.tobytes()
    # Bytes round trip too (the wire carries bytes, not arrays).
    kv3, sc3 = unpack_kv_page(buf.tobytes(), L, bs, n_kv, d)
    assert kv3.tobytes() == kv.tobytes() and sc3.tobytes() == sc.tobytes()
    with pytest.raises(ValueError, match="does not match"):
        unpack_kv_page(buf[:-1], L, bs, n_kv, d)


def test_capacity_ratio_at_fixed_budget():
    """The headline capacity claim: >= 1.8x resident blocks at a fixed
    HBM budget for llama3-8b geometry (the primary bench shape)."""
    bf16 = kv_page_bytes(32, 32, 8, 128, "bf16")
    int8 = kv_page_bytes(32, 32, 8, 128, "int8")
    budget = 8 << 30
    assert (budget // int8) / (budget // bf16) >= 1.8
    assert abs(kv_byte_ratio("int8", 128) - int8 / bf16) < 1e-9
    assert kv_byte_ratio("bf16") == 1.0


def test_bf16_default_layout_untouched():
    """kv_dtype defaults to bf16 and keeps plain per-layer arrays — the
    classic path must be byte-for-byte the pre-quantization layout."""
    core = EngineCore(CFG, tiny_engine(), seed=0)
    assert core.engine.kv_dtype == "bf16"
    assert not core.engine.kv_quantized
    assert isinstance(core.cache, tuple)
    assert not isinstance(core.cache[0], dict)
    q = make_core()
    assert isinstance(q.cache[0], dict)
    assert q.cache[0]["kv"].dtype == jnp.int8
    assert q.cache[0]["scale"].dtype == jnp.float32
    assert q.cache[0]["scale"].shape == q.cache[0]["kv"].shape[:-1]


def test_unknown_kv_dtype_rejected():
    with pytest.raises(ValueError, match="kv_dtype"):
        EngineCore(CFG, tiny_engine(kv_dtype="fp8"), seed=0)


# -- quality guard (the pinned greedy-match / logit-error bound) ------------

def test_quality_guard_greedy_match_and_logit_error():
    """Teacher-forced comparison so a single early flip cannot cascade:
    both caches consume the bf16 path's greedy tokens; at every position
    the int8 cache must pick the same argmax, wherever the bf16 logits
    decide it by more than the logit error, and stay inside the logit
    error bound."""
    from dynamo_tpu.engine.model import init_cache, init_params
    from tests.model_harness import prefill_chunk

    eng_bf = tiny_engine(max_model_len=256)
    eng_q = tiny_engine(max_model_len=256, kv_dtype="int8")
    params = init_params(jax.random.PRNGKey(0), CFG)
    total = match = 0
    max_err = 0.0
    for t in range(3):
        prompt = list(np.random.RandomState(t).randint(1, 300, size=40))
        ids = list(range(12))
        c_bf, c_q = init_cache(CFG, eng_bf), init_cache(CFG, eng_q)
        l_bf, c_bf = prefill_chunk(params, c_bf, prompt, 0, ids, CFG, eng_bf, 64)
        l_q, c_q = prefill_chunk(params, c_q, prompt, 0, ids, CFG, eng_q, 64)
        pos = len(prompt)
        for _ in range(16):
            a, b = int(np.argmax(l_bf)), int(np.argmax(l_q))
            err = float(np.max(np.abs(np.asarray(l_bf) - np.asarray(l_q))))
            max_err = max(max_err, err)
            # A near-tie is not a quality loss: where the bf16 run's two
            # best logits lie within twice this step's logit error, either
            # is a fair argmax of the quantised run, and the step is not
            # counted.
            top2 = np.sort(np.asarray(l_bf, np.float32).ravel())[-2:]
            if a == b or float(top2[1] - top2[0]) > 2.0 * err:
                total += 1
                match += a == b
            l_bf, c_bf = prefill_chunk(params, c_bf, [a], pos, ids, CFG, eng_bf, 32)
            l_q, c_q = prefill_chunk(params, c_q, [a], pos, ids, CFG, eng_q, 32)
            pos += 1
    assert total >= 40, f"only {total} of 48 steps were decided by a clear margin"
    assert match / total >= GREEDY_MATCH_FLOOR, (
        f"greedy agreement {match / total:.3f} under the pinned floor"
    )
    assert max_err <= MAX_LOGIT_ERR, (
        f"max logit error {max_err:.4f} over the pinned bound"
    )


def test_int8_megastep_stream_matches_k1():
    """The megastep invariant holds WITHIN the int8 dtype: k=8 and k=1
    produce bit-identical streams (quantized decode writes are inside
    the scanned body)."""
    prompt = list(range(7, 7 + 40))
    a = make_core(megastep_k=1)
    d1, _ = run_to_completion(a, [a.add_request(_req(prompt, "x", max_tokens=12))])
    b = make_core(megastep_k=8)
    d8, _ = run_to_completion(b, [b.add_request(_req(prompt, "x", max_tokens=12))])
    assert d1["x"] == d8["x"]
    assert b.exec_stats["megastep_dispatches"] >= 1


# -- bit-stability across every tier and transfer ---------------------------

def test_int8_bytes_stable_device_host_disk_onboard_peer(tmp_path):
    """THE round-trip satellite: quantized block bytes (int8 payload +
    scales, packed) are identical at every hop — device pages -> host
    tier -> disk tier -> onboarded back to device -> pulled by a peer
    over the kv-transfer bytes path. Quantize exactly once."""
    prompt = list(range(7, 7 + 40))
    base = make_core()
    ref, _ = run_to_completion(
        base, [base.add_request(_req(prompt, "ref", max_tokens=6))]
    )

    core = make_core(
        num_kv_blocks=24, host_kv_blocks=4,
        disk_kv_dir=str(tmp_path / "g3"), disk_kv_blocks=256,
        max_model_len=128,
    )
    s1 = core.add_request(_req(prompt, "a", max_tokens=6))
    run_to_completion(core, [s1])
    bs = core.engine.block_size
    cap = (len(prompt) - 1) // bs
    prefix_hashes = s1.prompt_hashes[:cap]
    # Hop 0: canonical bytes while device-resident.
    w0 = core.read_cached_pages(prefix_hashes)
    assert len(w0) == cap
    geom = core._page_geometry()
    for buf in w0:
        unpack_kv_page(buf, *geom)  # parses at the local geometry

    # Hop 1+2: evict through host into disk.
    _fill_with_noise(core, n_requests=8)
    _fill_with_noise(core, n_requests=8, tag=2000)
    core.offload.flush()
    in_host = [h for h in prefix_hashes if h in core.host_pool]
    in_disk = [h for h in prefix_hashes if h in core.disk_pool]
    assert in_host or in_disk, "noise did not push the prefix off-device"
    for i, h in enumerate(prefix_hashes):
        if h in core.host_pool:
            assert core.host_pool._blocks[h].kv.tobytes() == w0[i], (
                "host-tier bytes diverged from the device write"
            )
        if h in core.disk_pool:
            assert core.disk_pool.peek(h).tobytes() == w0[i], (
                "disk-tier bytes diverged from the device write"
            )

    # Hop 3: onboard back to device (admission prefix hit).
    s2 = core.add_request(_req(prompt, "b", max_tokens=6))
    d2, _ = run_to_completion(core, [s2])
    assert core.host_pool.stats.onboards + core.disk_pool.stats.onboards > 0
    assert s2.num_cached_tokens > 0
    assert d2["b"] == ref["ref"], "output changed across the tier round trip"
    w1 = core.read_cached_pages(prefix_hashes)
    assert w1 == w0, "onboarded device bytes diverged from the original"

    # Hop 4: peer pull over the kv-transfer bytes path.
    peer = make_core()
    blocks = []
    parent = None
    for h, buf in zip(prefix_hashes, w1):
        blocks.append({
            "hash": h, "parent": parent,
            "shape": [CFG.num_layers, bs, 2 * CFG.num_kv_heads, CFG.head_dim],
            "dtype": "int8",
            "layout": {"kind": "combined_kv_page", "block_size": bs,
                       "kv_dtype": "int8"},
            "kv": buf,
        })
        parent = h
    res = peer.import_blocks(blocks)
    assert res.imported == cap and res.dropped == 0
    w2 = peer.read_cached_pages(prefix_hashes)
    assert w2 == w0, "peer-imported bytes diverged from the original"
    # And the peer serves the prefix: same greedy output, prefix cached.
    s3 = peer.add_request(_req(prompt, "c", max_tokens=6))
    d3, _ = run_to_completion(peer, [s3])
    assert s3.num_cached_tokens >= cap * bs
    assert d3["c"] == ref["ref"]


def test_int8_disagg_hold_and_direct_import_byte_stable():
    """The disagg path proper: a held prefill's pages export as packed
    int8 bytes and a co-located core direct-imports them bit-identically
    (ONE device program, no host staging)."""
    a = make_core()
    prompt = list(range(3, 3 + 40))
    pre = _req(prompt, "hold", max_tokens=2)
    pre.kv_transfer_params = {"do_remote_decode": True}
    run_to_completion(a, [a.add_request(pre)])
    descs = a.export_descriptors("hold")
    assert descs and descs[0]["dtype"] == "int8"
    assert descs[0]["layout"]["kv_dtype"] == "int8"
    pages = a.read_held_pages("hold", 0, 32)
    hashes = [d["hash"] for d in descs]

    b = make_core()
    res = b.import_blocks_direct(a, "hold")
    assert res.imported == len(descs)
    assert b.read_cached_pages(hashes) == pages, (
        "direct-imported pages diverged from the staged bytes"
    )
    a.release_held("hold")


def test_mixed_dtype_transfer_fails_fast():
    """An int8 producer feeding a bf16 consumer (or vice versa) must
    fail with a pointed error — silently casting would re-quantize or
    serve garbage scales."""
    a = make_core()
    prompt = list(range(5, 5 + 40))
    pre = _req(prompt, "hold", max_tokens=2)
    pre.kv_transfer_params = {"do_remote_decode": True}
    run_to_completion(a, [a.add_request(pre)])
    descs = a.export_descriptors("hold")
    pages = a.read_held_pages("hold", 0, 32)
    blocks = [dict(d, kv=kv) for d, kv in zip(descs, pages)]

    bf = EngineCore(CFG, tiny_engine(), seed=1)
    with pytest.raises(ValueError, match="dtype mismatch"):
        bf.import_blocks(blocks)
    with pytest.raises(ValueError, match="dtype mismatch"):
        bf.import_blocks_direct(a, "hold")

    # And the mirror image: bf16 pages into an int8 consumer.
    b2 = EngineCore(CFG, tiny_engine(), seed=2)
    pre2 = _req(prompt, "hold2", max_tokens=2)
    pre2.kv_transfer_params = {"do_remote_decode": True}
    run_to_completion(b2, [b2.add_request(pre2)])
    descs2 = b2.export_descriptors("hold2")
    pages2 = b2.read_held_pages("hold2", 0, 32)
    q = make_core()
    with pytest.raises(ValueError, match="dtype mismatch"):
        q.import_blocks([dict(d, kv=kv) for d, kv in zip(descs2, pages2)])


def test_metrics_report_int8_capacity():
    core = make_core()
    st = core.kv_cache_stats()
    assert st["kv_dtype"] == "int8" and st["kv_dtype_int8"] == 1
    assert st["capacity_blocks"] == core.engine.num_kv_blocks
    bf = EngineCore(CFG, tiny_engine(), seed=0)
    st_bf = bf.kv_cache_stats()
    assert st_bf["kv_dtype_int8"] == 0
    assert st["bytes_per_block"] < st_bf["bytes_per_block"]
