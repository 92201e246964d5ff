"""Bytes and operations from shapes, against hand counts; the peaks table."""

import pytest

from chipbench import peaks
from chipbench.configs import load_config, model_fields


def test_peaks_table_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s, p.hbm_bytes) == (
        197e12, 393e12, 819e9, 16e9)
    assert "v5e" in p.source
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_7b_int8_weight_stream_by_hand():
    mf = model_fields(load_config("qwen2.5-7b-int8"))
    h, i, v, q, kv = 3584, 18944, 152064, 3584, 512
    per_layer = h * (q + 2 * kv) + q * h + 3 * h * i
    assert peaks.projection_params(mf) == per_layer == 233_046_016
    scales = 4 * ((q + 2 * kv) + h + 2 * i + h)
    small = (q + 2 * kv) * 2 + 2 * h * 2
    want = 28 * (per_layer + scales + small) + (h * v + 4 * v) + h * 2
    assert peaks.decode_weight_bytes(mf, "int8") == want
    # 7.08 GB: a 8.64 ms floor at 819 GB/s. (The engine's 8.17 GB of
    # parameters also holds the 1.09 GB embedding table, of which a step
    # reads one row per lane.)
    assert want / 819e9 * 1e3 == pytest.approx(8.64, abs=0.02)


def test_1p5b_bf16_weight_stream_by_hand():
    mf = model_fields(load_config("qwen2.5-1.5b-bf16"))
    h, i, v, q, kv = 1536, 8960, 151936, 1536, 256
    per_layer = (h * (q + 2 * kv) + q * h + 3 * h * i) * 2 + (q + 2 * kv) * 2 + 4 * h
    want = 28 * per_layer + h * v * 2 + h * 2
    assert peaks.decode_weight_bytes(mf, None) == want
    assert want / 819e9 * 1e3 == pytest.approx(3.77, abs=0.02)
    with pytest.raises(ValueError):
        peaks.decode_weight_bytes(mf, "fp8")


@pytest.mark.parametrize("name,want", [("qwen2.5-7b-int8", 57344), ("qwen2.5-1.5b-bf16", 28672)])
def test_kv_bytes_per_token(name, want):
    assert peaks.kv_bytes_per_token(model_fields(load_config(name))) == want


def test_attention_bytes_count_whole_blocks():
    mf = model_fields(load_config("qwen2.5-7b-int8"))
    # 33 tokens hold 2 blocks of 32; one layer: 2 (K,V) x 4 heads x 128 x 2 B per token
    assert peaks.attn_decode_bytes_per_layer([33], mf, 32) == 64 * 2 * 4 * 128 * 2
    assert peaks.attn_decode_bytes_per_layer([32, 1], mf, 32) == 64 * 2048


def test_forward_flops_per_token():
    mf = model_fields(load_config("qwen2.5-1.5b-bf16"))
    base = 2 * (28 * peaks.projection_params(mf) + 1536 * 151936)
    assert peaks.forward_flops_per_token(mf) == base
    assert peaks.forward_flops_per_token(mf, 1000) == base + 28 * 4 * 1536 * 1000
