"""Published peak rates of the chips, and the bytes and operations the
algorithm needs, computed from shapes. The yardstick for every roofline
share: kept here so that no PR that claims a gain can change it.

Peaks are copied from ``dynamo_tpu.device.DEVICE_PEAKS`` (sound numbers,
but a yardstick does not live in the program; PERF.md, Open questions).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# Keyed by ``jax.devices()[0].device_kind``. A device that is not here is
# an error, not a default.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e" (per chip)',
    ),
}


class UnknownDevice(ValueError):
    """No published peaks are recorded for this ``device_kind``."""


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks recorded for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add the chip with its source before computing a "
            "roofline share on it.") from None


_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(mf: dict):
    q = mf["num_heads"] * mf["head_dim"]
    kv = mf["num_kv_heads"] * mf["head_dim"]
    return mf["hidden_size"], mf["intermediate_size"], mf["vocab_size"], mf["num_layers"], q, kv


def projection_params(mf: dict) -> int:
    """Weights of one layer's matrix multiplications (qkv, o, gate, up, down)."""
    h, i, _, _, q, kv = _sizes(mf)
    return h * (q + 2 * kv) + q * h + 3 * h * i


def decode_weight_bytes(mf: dict, quant: str | None) -> int:
    """Bytes of weights one decode step must read from HBM whatever the
    batch: every layer's projections, biases and norms, and the output
    matrix (the embedding table itself when tied). The embedding lookup
    reads a row per lane and is left out. int8 weight-only: 1 byte per
    projection weight plus one float32 scale per output channel."""
    h, i, v, L, q, kv = _sizes(mf)
    act = _DTYPE_BYTES[mf.get("dtype", "bfloat16")]
    small = (q + 2 * kv) * act * bool(mf.get("attn_qkv_bias")) + 2 * h * act
    out_channels = (q + 2 * kv) + h + 2 * i + h
    if quant == "int8":
        per_layer = projection_params(mf) + 4 * out_channels + small
        head = h * v * act if mf.get("tie_embeddings") else h * v + 4 * v
    elif quant is None:
        per_layer = projection_params(mf) * act + small
        head = h * v * act
    else:
        raise ValueError(f"unknown quantisation {quant!r}")
    return L * per_layer + head + h * act


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds over all layers."""
    return 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes * mf["num_layers"]


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """Bytes one layer's decode attention must read: the K and V of every
    block in use by the batch's sequences (whole blocks: the kernel moves
    pages), for one query token each."""
    blocks = sum(-(-t // block_size) for t in context_tokens)
    return blocks * block_size * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs: projections, output matrix, and
    attention scores and values against ``context`` tokens."""
    h, _, v, L, q, _ = _sizes(mf)
    return 2 * (L * projection_params(mf) + h * v) + L * 4 * q * context
