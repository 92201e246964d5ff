"""``model_type`` "ouro": a looped decoder (ByteDance Ouro; "Scaling Latent
Reasoning via Looped Language Models", 2025-10). ``num_hidden_layers``
plain multi-head layers with sandwich norms (four norm weights a layer)
run ``total_ut_steps`` times a token, the final norm after every pass;
each (pass, layer) keeps K and V of its own. Its plain reference is
``chipbench/reference/ouro.py``.

Its counts are the dense functions of ``chipbench/peaks.py`` with the
layers' part taken once per pass: a decode step streams the layers
``ut_steps`` times (they do not stay on the chip between passes), a token
holds ``num_layers x ut_steps`` planes of K/V, and the attention kernel is
called once per pass and layer.
"""

from __future__ import annotations

from chipbench import peaks
from chipbench.architectures import UNKNOWN, Observed, qwen2

# published key -> ModelConfig field
KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
    "total_ut_steps": "ut_steps",
    "early_exit_threshold": "early_exit_threshold",
}


def derived(cfg: dict) -> dict:
    """No published key says so: sandwich norms and no bias on q/k/v/o
    are part of the model type (``modeling_ouro.py``)."""
    return {**qwen2.derived(cfg), "sandwich_norm": True, "attn_qkv_bias": False}


# -- the engine's parameter tree as the reference's float32 pieces ---------

def published_layout(params, l: int, mf: dict):
    """Layer ``l`` as (attention weights with ``attn_norm`` and
    ``attn_post_norm``, mlp_norm, mlp_post_norm, iterator of MLP column
    blocks), de-quantised piece by piece as ``qwen2.published_layout``."""
    import jax.numpy as jnp

    w_attn, mlp_norm, blocks = qwen2.published_layout(params, l, mf)
    lp = qwen2.layer(params, l)
    w_attn["attn_post_norm"] = lp["attn_post_norm"].astype(jnp.float32)
    return w_attn, mlp_norm, lp["mlp_post_norm"].astype(jnp.float32), blocks


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, every_pass: bool = False):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``; with
    ``every_pass`` (logits [ut_steps, len(rows), vocab], gate probabilities
    [ut_steps, len(rows)]), to see in which pass a difference grows."""
    import jax.numpy as jnp

    from chipbench.reference import ouro

    qwen2.require_tp1(params)
    gate = params.get("exit_gate")
    return ouro.forward(
        ids, params["embed"],
        lambda: (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        ut_steps=mf["ut_steps"],
        n_heads=mf["num_heads"], head_dim=mf["head_dim"], theta=mf["rope_theta"],
        eps=mf["rms_norm_eps"], rows=rows, every_pass=every_pass,
        gate=None if gate is None else (gate["w"].astype(jnp.float32),
                                        gate["b"].astype(jnp.float32)),
    )


# -- counts from shapes: the layers' part once per pass --------------------

def _act_bytes(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every pass
    streams every layer's projections and its four norms and reads the
    final norm; the output matrix is read once. (The exit gate is not
    evaluated at threshold 1.)"""
    h, act, ut = mf["hidden_size"], _act_bytes(mf), mf["ut_steps"]
    outside = peaks.decode_weight_bytes({**mf, "num_layers": 0}, quant)   # head + final norm
    layers = peaks.decode_weight_bytes(mf, quant) - outside               # two norms a layer
    layers += mf["num_layers"] * 2 * h * act                              # the two output norms
    return ut * layers + (outside - h * act) + ut * h * act


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds over all planes (pass x layer)."""
    return mf["ut_steps"] * peaks.kv_bytes_per_token(mf, kv_bytes)


# one kernel call per pass and layer, each on its own plane: a call reads
# what a dense layer's call reads
attn_decode_bytes_per_layer = peaks.attn_decode_bytes_per_layer


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """The dense count with the layers' part (projections and attention
    against ``context`` tokens) once per pass."""
    head = peaks.forward_flops_per_token({**mf, "num_layers": 0}, context)
    return mf["ut_steps"] * (peaks.forward_flops_per_token(mf, context) - head) + head
