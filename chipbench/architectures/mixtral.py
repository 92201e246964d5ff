"""``model_type`` "mixtral": the dense decoder's attention without a bias,
and a sparse MLP of ``num_local_experts`` SwiGLUs of which a router
chooses ``num_experts_per_tok`` per token. Its plain reference is
``chipbench/reference/mixtral.py``.

A rehearsal architecture so far: ``configs/tiny-moe-rehearsal.json`` runs
on the CPU under ``tests/chipbench``; no cell of ``BENCHMARK.json`` is of
it, and nothing here has been read on a chip.
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
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
}

derived = qwen2.derived


def published_layout(params, l: int, mf: dict):
    """Layer ``l`` of the engine's parameter tree (``w_router [h, E]``,
    ``w_gate``, ``w_up`` ``[E, h, i]``, ``w_down [E, i, h]``; attention
    fused as in the dense decoder) as (attention weights, mlp_norm,
    w_router, iterator of the experts' float32 pieces)."""
    import jax.numpy as jnp

    lp = qwen2.layer(params, l)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — experts are served unquantised
    experts = ((f32(lp["w_gate"][e]), f32(lp["w_up"][e]), f32(lp["w_down"][e]))
               for e in range(mf["num_experts"]))
    return (qwen2.attention_weights(lp, mf), f32(lp["mlp_norm"]), f32(lp["w_router"]), experts)


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``."""
    import jax.numpy as jnp

    from chipbench.reference import mixtral

    qwen2.require_tp1(params)
    return mixtral.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], n_kv=mf["num_kv_heads"],
        head_dim=mf["head_dim"], theta=mf["rope_theta"],
        eps=mf["rms_norm_eps"], top_k=mf["num_experts_per_tok"], rows=rows,
    )


# -- counts from shapes and, for the experts a step reads, the traffic -----

def _expert_params(mf: dict) -> int:
    return 3 * mf["hidden_size"] * mf["intermediate_size"]


def experts_read_per_step(mf: dict, observed: Observed = UNKNOWN) -> float:
    """Experts of one layer whose weights a decode step reads. With ``B``
    live lanes, each choosing k of E, E (1 - (1 - k/E)^B) distinct experts
    are expected if the router spreads tokens evenly; where the lanes are
    not known, the k that a single lane reads: the least any step can.
    (What a step did read is a counter the program does not keep yet.)"""
    E, k = mf["num_experts"], mf["num_experts_per_tok"]
    lanes = observed.decode_lanes_mean
    return k if lanes is None else max(k, E * (1.0 - (1.0 - k / E) ** lanes))


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> float:
    """The dense count (attention, norms, output matrix) with each layer's
    one MLP replaced by the router and the experts the step reads."""
    if quant is not None:
        raise ValueError(f"experts are served unquantised; no count for quant {quant!r}")
    act = peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]
    per_layer = (mf["hidden_size"] * mf["num_experts"]
                 + (experts_read_per_step(mf, observed) - 1) * _expert_params(mf))
    return peaks.decode_weight_bytes(mf, None) + mf["num_layers"] * per_layer * act


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """A token runs the router and its k experts, whatever the batch."""
    per_layer = (mf["hidden_size"] * mf["num_experts"]
                 + (mf["num_experts_per_tok"] - 1) * _expert_params(mf))
    return peaks.forward_flops_per_token(mf, context) + 2 * mf["num_layers"] * per_layer


kv_bytes_per_token = peaks.kv_bytes_per_token
attn_decode_bytes_per_layer = peaks.attn_decode_bytes_per_layer
