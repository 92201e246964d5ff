"""``model_type`` "lfm2_moe": LiquidAI's LFM2-24B-A2B (published
``config.json``; the family's ``Lfm2Moe*`` modelling code). Layers of TWO
kinds by ``layer_types``: a gated short convolution (``conv_L_cache`` taps,
depthwise, causal), which keeps ``(L - 1) x h`` values of state a sequence
whatever the context, and grouped-query attention with an RMSNorm over each
head of q and k before rope, heads of width ``hidden_size /
num_attention_heads`` (64). ``num_dense_layers`` dense SwiGLU layers, then
layers of ``num_experts`` experts of width ``moe_intermediate_size`` scored
by a sigmoid, ``num_experts_per_tok`` chosen by score PLUS a learned bias
(``use_expert_bias``), weighted by their plain scores, normalised. No
groups, no shared expert. Its plain reference is
``chipbench/reference/lfm2_moe.py``.

Counts: a decode step of the program runs EVERY expert on every row
(``dynamo_tpu/engine/model.py``, ``_experts_all_rows``), so it reads all
``num_experts`` whatever the router favours, and :func:`decode_weight_bytes`
counts them all; ``tests/chipbench/test_chipbench_lfm2.py`` pins the count
to the leaves the program's decode step reads. Only attention layers cache
K/V: :func:`kv_bytes_per_token` counts those, and
:func:`attn_decode_bytes_per_layer` ONE attention layer's rows, at the
published 2 x n_kv x 64 values a token (the program keeps two heads a
128-wide row, the same bytes: ``ModelConfig.kv_head_pairs``).
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
    "norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
    "layer_types": "layer_types",
    "conv_L_cache": "conv_L_cache",
    "conv_bias": "conv_bias",
    "num_dense_layers": "first_dense_layers",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "use_expert_bias": "router_bias",
}

# what sum(sc_chosen) is raised by before the division (the family's code;
# not in config.json)
ROUTER_NORM_EPS = 1e-6


def derived(cfg: dict) -> dict:
    """Head width, rope's base (inside ``rope_parameters``), the router's
    scoring and QK-norm are the model type's. Published keys this file
    reads no equation from must hold the one value the equations assume."""
    rope = cfg.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"lfm2_moe: rope_type={rope['rope_type']!r} is not modelled")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("lfm2_moe: layer_types must name each of num_hidden_layers")
    return {
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "rope_theta": float(rope.get("rope_theta", 1000000.0)),
        "router_scoring": "sigmoid",
        "router_norm_eps": ROUTER_NORM_EPS,
        "qk_norm": True,
    }


# -- the engine's parameter tree as the reference's float32 pieces ---------

def _kind_index(mf: dict, l: int) -> tuple[str, int]:
    """(published kind of layer ``l``, its index among its kind)."""
    kinds = mf["layer_types"]
    return kinds[l], sum(k == kinds[l] for k in kinds[:l])


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` of the engine's tree (``layers``: the two norms of every
    layer; ``conv`` / ``attn``: the operators, one entry a layer of that
    kind; ``dense_mlp``: the leading layers' SwiGLU; ``moe``: the others'
    router, bias and experts ``w_gu [E, h, 2 im]`` / ``w_down``) as ``(kind,
    operator weights, ffn_norm, mlp)`` for ``reference.lfm2_moe.forward``."""
    import jax.numpy as jnp

    from chipbench.architectures.axk1 import _column_blocks

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — served unquantised
    kind, at = _kind_index(mf, l)
    norms = qwen2.layer(params, l)
    if kind == "conv":
        w_op = {k: f32(v[at]) for k, v in params["conv"].items()}
    else:
        lp = {k: v[at] for k, v in params["attn"].items()}
        q_size = mf["num_heads"] * mf["head_dim"]
        kv_size = mf["num_kv_heads"] * mf["head_dim"]
        wqkv = f32(lp["wqkv"])
        w_op = {"wq": wqkv[:, :q_size], "wk": wqkv[:, q_size:q_size + kv_size],
                "wv": wqkv[:, q_size + kv_size:], "wo": f32(lp["wo"]),
                "q_layernorm": f32(lp["q_layernorm"]), "k_layernorm": f32(lp["k_layernorm"])}
    w_op["operator_norm"] = f32(norms["attn_norm"])
    dense_layers = mf.get("first_dense_layers", 0)
    if l < dense_layers:
        d = {k: v[l] for k, v in params["dense_mlp"].items()}
        mlp = ("dense", _column_blocks(d["wgu"], d["w_down"], mf["intermediate_size"],
                                       mlp_blocks))
        return kind, w_op, f32(norms["mlp_norm"]), mlp
    m = {k: v[l - dense_layers] for k, v in params["moe"].items()}
    im = mf["moe_intermediate_size"]

    def experts():
        for e in range(mf["num_experts"]):
            yield (e, f32(m["w_gu"][e, :, :im]), f32(m["w_gu"][e, :, im:]), f32(m["w_down"][e]))

    return kind, w_op, f32(norms["mlp_norm"]), (
        "sparse", f32(m["w_router"]), f32(m["expert_bias"]), experts())


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``."""
    import jax.numpy as jnp

    from chipbench.reference import lfm2_moe

    qwen2.require_tp1(params)
    return lfm2_moe.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], n_kv=mf["num_kv_heads"], head_dim=mf["head_dim"],
        theta=mf["rope_theta"], eps=mf["rms_norm_eps"],
        top_k=mf["num_experts_per_tok"], scale=mf["routed_scaling_factor"],
        norm_eps=mf["router_norm_eps"], rows=rows,
    )


# -- counts from shapes ------------------------------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def _layers(mf: dict, kind: str) -> int:
    return sum(k == kind for k in mf["layer_types"])


def conv_params(mf: dict) -> int:
    """One conv operator: in_proj [h, 3h], the taps [L, h], out_proj [h, h]."""
    h = mf["hidden_size"]
    return 3 * h * h + mf["conv_L_cache"] * h + h * h


def attention_params(mf: dict) -> int:
    """One attention operator's matrices and its two head norms."""
    h, d = mf["hidden_size"], mf["head_dim"]
    q, kv = mf["num_heads"] * d, mf["num_kv_heads"] * d
    return h * (q + 2 * kv) + q * h + 2 * d


def expert_params(mf: dict) -> int:
    return 3 * mf["hidden_size"] * mf["moe_intermediate_size"]


def experts_read_per_step(mf: dict, observed: Observed = UNKNOWN) -> int:
    """Experts of one sparse layer whose weights a decode step reads: ALL
    of them, whatever the batch routes (the program's decode path runs
    every expert on every row). ``observed`` has no say."""
    return mf["num_experts"]


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every layer's
    operator and its two norms, a dense layer's SwiGLU, a sparse layer's
    router, bias (float32) and the experts it reads, the final norm and
    the output matrix (the tied embedding table, read whole as the head).
    The embedding LOOKUP reads a row a lane and is left out."""
    if quant is not None:
        raise ValueError(f"lfm2_moe is served unquantised; no count for quant {quant!r}")
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf.get("first_dense_layers", 0)
    sparse = h * mf["num_experts"] + experts_read_per_step(mf, observed) * expert_params(mf)
    params = (_layers(mf, "conv") * conv_params(mf)
              + _layers(mf, "full_attention") * attention_params(mf) + L * 2 * h
              + Ld * 3 * h * mf["intermediate_size"] + (L - Ld) * sparse
              + h + h * mf["vocab_size"])
    bias = (L - Ld) * mf["num_experts"] * 4 if mf.get("router_bias") else 0
    return params * _act(mf) + bias


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the ATTENTION layers' only."""
    return (_layers(mf, "full_attention") * 2 * mf["num_kv_heads"] * mf["head_dim"]
            * kv_bytes)


def state_bytes_per_sequence(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of convolution state one sequence needs, whatever its context."""
    return _layers(mf, "conv") * (mf["conv_L_cache"] - 1) * mf["hidden_size"] * kv_bytes


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """Bytes ONE attention layer's decode call must read: K and V of every
    block in use by the batch's sequences at the published head width
    (whole blocks: pages are what moves). The paired layout holds the same
    bytes; a head padded to 128 would hold twice them."""
    blocks = sum(-(-t // block_size) for t in context_tokens)
    return blocks * block_size * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs: the operators, a dense layer's
    SwiGLU, a sparse layer's router and its ``k`` chosen experts, the output
    matrix, and attention against ``context`` tokens in the attention
    layers."""
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf.get("first_dense_layers", 0)
    sparse = h * mf["num_experts"] + mf["num_experts_per_tok"] * expert_params(mf)
    matmuls = (_layers(mf, "conv") * conv_params(mf)
               + _layers(mf, "full_attention") * attention_params(mf)
               + Ld * 3 * h * mf["intermediate_size"] + (L - Ld) * sparse
               + h * mf["vocab_size"])
    attn = _layers(mf, "full_attention") * 4 * mf["num_heads"] * mf["head_dim"] * context
    return int(2 * matmuls + attn)
