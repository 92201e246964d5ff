"""``model_type`` "qwen2": the dense decoder with grouped-query attention
and a bias on q, k, v only (Qwen2.5 technical report; ``modeling_qwen2.py``
of the published checkpoints). Its plain reference is
``chipbench/reference/qwen2.py``; its counts are the dense functions of
``chipbench/peaks.py``.
"""

from __future__ import annotations

from chipbench import peaks
from chipbench.architectures import UNKNOWN, Observed

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
    "attention_bias": "attn_qkv_bias",
    "torch_dtype": "dtype",
}


def derived(cfg: dict) -> dict:
    return {"head_dim": cfg["hidden_size"] // cfg["num_attention_heads"]}


# -- the engine's parameter tree as the reference's float32 pieces ---------

def dequant(w):
    """Engine weight leaf (plain, or int8 {"w", "scale"}) as float32."""
    import jax.numpy as jnp

    if isinstance(w, dict):
        return w["w"].astype(jnp.float32) * w["scale"].astype(jnp.float32)
    return w.astype(jnp.float32)


def require_tp1(params) -> None:
    """The fused projections must be in the tp=1 column order
    (``[q | k | v]``, ``[gate | up]``): the only one read here."""
    if int(params.get("fuse_tp", 1)) != 1:
        raise ValueError("the reference reads the tp=1 fused layout only")


def layer(params, l: int) -> dict:
    """Layer ``l`` of the engine's stacked parameter tree."""
    import jax

    return jax.tree.map(lambda a: a[l], params["layers"])


def attention_weights(lp: dict, mf: dict) -> dict:
    """The attention block of one layer ``lp`` (fused, maybe int8) as the
    unfused float32 pieces ``reference.qwen2.attention`` takes; a model
    without the bias gets zeros."""
    import jax.numpy as jnp

    q_size = mf["num_heads"] * mf["head_dim"]
    kv_size = mf["num_kv_heads"] * mf["head_dim"]
    wqkv = dequant(lp["wqkv"])
    bqkv = (lp["bqkv"].astype(jnp.float32) if "bqkv" in lp
            else jnp.zeros((q_size + 2 * kv_size,), jnp.float32))
    return {
        "attn_norm": lp["attn_norm"].astype(jnp.float32),
        "wq": wqkv[:, :q_size], "wk": wqkv[:, q_size:q_size + kv_size],
        "wv": wqkv[:, q_size + kv_size:],
        "bq": bqkv[:q_size], "bk": bqkv[q_size:q_size + kv_size],
        "bv": bqkv[q_size + kv_size:],
        "wo": dequant(lp["wo"]),
    }


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` as (attention weights, mlp_norm, iterator of MLP column
    blocks). Each piece is de-quantised when it is asked for and dropped
    when the reference has used it."""
    import jax
    import jax.numpy as jnp

    lp = layer(params, l)
    inter = mf["intermediate_size"]
    edges = [inter * i // mlp_blocks for i in range(mlp_blocks + 1)]
    cols = lambda w, a, b: dequant(jax.tree.map(lambda x: x[..., a:b], w))  # noqa: E731

    def blocks():
        for a, b in zip(edges, edges[1:]):
            down = lp["w_down"]
            if isinstance(down, dict):   # scale is per output channel: all rows share it
                w_down = down["w"][a:b].astype(jnp.float32) * down["scale"]
            else:
                w_down = down[a:b].astype(jnp.float32)
            yield cols(lp["wgu"], a, b), cols(lp["wgu"], inter + a, inter + b), w_down

    return attention_weights(lp, mf), lp["mlp_norm"].astype(jnp.float32), blocks()


def lm_head_chunks(params, mf: dict, chunks: int):
    """``[h, v_chunk]`` float32 slices of the output matrix (the embedding
    table's rows, transposed, when tied)."""
    import jax
    import jax.numpy as jnp

    v = mf["vocab_size"]
    edges = [v * i // chunks for i in range(chunks + 1)]
    for a, b in zip(edges, edges[1:]):
        if mf.get("tie_embeddings"):
            yield params["embed"][a:b].astype(jnp.float32).T
        else:
            yield dequant(jax.tree.map(lambda x: x[..., a:b], params["lm_head"]))


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``."""
    import jax.numpy as jnp

    from chipbench.reference import qwen2

    require_tp1(params)
    return qwen2.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32), lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], n_kv=mf["num_kv_heads"],
        head_dim=mf["head_dim"], theta=mf["rope_theta"],
        eps=mf["rms_norm_eps"], rows=rows,
    )


# -- counts from shapes: a dense model's do not depend on the traffic ------

def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    return peaks.decode_weight_bytes(mf, quant)


kv_bytes_per_token = peaks.kv_bytes_per_token
attn_decode_bytes_per_layer = peaks.attn_decode_bytes_per_layer
forward_flops_per_token = peaks.forward_flops_per_token
