"""``model_type`` "axk1": SKT's A.X-K1 (published ``config.json``; the
DeepSeek-V3 family's layer). Multi-head LATENT attention: a low-rank q with
its norm, one compressed K/V vector a token (``kv_lora_rank``, with its
norm) beside a rope key shared by all heads, YaRN frequencies; the cached
unit is ``[ckv | kr]``, ``kv_lora_rank + qk_rope_head_dim`` values a token a
layer. ``first_k_dense_replace`` dense SwiGLU layers, then layers of
``n_routed_experts`` experts of width ``moe_intermediate_size`` scored by a
sigmoid, chosen ``num_experts_per_tok`` a token inside the ``topk_group``
best of ``n_group`` groups, weights normalised and scaled by
``routed_scaling_factor``, beside ``n_shared_experts`` shared ones. Its plain
reference is ``chipbench/reference/axk1.py``.

**A chip's share.** A configuration of it states ``experts_held``:
``{"rank", "of", "published"}``. ``n_routed_experts`` in the file is what
THIS chip holds (``published / of``, listed in ``reduced``); the router keeps
the published width, and program and reference add only the held experts'
terms (``chipbench/reference/axk1.py``, "The share").

Counts: a decode step of the program runs every held expert on every row
(``dynamo_tpu/engine/model.py``, ``_EXPERTS_ALL_ROWS_MAX``), so it reads ALL
held experts whatever the router favours, and :func:`decode_weight_bytes`
counts them all; ``tests/chipbench/test_chipbench_axk1.py`` pins the count
to the leaves the program's decode step reads.
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
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attn_qkv_bias",
    "torch_dtype": "dtype",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "rope_scaling": "rope_scaling",
    "first_k_dense_replace": "first_dense_layers",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts_per_tok": "num_experts_per_tok",
    "scoring_func": "router_scoring",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_shared_experts": "num_shared_experts",
}


def derived(cfg: dict) -> dict:
    """The attention kind and the q/k head's whole width are the model
    type's; the router's width and this chip's share come from
    ``experts_held`` beside ``n_routed_experts`` (what is held). Published
    keys this file reads no equation from must hold the one value the
    equations assume."""
    assumed = {"topk_method": "none", "moe_layer_freq": 1, "hidden_act": "silu",
               "scoring_func": "sigmoid"}
    for key, want in assumed.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"axk1: {key}={cfg[key]!r} is not modelled (only {want!r})")
    held = cfg.get("experts_held") or {
        "rank": 0, "of": 1, "published": cfg["n_routed_experts"]}
    if held["published"] != held["of"] * cfg["n_routed_experts"]:
        raise ValueError(
            f"axk1: n_routed_experts={cfg['n_routed_experts']} held by each of "
            f"{held['of']} chips is not the published {held['published']}")
    return {
        "attention": "mla",
        "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        "num_experts": held["published"],
        "experts_held": (held["rank"], held["of"]),
    }


# -- the engine's parameter tree as the reference's float32 pieces ---------

_ATTN = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wo")


def _held(mf: dict) -> tuple[int, int]:
    rank, of = mf.get("experts_held") or (0, 1)
    n = mf["num_experts"] // of
    return rank * n, (rank + 1) * n


def _column_blocks(wgu, w_down, width: int, blocks: int):
    """``[gate | up]`` of total width ``2 x width`` and its ``w_down`` as
    float32 (w_gate, w_up, w_down) column blocks."""
    import jax.numpy as jnp

    edges = [width * i // blocks for i in range(blocks + 1)]
    for a, b in zip(edges, edges[1:]):
        yield (wgu[:, a:b].astype(jnp.float32), wgu[:, width + a:width + b].astype(jnp.float32),
               w_down[a:b].astype(jnp.float32))


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` of the engine's tree (``layers``: attention and norms of
    every layer; ``dense_mlp``: the leading layers' SwiGLU; ``moe``: the
    others' router, held experts ``w_gu [Eh, h, 2 im]`` / ``w_down`` and
    shared SwiGLU) as ``(attention weights, mlp_norm, mlp)`` for
    ``reference.axk1.forward``, each piece float32 when it is asked for."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — served unquantised
    lp = qwen2.layer(params, l)
    w_attn = {k: f32(lp[k]) for k in _ATTN}
    # the engine keeps kv_b_proj in two parts, as its absorbed decode contracts
    # them: wk_b [H, dn, rkv] and wv_b [H, rkv, dv]; the published [rkv, H (dn + dv)]
    w_attn["wkv_b"] = jnp.concatenate(
        [jnp.transpose(f32(lp["wk_b"]), (2, 0, 1)), jnp.transpose(f32(lp["wv_b"]), (1, 0, 2))],
        axis=-1).reshape(mf["kv_lora_rank"], -1)
    dense_layers = mf.get("first_dense_layers", 0)
    if l < dense_layers:
        d = {k: v[l] for k, v in params["dense_mlp"].items()}
        mlp = ("dense", _column_blocks(d["wgu"], d["w_down"], mf["intermediate_size"],
                                       mlp_blocks))
        return w_attn, f32(lp["mlp_norm"]), mlp
    # the held experts' leaves are one array a sparse layer, the others stacked
    m = {k: v[l - dense_layers] for k, v in params["moe"].items()}
    im = mf["moe_intermediate_size"]
    lo, hi = _held(mf)

    def experts():
        for j, e in enumerate(range(lo, hi)):
            yield (e, f32(m["w_gu"][j, :, :im]), f32(m["w_gu"][j, :, im:]), f32(m["w_down"][j]))

    shared = (_column_blocks(m["shared_wgu"], m["shared_down"],
                             mf["num_shared_experts"] * im, 1)
              if "shared_wgu" in m else iter(()))
    return w_attn, f32(lp["mlp_norm"]), ("sparse", f32(m["w_router"]), experts(), shared)


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, held: tuple[int, int] | None = None):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``, given
    the same share of the experts as the engine (``held`` narrows it, for
    the test that adds the shares up)."""
    import jax.numpy as jnp

    from chipbench.reference import axk1

    qwen2.require_tp1(params)
    return axk1.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], dn=mf["qk_nope_head_dim"], dr=mf["qk_rope_head_dim"],
        dv=mf["v_head_dim"], rkv=mf["kv_lora_rank"], theta=mf["rope_theta"],
        scaling=dict(mf.get("rope_scaling") or {}), eps=mf["rms_norm_eps"],
        n_group=mf["n_group"], topk_group=mf["topk_group"],
        top_k=mf["num_experts_per_tok"], scale=mf["routed_scaling_factor"],
        held=held or _held(mf), rows=rows,
    )


# -- counts from shapes ------------------------------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def attention_params(mf: dict) -> int:
    """One layer's attention matrices and the two norms inside them."""
    h, H = mf["hidden_size"], mf["num_heads"]
    dn, dr, dv = mf["qk_nope_head_dim"], mf["qk_rope_head_dim"], mf["v_head_dim"]
    rq, rkv = mf["q_lora_rank"], mf["kv_lora_rank"]
    return (h * rq + rq + rq * H * (dn + dr) + h * (rkv + dr) + rkv
            + rkv * H * (dn + dv) + H * dv * h)


def expert_params(mf: dict) -> int:
    return 3 * mf["hidden_size"] * mf["moe_intermediate_size"]


def experts_read_per_step(mf: dict, observed: Observed = UNKNOWN) -> int:
    """Routed experts of one layer whose weights a decode step reads: all
    that are held, whatever the batch routes (the program's decode path
    runs every held expert on every row). ``observed`` has no say."""
    lo, hi = _held(mf)
    return hi - lo


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every layer's
    attention (with the absorbed ``Wkvb``) and its two norms, a dense
    layer's SwiGLU, a sparse layer's router, the experts it reads and the
    shared experts, the final norm and the output matrix. The embedding
    lookup reads a row per lane and is left out."""
    if quant is not None:
        raise ValueError(f"axk1 is served unquantised; no count for quant {quant!r}")
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf.get("first_dense_layers", 0)
    sparse = (h * mf["num_experts"]
              + (experts_read_per_step(mf, observed) + mf["num_shared_experts"])
              * expert_params(mf))
    params = (L * (attention_params(mf) + 2 * h) + Ld * 3 * h * mf["intermediate_size"]
              + (L - Ld) * sparse + h + h * mf["vocab_size"])
    return params * _act(mf)


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of ``[ckv | kr]`` one token holds over all layers."""
    return mf["num_layers"] * (mf["kv_lora_rank"] + mf["qk_rope_head_dim"]) * kv_bytes


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """Bytes one layer's absorbed decode attention must read: the latent
    rows of every block in use by the batch's sequences, once for all
    heads (whole blocks: pages are what moves). Bandwidth-bound: 2 H (2 rkv
    + dr) FLOP over (rkv + dr) x 2 B, 121 FLOP a byte against the v5e's 240."""
    blocks = sum(-(-t // block_size) for t in context_tokens)
    return blocks * block_size * (mf["kv_lora_rank"] + mf["qk_rope_head_dim"]) * kv_bytes


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs ON THIS CHIP: attention
    projections, a dense layer's SwiGLU, a sparse layer's router, shared
    experts and the ``k x held / E`` routed experts an even router sends
    it here, the output matrix, and absorbed attention against ``context``
    tokens."""
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf.get("first_dense_layers", 0)
    lo, hi = _held(mf)
    routed = mf["num_experts_per_tok"] * (hi - lo) / mf["num_experts"]
    sparse = h * mf["num_experts"] + (routed + mf["num_shared_experts"]) * expert_params(mf)
    matmuls = (L * attention_params(mf) + Ld * 3 * h * mf["intermediate_size"]
               + (L - Ld) * sparse + h * mf["vocab_size"])
    attn = L * 2 * mf["num_heads"] * (2 * mf["kv_lora_rank"] + mf["qk_rope_head_dim"]) * context
    return int(2 * matmuls + attn)
