"""``model_type`` "laguna": poolside's Laguna-S-2.1 (published
``config.json``). Attention layers of TWO kinds by ``layer_types``:
``full_attention`` (one in four) and ``sliding_attention``, whose query sees
``sliding_window`` keys; the kinds differ in their QUERY heads
(``num_attention_heads_per_layer``, 48 / 72 over 8 KV heads of 128) and in
their rope (``rope_parameters`` by kind: YaRN over half of each head on a
full layer, plain rope over the whole head on a window layer); every head's
output passes a sigmoid gate (``gating`` "per-head"). ``mlp_only_layers``
keep a dense SwiGLU; the others have ``num_experts`` experts of width
``moe_intermediate_size`` scored by a sigmoid, ``num_experts_per_tok``
chosen, weights normalised and scaled by ``moe_routed_scaling_factor``,
beside one shared expert. Its plain reference is
``chipbench/reference/laguna.py``.

**A chip's share.** A configuration of it states ``experts_held``:
``{"rank", "of", "published"}``. ``num_experts`` in the file is what THIS
chip holds (``published / of``, listed in ``reduced``); the router keeps the
published width, and program and reference add only the held experts' terms.

Counts. A decode step of the program runs every held expert on every row
(``dynamo_tpu/ops/expert_stream.py``), so :func:`decode_weight_bytes` counts
them all; ``tests/chipbench/test_chipbench_laguna.py`` pins the count to the
leaves the program's decode step reads. A token holds K/V for as long as its
sequence lives in the FULL layers only: :func:`kv_bytes_per_token` counts
those; a window layer holds ``sliding_window`` tokens' blocks a sequence
whatever the context (:func:`window_bytes_per_sequence`).
:func:`attn_decode_bytes_per_layer` is the MEAN over a step's attention
calls, of both kinds, of the bytes a call must read, so that a reader that
divides the calls' seconds by the calls it finds needs no say in the kinds.
"""

from __future__ import annotations

from chipbench import peaks
from chipbench.architectures import UNKNOWN, Observed, qwen2
from chipbench.architectures.axk1 import _column_blocks, _held

# published key -> ModelConfig field
KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attn_qkv_bias",
    "torch_dtype": "dtype",
    "layer_types": "layer_types",
    "sliding_window": "sliding_window",
    "num_attention_heads_per_layer": "heads_per_layer",
    "rope_parameters": "rope_by_kind",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "moe_routed_scaling_factor": "routed_scaling_factor",
}


def derived(cfg: dict) -> dict:
    """The router's scoring and the gate are the model type's; the leading
    dense layers come from ``mlp_layer_types``; the router's width and this
    chip's share from ``experts_held`` beside ``num_experts`` (what is
    held). Published keys this file reads no equation from must hold the one
    value the equations assume."""
    L = cfg["num_hidden_layers"]
    assumed = {"gating": "per-head", "decoder_sparse_step": 1,
               "moe_apply_router_weight_on_input": False,
               "moe_router_logit_softcapping": 0, "hidden_act": "silu",
               "shared_expert_intermediate_size": cfg["moe_intermediate_size"]}
    for key, want in assumed.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"laguna: {key}={cfg[key]!r} is not modelled (only {want!r})")
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        if len(cfg[key]) != L:
            raise ValueError(f"laguna: {key} must name each of num_hidden_layers={L}")
    if set(cfg.get("gating_types", ["per_head"])) != {"per_head"}:
        raise ValueError("laguna: gating_types other than per_head are not modelled")
    kinds = cfg["mlp_layer_types"]
    dense = kinds.index("sparse") if "sparse" in kinds else L
    if any(k != "sparse" for k in kinds[dense:]) or \
            list(cfg.get("mlp_only_layers", range(dense))) != list(range(dense)):
        raise ValueError("laguna: dense MLP layers after a sparse one are not modelled")
    held = cfg.get("experts_held") or {"rank": 0, "of": 1, "published": cfg["num_experts"]}
    if held["published"] != held["of"] * cfg["num_experts"]:
        raise ValueError(
            f"laguna: num_experts={cfg['num_experts']} held by each of "
            f"{held['of']} chips is not the published {held['published']}")
    return {
        "router_scoring": "sigmoid",
        "attn_gate": True,
        "first_dense_layers": dense,
        "num_shared_experts": 1,
        "num_experts": held["published"],
        "experts_held": (held["rank"], held["of"]),
    }


# -- the engine's parameter tree as the reference's float32 pieces ---------

_GROUP = {"full_attention": "attn", "sliding_attention": "attn_window"}


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` of the engine's tree (``layers``: the two norms of every
    layer; ``attn`` / ``attn_window``: the attention leaves, one entry a
    layer of that kind, ``wqkv`` fused ``[q | k | v]`` for the kind's query
    heads; ``dense_mlp`` / ``moe`` as A.X-K1's) as ``(kind, attention
    weights, mlp_norm, mlp)`` for ``reference.laguna.forward``."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — served unquantised
    kinds = mf["layer_types"]
    at = sum(k == kinds[l] for k in kinds[:l])
    lp = {k: v[at] for k, v in params[_GROUP[kinds[l]]].items()}
    norms = qwen2.layer(params, l)
    kv_size = mf["num_kv_heads"] * mf["head_dim"]
    q_size = mf["heads_per_layer"][l] * mf["head_dim"]
    wqkv = f32(lp["wqkv"])
    w_attn = {"attn_norm": f32(norms["attn_norm"]), "wq": wqkv[:, :q_size],
              "wk": wqkv[:, q_size:q_size + kv_size], "wv": wqkv[:, q_size + kv_size:],
              "wg": f32(lp["wg"]), "wo": f32(lp["wo"])}
    dense_layers = mf["first_dense_layers"]
    if l < dense_layers:
        d = {k: v[l] for k, v in params["dense_mlp"].items()}
        mlp = ("dense", _column_blocks(d["wgu"], d["w_down"], mf["intermediate_size"],
                                       mlp_blocks))
        return kinds[l], w_attn, f32(norms["mlp_norm"]), mlp
    m = {k: v[l - dense_layers] for k, v in params["moe"].items()}
    im = mf["moe_intermediate_size"]
    lo, hi = _held(mf)

    def experts():
        for j, e in enumerate(range(lo, hi)):
            yield (e, f32(m["w_gu"][j, :, :im]), f32(m["w_gu"][j, :, im:]), f32(m["w_down"][j]))

    shared = _column_blocks(m["shared_wgu"], m["shared_down"], im, 1)
    return kinds[l], w_attn, f32(norms["mlp_norm"]), (
        "sparse", f32(m["w_router"]), experts(), shared)


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, held: tuple[int, int] | None = None,
                     shared: bool = True, faults: tuple[str, ...] = ()):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``, given
    the same share of the experts as the engine (``held`` narrows it and
    ``shared`` leaves the shared expert out, for the test that adds the
    shares up; ``faults`` leaves a mechanism out, for the comparisons that
    must fail)."""
    import jax.numpy as jnp

    from chipbench.reference import laguna

    qwen2.require_tp1(params)
    return laguna.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_kv=mf["num_kv_heads"], head_dim=mf["head_dim"],
        rope_by_kind={k: dict(v) for k, v in dict(mf["rope_by_kind"]).items()},
        window=mf["sliding_window"], eps=mf["rms_norm_eps"],
        top_k=mf["num_experts_per_tok"], scale=mf["routed_scaling_factor"],
        held=held or _held(mf), rows=rows, shared=shared, faults=faults,
    )


# -- counts from shapes ------------------------------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def _layers(mf: dict, kind: str) -> list[int]:
    return [l for l, k in enumerate(mf["layer_types"]) if k == kind]


def attention_params(mf: dict, l: int) -> int:
    """Layer ``l``'s attention matrices and its gate's."""
    h, d, n = mf["hidden_size"], mf["head_dim"], mf["heads_per_layer"][l]
    return h * (n + 2 * mf["num_kv_heads"]) * d + n * d * h + h * n


def expert_params(mf: dict) -> int:
    return 3 * mf["hidden_size"] * mf["moe_intermediate_size"]


def experts_read_per_step(mf: dict, observed: Observed = UNKNOWN) -> int:
    """Routed experts of one layer whose weights a decode step reads: all
    that are held, whatever the batch routes. ``observed`` has no say."""
    lo, hi = _held(mf)
    return hi - lo


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every layer's
    attention with its gate and its two norms, the dense layer's SwiGLU, a
    sparse layer's router, the held experts and the shared one, the final
    norm and the output matrix. The embedding lookup reads a row per lane
    and is left out."""
    if quant is not None:
        raise ValueError(f"laguna is served unquantised; no count for quant {quant!r}")
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf["first_dense_layers"]
    sparse = (h * mf["num_experts"]
              + (experts_read_per_step(mf, observed) + mf["num_shared_experts"])
              * expert_params(mf))
    params = (sum(attention_params(mf, l) for l in range(L)) + L * 2 * h
              + Ld * 3 * h * mf["intermediate_size"] + (L - Ld) * sparse
              + h + h * mf["vocab_size"])
    return params * _act(mf)


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds for as long as its sequence lives:
    the FULL layers' only."""
    return (len(_layers(mf, "full_attention")) * 2 * mf["num_kv_heads"] * mf["head_dim"]
            * kv_bytes)


def window_bytes_per_sequence(mf: dict, block_size: int, kv_bytes: int = 2) -> int:
    """Bytes of K and V the window layers hold for one decoding sequence,
    whatever its context: ``sliding_window / block_size + 1`` blocks each."""
    blocks = mf["sliding_window"] // block_size + 1
    return (len(_layers(mf, "sliding_attention")) * blocks * block_size
            * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes)


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """The MEAN bytes one attention call of a decode step must read, over
    the step's calls of both kinds: a full layer reads K and V of every
    block in use by the batch's sequences, a window layer those of the
    blocks that hold a sequence's newest ``sliding_window`` tokens, wherever
    they lie in their blocks (``min(context, sliding_window + block_size)``
    tokens' blocks: whole blocks are what moves). A window layer that walked
    the whole context would read what a full layer reads, and the share of
    the roofline computed from this count would fall by that much."""
    row = 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes
    full = sum(-(-t // block_size) for t in context_tokens)
    seen = mf["sliding_window"] + block_size
    win = sum(-(-min(t, seen) // block_size) for t in context_tokens)
    n_full, n_win = len(_layers(mf, "full_attention")), len(_layers(mf, "sliding_attention"))
    return (n_full * full + n_win * win) * block_size * row // (n_full + n_win)


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs ON THIS CHIP: attention
    projections and gates, the dense layer's SwiGLU, a sparse layer's router,
    shared expert and the ``k x held / E`` routed experts an even router
    sends it here, the output matrix, and attention against ``context``
    tokens in a full layer and ``min(context, sliding_window)`` in a window
    layer, at each layer's own query heads."""
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf["first_dense_layers"]
    lo, hi = _held(mf)
    routed = mf["num_experts_per_tok"] * (hi - lo) / mf["num_experts"]
    sparse = h * mf["num_experts"] + (routed + mf["num_shared_experts"]) * expert_params(mf)
    matmuls = (sum(attention_params(mf, l) for l in range(L))
               + Ld * 3 * h * mf["intermediate_size"] + (L - Ld) * sparse
               + h * mf["vocab_size"])
    attn = sum(
        4 * mf["heads_per_layer"][l] * mf["head_dim"]
        * (context if kind == "full_attention" else min(context, mf["sliding_window"]))
        for l, kind in enumerate(mf["layer_types"]))
    return int(2 * matmuls + attn)
