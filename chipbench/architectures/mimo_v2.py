"""``model_type`` "mimo_v2": Xiaomi's MiMo-V2.5 (published ``config.json``;
the language model only: the catalog's copy has no key of the vision and
audio towers or of the MTP layers, and none is modelled). Attention layers
of TWO kinds by ``hybrid_layer_pattern`` (0 full, 1 sliding-window, five to
one): both with ``num_attention_heads`` query heads whose KEY is ``head_dim``
(192) wide beside a VALUE of ``v_head_dim`` (128), a third of each head
rotated (``partial_rotary_factor`` 0.334: the first 64 values), every value
scaled by ``attention_value_scale``; the kinds differ in their KV heads
(``num_key_value_heads`` 4 / ``swa_num_key_value_heads`` 8), in rope's base
(``rope_theta`` / ``swa_rope_theta``) and in a learned SINK logit a query
head that joins a window layer's softmax and carries no value
(``add_swa_attention_sink_bias``). ``moe_layer_freq`` 0 keeps a dense
SwiGLU; the others have ``n_routed_experts`` experts of width
``moe_intermediate_size`` scored by a sigmoid, ``num_experts_per_tok`` chosen
by score PLUS a learned bias (``topk_method`` "noaux_tc"), weighted by their
plain scores, normalised; no groups (``n_group`` 1), no shared expert. Its
plain reference is ``chipbench/reference/mimo_v2.py``.

**A chip's share.** A configuration of it states ``experts_held``:
``{"rank", "of", "published"}``. ``n_routed_experts`` in the file is what
THIS chip holds (``published / of``, listed in ``reduced``); the router keeps
the published width, and program and reference add only the held experts'
terms.

Counts. A decode step of the program runs every held expert on every row
(``dynamo_tpu/ops/expert_stream.py``), so :func:`decode_weight_bytes` counts
them all; ``tests/chipbench/test_chipbench_mimo.py`` pins the count to the
leaves the program's decode step reads. A token holds K/V for as long as its
sequence lives in the FULL layers only (:func:`kv_bytes_per_token`: 2 x 4 x
320 x 2 B = 5,120 here); a window layer holds ``sliding_window`` tokens'
blocks a sequence whatever the context (:func:`window_bytes_per_sequence`).
:func:`attn_decode_bytes_per_layer` is the MEAN over a step's attention
calls, of both kinds, of the LEAST a call reads: a full layer every cached
token's ``n_kv x (192 + 128)`` values, a window layer those of its newest
``sliding_window`` tokens; block edges are NOT counted (the pages a kernel
moves hold more), so that no share of the roofline can read over 100%.
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
    "swa_num_key_value_heads": "window_kv_heads",
    "head_dim": "head_dim",
    "v_head_dim": "v_head_dim",
    "attention_value_scale": "attn_value_scale",
    "layernorm_epsilon": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "attn_qkv_bias",
    "torch_dtype": "dtype",
    "sliding_window": "sliding_window",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "n_group": "n_group",
    "topk_group": "topk_group",
}

_KINDS = ("full_attention", "sliding_attention")   # hybrid_layer_pattern 0, 1


def derived(cfg: dict) -> dict:
    """The layers' kinds from ``hybrid_layer_pattern``, rope by kind from the
    two bases and the rotated share, the sinks from the two switches, the
    leading dense layers from ``moe_layer_freq``, the router from
    ``scoring_func`` and ``topk_method``; the router's width and this chip's
    share from ``experts_held`` beside ``n_routed_experts`` (what is held).
    Published keys this file reads no equation from must hold the one value
    the equations assume."""
    L = cfg["num_hidden_layers"]
    assumed = {
        "swa_num_attention_heads": cfg["num_attention_heads"],
        "swa_head_dim": cfg["head_dim"], "swa_v_head_dim": cfg["v_head_dim"],
        "sliding_window_size": cfg["sliding_window"], "hidden_act": "silu",
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_shared_experts": None,
        "routed_scaling_factor": None, "hybrid_block_size": None,
    }
    for key, want in assumed.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"mimo_v2: {key}={cfg[key]!r} is not modelled (only {want!r})")
    if (cfg.get("rope_scaling") or {}).get("rope_type", "default") != "default":
        raise ValueError("mimo_v2: a scaled rope is not modelled")
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        if len(cfg[key]) != L or set(cfg[key]) - {0, 1}:
            raise ValueError(f"mimo_v2: {key} must name each of num_hidden_layers={L} 0 or 1")
    freq = list(cfg["moe_layer_freq"])
    dense = freq.index(1) if 1 in freq else L
    if 0 in freq[dense:]:
        raise ValueError("mimo_v2: dense MLP layers after a sparse one are not modelled")
    held = cfg.get("experts_held") or {"rank": 0, "of": 1,
                                       "published": cfg["n_routed_experts"]}
    if held["published"] != held["of"] * cfg["n_routed_experts"]:
        raise ValueError(
            f"mimo_v2: n_routed_experts={cfg['n_routed_experts']} held by each of "
            f"{held['of']} chips is not the published {held['published']}")
    rotated = {"rope_type": "default", "partial_rotary_factor": cfg["partial_rotary_factor"]}
    sinks = tuple(kind for kind, key in zip(_KINDS, (
        "add_full_attention_sink_bias", "add_swa_attention_sink_bias")) if cfg.get(key))
    return {
        "layer_types": tuple(_KINDS[i] for i in cfg["hybrid_layer_pattern"]),
        "rope_by_kind": {
            "full_attention": {**rotated, "rope_theta": cfg["rope_theta"]},
            "sliding_attention": {**rotated, "rope_theta": cfg["swa_rope_theta"]},
        },
        "attn_sinks": sinks,
        "router_scoring": "sigmoid",
        "router_bias": True,
        "first_dense_layers": dense,
        "num_experts": held["published"],
        "experts_held": (held["rank"], held["of"]),
    }


# -- the engine's parameter tree as the reference's float32 pieces ---------

_GROUP = {"full_attention": "attn", "sliding_attention": "attn_window"}


def kv_heads(mf: dict) -> dict:
    """KV heads of each published layer kind."""
    return {"full_attention": mf["num_kv_heads"],
            "sliding_attention": mf.get("window_kv_heads") or mf["num_kv_heads"]}


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` of the engine's tree (``layers``: the two norms of every
    layer; ``attn`` / ``attn_window``: the attention leaves, one entry a
    layer of that kind, ``wqkv`` fused ``[q | k | v]`` at the kind's KV heads
    and the two widths, ``sink`` where the kind has one; ``dense_mlp`` /
    ``moe`` as LFM2's) as ``(kind, attention weights, mlp_norm, mlp)`` for
    ``reference.mimo_v2.forward``."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — served unquantised
    kinds = mf["layer_types"]
    at = sum(k == kinds[l] for k in kinds[:l])
    lp = {k: v[at] for k, v in params[_GROUP[kinds[l]]].items()}
    norms = qwen2.layer(params, l)
    n_kv = kv_heads(mf)[kinds[l]]
    q_size, k_size = mf["num_heads"] * mf["head_dim"], n_kv * mf["head_dim"]
    wqkv = f32(lp["wqkv"])
    w_attn = {"attn_norm": f32(norms["attn_norm"]), "wq": wqkv[:, :q_size],
              "wk": wqkv[:, q_size:q_size + k_size], "wv": wqkv[:, q_size + k_size:],
              "wo": f32(lp["wo"]), "sink": f32(lp["sink"]) if "sink" in lp else None}
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

    return kinds[l], w_attn, f32(norms["mlp_norm"]), (
        "sparse", f32(m["w_router"]), f32(m["expert_bias"]), experts())


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, held: tuple[int, int] | None = None,
                     faults: tuple[str, ...] = ()):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``, given
    the same share of the experts as the engine (``held`` narrows it, for
    the test that adds the shares up; ``faults`` leaves a mechanism out, for
    the comparisons that must fail)."""
    import jax.numpy as jnp

    from chipbench.reference import mimo_v2

    qwen2.require_tp1(params)
    return mimo_v2.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        kv_heads=kv_heads(mf), head_dim=mf["head_dim"], v_head_dim=mf["v_head_dim"],
        rope_by_kind={k: dict(v) for k, v in dict(mf["rope_by_kind"]).items()},
        window=mf["sliding_window"], eps=mf["rms_norm_eps"],
        top_k=mf["num_experts_per_tok"], value_scale=mf["attn_value_scale"],
        held=held or _held(mf), rows=rows, faults=faults,
    )


# -- counts from shapes ------------------------------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def _layers(mf: dict, kind: str) -> list[int]:
    return [l for l, k in enumerate(mf["layer_types"]) if k == kind]


def kv_values(mf: dict, kind: str) -> int:
    """Values one token caches in one layer of ``kind``: its KV heads' keys
    and values at their own widths."""
    return kv_heads(mf)[kind] * (mf["head_dim"] + mf["v_head_dim"])


def attention_params(mf: dict, l: int) -> int:
    """Layer ``l``'s attention matrices (the sinks are counted in bytes by
    :func:`decode_weight_bytes`: they are float32)."""
    h, n = mf["hidden_size"], mf["num_heads"]
    return (h * (n * mf["head_dim"] + kv_values(mf, mf["layer_types"][l]))
            + n * mf["v_head_dim"] * h)


def expert_params(mf: dict) -> int:
    return 3 * mf["hidden_size"] * mf["moe_intermediate_size"]


def experts_read_per_step(mf: dict, observed: Observed = UNKNOWN) -> int:
    """Routed experts of one layer whose weights a decode step reads: all
    that are held, whatever the batch routes. ``observed`` has no say."""
    lo, hi = _held(mf)
    return hi - lo


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every layer's
    attention with its two norms (and a window layer's float32 sinks), the
    dense layer's SwiGLU, a sparse layer's router with its float32 choice
    bias and the held experts, the final norm and the output matrix. The
    embedding lookup reads a row per lane and is left out."""
    if quant is not None:
        raise ValueError(f"mimo_v2 is served unquantised; no count for quant {quant!r}")
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf["first_dense_layers"]
    E = mf["num_experts"]
    sparse = h * E + experts_read_per_step(mf, observed) * expert_params(mf)
    params = (sum(attention_params(mf, l) for l in range(L)) + L * 2 * h
              + Ld * 3 * h * mf["intermediate_size"] + (L - Ld) * sparse
              + h + h * mf["vocab_size"])
    sinks = sum(mf["num_heads"] for k in mf["layer_types"] if k in mf.get("attn_sinks", ()))
    return params * _act(mf) + 4 * (sinks + (L - Ld) * E)


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds for as long as its sequence lives:
    the FULL layers' only."""
    return len(_layers(mf, "full_attention")) * kv_values(mf, "full_attention") * kv_bytes


def window_bytes_per_sequence(mf: dict, block_size: int, kv_bytes: int = 2) -> int:
    """Bytes of K and V the window layers hold for one decoding sequence,
    whatever its context: ``sliding_window / block_size + 1`` blocks each."""
    blocks = mf["sliding_window"] // block_size + 1
    return (len(_layers(mf, "sliding_attention")) * blocks * block_size
            * kv_values(mf, "sliding_attention") * kv_bytes)


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """The MEAN bytes one attention call of a decode step must read, over
    the step's calls of both kinds, and the LEAST each could: a full layer
    K and V of every cached token, a window layer those of a sequence's
    newest ``sliding_window`` tokens. ``block_size`` has no say: the tokens
    a block holds past a context's end, or before a window's start, are not
    counted, so that the share of the roofline computed from this count
    cannot pass 100% whatever pages a kernel moves."""
    full = sum(context_tokens) * kv_values(mf, "full_attention")
    win = (sum(min(t, mf["sliding_window"]) for t in context_tokens)
           * kv_values(mf, "sliding_attention"))
    n_full, n_win = len(_layers(mf, "full_attention")), len(_layers(mf, "sliding_attention"))
    return (n_full * full + n_win * win) * kv_bytes // (n_full + n_win)


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs ON THIS CHIP: attention
    projections, the dense layer's SwiGLU, a sparse layer's router and the
    ``k x held / E`` routed experts an even router sends it here, the output
    matrix, and attention against ``context`` tokens in a full layer and
    ``min(context, sliding_window)`` in a window layer: scores over the
    key's width, values over the value's."""
    h, L, Ld = mf["hidden_size"], mf["num_layers"], mf["first_dense_layers"]
    lo, hi = _held(mf)
    routed = mf["num_experts_per_tok"] * (hi - lo) / mf["num_experts"]
    sparse = h * mf["num_experts"] + routed * expert_params(mf)
    matmuls = (sum(attention_params(mf, l) for l in range(L))
               + Ld * 3 * h * mf["intermediate_size"] + (L - Ld) * sparse
               + h * mf["vocab_size"])
    attn = sum(
        2 * mf["num_heads"] * (mf["head_dim"] + mf["v_head_dim"])
        * (context if kind == "full_attention" else min(context, mf["sliding_window"]))
        for kind in mf["layer_types"])
    return int(2 * matmuls + attn)
