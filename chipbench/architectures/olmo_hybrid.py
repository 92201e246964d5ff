"""``model_type`` "olmo_hybrid": allenai's Olmo-Hybrid-7B (published
``config.json``; Olmo 2/3's block, and for the ``linear_*`` keys the
``GatedDeltaNet`` block of the flash-linear-attention code the family builds
on). Layers of TWO kinds by ``layer_types``: a gated delta rule
(``linear_num_value_heads`` heads, keys ``linear_key_head_dim`` and values
``linear_value_head_dim`` wide, a depthwise causal convolution of
``linear_conv_kernel_dim`` taps on q, k and v, beta in (0, 2) with
``linear_allow_neg_eigval``), which keeps a FLOAT32 state of ``H x dk x dv``
values a sequence whatever the context, and multi-head attention with an
RMSNorm over the whole q and k projections and NO rotary embedding
(``rope_parameters.rope_theta`` null). A dense SwiGLU in every layer, each
sub-layer normed on its output, the output matrix untied. Its plain
reference is ``chipbench/reference/olmo_hybrid.py``.

Counts: only the full layers cache K/V (:func:`kv_bytes_per_token`,
:func:`attn_decode_bytes_per_layer` for ONE of them); a linear layer's decode
step reads and writes its whole state for every live lane
(:func:`state_step_bytes_per_layer`, what ``readers/state_roofline.py``
holds the ``state_step`` scope to), a stream that is neither weights nor
pages. ``tests/chipbench/test_chipbench_olmo_hybrid.py`` pins the counts to
the program's leaves and its slab.
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
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
    "layer_types": "layer_types",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim",
    "linear_allow_neg_eigval": "linear_allow_neg_eigval",
}


def derived(cfg: dict) -> dict:
    """Head width, NO rope (``rope_theta`` null inside ``rope_parameters``),
    the whole-projection QK-norm and the output norms are the model type's.
    Published keys this file reads no equation from must hold the one value
    the equations assume."""
    rope = cfg.get("rope_parameters") or {}
    if rope.get("rope_theta") is not None:
        raise ValueError(f"olmo_hybrid: rope_theta={rope['rope_theta']!r}: the full layers "
                         "are modelled without a rotary embedding (the published null)")
    if cfg.get("attention_bias") or cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("olmo_hybrid: attention_bias or an activation other than silu "
                         "is not modelled")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("olmo_hybrid: layer_types must name each of num_hidden_layers")
    return {
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "rope_theta": None,
        "qk_norm": True,
        "qk_norm_over": "projection",
        "post_norm": True,
    }


# -- the engine's parameter tree as the reference's float32 pieces ---------

def _kind_index(mf: dict, l: int) -> tuple[str, int]:
    """(published kind of layer ``l``, its index among its kind)."""
    kinds = mf["layer_types"]
    return kinds[l], sum(k == kinds[l] for k in kinds[:l])


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` of the engine's tree (``layers``: the two output norms and
    the SwiGLU of every layer; ``linear`` / ``attn``: the mixers, one entry a
    layer of that kind) as ``(kind, mixer weights, ffn post norm, blocks)``
    for ``reference.olmo_hybrid.forward``: the mixer's matrices in the
    engine's dtype (the reference makes them float32 inside its jitted
    pieces), ``blocks`` float32 column blocks of the SwiGLU cut straight from
    the stacked leaves (a whole layer's ``wgu`` is never sliced out)."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — served unquantised
    kind, at = _kind_index(mf, l)
    if kind == "linear_attention":
        m = params["linear"]
        H, dk = mf["linear_num_value_heads"], mf["linear_key_head_dim"]
        w = {"wq": m["w_qkv"][at, :, :H * dk], "wk": m["w_qkv"][at, :, H * dk:2 * H * dk],
             "wv": m["w_qkv"][at, :, 2 * H * dk:], "wz": m["w_z"][at],
             "wb": m["w_ba"][at, :, :H], "wa": m["w_ba"][at, :, H:], "conv_w": m["conv_w"][at],
             "A_log": m["A_log"][at], "dt_bias": m["dt_bias"][at],
             "o_norm": m["o_norm"][at], "wo": m["w_out"][at]}
    else:
        m = params["attn"]
        q_size = mf["num_heads"] * mf["head_dim"]
        kv_size = mf["num_kv_heads"] * mf["head_dim"]
        w = {"wq": m["wqkv"][at, :, :q_size], "wk": m["wqkv"][at, :, q_size:q_size + kv_size],
             "wv": m["wqkv"][at, :, q_size + kv_size:], "wo": m["wo"][at],
             "q_norm": m["q_layernorm"][at], "k_norm": m["k_layernorm"][at]}
    w["post_norm"] = params["layers"]["attn_norm"][l]
    inter = mf["intermediate_size"]
    edges = [inter * i // mlp_blocks for i in range(mlp_blocks + 1)]
    wgu, w_down = params["layers"]["wgu"], params["layers"]["w_down"]

    def blocks():
        for a, b in zip(edges, edges[1:]):
            yield f32(wgu[l, :, a:b]), f32(wgu[l, :, inter + a:inter + b]), f32(w_down[l, a:b])

    return kind, w, f32(params["layers"]["mlp_norm"][l]), blocks()


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, faults: tuple[str, ...] = ()):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``.
    ``faults`` changes what a control changes, for the comparisons that must
    fail (``reference.olmo_hybrid.FAULTS``)."""
    import jax.numpy as jnp

    from chipbench.reference import olmo_hybrid

    qwen2.require_tp1(params)
    if mf["num_heads"] != mf["num_kv_heads"]:
        raise ValueError("olmo_hybrid: the reference's full layers are multi-head")
    return olmo_hybrid.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], head_dim=mf["head_dim"],
        H=mf["linear_num_value_heads"], dk=mf["linear_key_head_dim"],
        dv=mf["linear_value_head_dim"], eps=mf["rms_norm_eps"],
        neg_eigval=mf["linear_allow_neg_eigval"], rows=rows, faults=faults,
    )


# -- counts from shapes ------------------------------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def _layers(mf: dict, kind: str) -> int:
    return sum(k == kind for k in mf["layer_types"])


def _hkv(mf: dict) -> tuple[int, int, int]:
    return (mf["linear_num_value_heads"], mf["linear_key_head_dim"],
            mf["linear_value_head_dim"])


def linear_channels(mf: dict) -> int:
    H, dk, dv = _hkv(mf)
    return 2 * mf["linear_num_key_heads"] * dk + H * dv


def linear_matrix_params(mf: dict) -> int:
    """One linear mixer's matrices: q, k ``[h, H dk]``, v, the gate and the
    output projection ``[h, H dv]``, the two ``[h, H]`` maps."""
    h = mf["hidden_size"]
    H, dk, dv = _hkv(mf)
    return 2 * h * mf["linear_num_key_heads"] * dk + 3 * h * H * dv + 2 * h * H


def linear_small_bytes(mf: dict) -> int:
    """The taps and the output norm at the model's dtype, ``A_log`` and
    ``dt_bias`` in float32."""
    H, _, dv = _hkv(mf)
    return (mf["linear_conv_kernel_dim"] * linear_channels(mf) + dv) * _act(mf) + 2 * H * 4


def attention_params(mf: dict) -> int:
    """One full layer's matrices and its two whole-projection norms."""
    h, d = mf["hidden_size"], mf["head_dim"]
    q, kv = mf["num_heads"] * d, mf["num_kv_heads"] * d
    return h * (q + 2 * kv) + q * h + q + kv


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every leaf but
    the embedding table (its LOOKUP reads a row a lane): the mixers, the two
    output norms and the SwiGLU of every layer, the final norm and the untied
    output matrix."""
    if quant is not None:
        raise ValueError(f"olmo_hybrid is served unquantised; no count for quant {quant!r}")
    if mf.get("tie_embeddings"):
        raise ValueError("olmo_hybrid: the output matrix is untied as published")
    h, L = mf["hidden_size"], mf["num_layers"]
    n_lin = _layers(mf, "linear_attention")
    params = (n_lin * linear_matrix_params(mf)
              + _layers(mf, "full_attention") * attention_params(mf)
              + L * (2 * h + 3 * h * mf["intermediate_size"]) + h + h * mf["vocab_size"])
    return params * _act(mf) + n_lin * linear_small_bytes(mf)


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the FULL layers' only."""
    return (_layers(mf, "full_attention") * 2 * mf["num_kv_heads"] * mf["head_dim"]
            * kv_bytes)


def state_bytes_per_sequence(mf: dict) -> int:
    """Bytes of recurrent state one sequence holds over all linear layers,
    whatever its context: the float32 state and the convolution's ``K - 1``
    newest rows at the model's dtype."""
    H, dk, dv = _hkv(mf)
    return _layers(mf, "linear_attention") * (
        H * dk * dv * 4
        + (mf["linear_conv_kernel_dim"] - 1) * linear_channels(mf) * _act(mf))


def state_step_bytes_per_layer(lanes: float, mf: dict) -> float:
    """Bytes ONE linear layer's decode step must move: every live lane's
    float32 state read once and written once."""
    H, dk, dv = _hkv(mf)
    return 2 * H * dk * dv * 4 * lanes


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """Bytes ONE full layer's decode call must read: K and V of every block
    in use by the batch's sequences (whole blocks: pages are what moves)."""
    blocks = sum(-(-t // block_size) for t in context_tokens)
    return blocks * block_size * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs: the mixers' matrices, the taps, a
    linear layer's state (``S'^T k``, the rank-one update, ``S^T q``: 6 H dk
    dv), the SwiGLU, the output matrix, and attention against ``context``
    tokens in the full layers."""
    h, L = mf["hidden_size"], mf["num_layers"]
    H, dk, dv = _hkv(mf)
    n_lin, n_full = _layers(mf, "linear_attention"), _layers(mf, "full_attention")
    matmuls = (n_lin * (linear_matrix_params(mf)
                        + mf["linear_conv_kernel_dim"] * linear_channels(mf))
               + n_full * (attention_params(mf)
                         - (mf["num_heads"] + mf["num_kv_heads"]) * mf["head_dim"])
               + L * 3 * h * mf["intermediate_size"] + h * mf["vocab_size"])
    state = n_lin * 6 * H * dk * dv
    attn = n_full * 4 * mf["num_heads"] * mf["head_dim"] * context
    return int(2 * matmuls + state + attn)
