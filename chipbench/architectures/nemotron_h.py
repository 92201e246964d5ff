"""``model_type`` "nemotron_h": NVIDIA's Nemotron-3-Nano-30B-A3B (published
``config.json``; the family's modelling code: a Mamba-2 mixer as the
``mamba_ssm`` reference writes it, DeepSeek-V3's router). Layers by
``hybrid_override_pattern``, one letter a layer, EACH BLOCK ONE SUB-LAYER
under one norm: ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads of
``mamba_head_dim``, ``n_groups`` groups, a state ``ssm_state_size`` wide, a
depthwise causal convolution of ``conv_kernel`` taps with a bias), which keeps
a FLOAT32 state of ``H x P x N`` values a sequence whatever the context;
``*`` grouped-query attention with NO rotary embedding; ``E``
``n_routed_experts`` experts of ``relu(x Wu)^2 Wd`` (``mlp_hidden_act``
"relu2": two matrices, no gate) ``moe_intermediate_size`` wide, scored by a
sigmoid, ``num_experts_per_tok`` chosen by score + bias, weights normalised
and scaled by ``routed_scaling_factor``, beside one shared expert
``moe_shared_expert_intermediate_size`` wide. Its plain reference is
``chipbench/reference/nemotron_h.py``.

**A chip's share.** A configuration of it states ``experts_held``:
``{"rank", "of", "published"}``. ``n_routed_experts`` in the file is what THIS
chip holds (``published / of``, listed in ``reduced``); the router keeps the
published width, and program and reference add only the held experts' terms.

Counts, all at the PUBLISHED widths (the program stores an un-gated expert
1,856 wide as 1,920, zeros behind it: a layout,
``ModelConfig.expert_stored_width``): only the attention layers cache K/V
(:func:`kv_bytes_per_token`); a decode step of the program runs every held
expert on every row, so :func:`decode_weight_bytes` counts them all whatever
``observed`` says the batch routed (at the cell's 128 lanes x 6 of 128 every
held expert is touched anyway); a mamba layer's decode step reads and writes
its whole state for every live lane (:func:`state_step_bytes_per_layer`, what
``readers/slab_state_roofline.py`` holds the ``ssd_step`` scope to).
``tests/chipbench/test_chipbench_nemotron_h.py`` pins the counts by hand and
to the program's leaves.
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
    "norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
    "mamba_num_heads": "ssm_num_heads",
    "mamba_head_dim": "ssm_head_dim",
    "ssm_state_size": "ssm_state_size",
    "n_groups": "ssm_n_groups",
    "conv_kernel": "ssm_conv_kernel",
    "chunk_size": "ssm_chunk_size",
    "use_conv_bias": "ssm_conv_bias",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_shared_expert_intermediate_size": "shared_expert_intermediate_size",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
}

# ``hybrid_override_pattern``'s letters as ``ModelConfig.layer_types`` names them
LETTERS = {"M": "mamba", "*": "full_attention", "E": "moe"}


def derived(cfg: dict) -> dict:
    """The layer kinds from the pattern's letters, the router's width and this
    chip's share from ``experts_held`` beside ``n_routed_experts`` (what is
    held), and what is the model type's: blocks of one sub-layer, no rotary
    embedding, sigmoid scores with a bias on the choice, un-gated ``relu^2``
    experts. Published keys this file reads no equation from must hold the one
    value the equations assume."""
    assumed = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "attention_bias": False,
               "mlp_bias": False, "mamba_proj_bias": False, "use_bias": False,
               "sliding_window": None}
    for key, want in assumed.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"nemotron_h: {key}={cfg[key]!r} is not modelled (only {want!r})")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set(LETTERS):
        raise ValueError(
            f"nemotron_h: hybrid_override_pattern {pattern!r} must name each of "
            f"num_hidden_layers={cfg['num_hidden_layers']} layers one of {sorted(LETTERS)} "
            "(a '-' block, a dense MLP alone, is not modelled)")
    held = cfg.get("experts_held") or {
        "rank": 0, "of": 1, "published": cfg["n_routed_experts"]}
    if held["published"] != held["of"] * cfg["n_routed_experts"]:
        raise ValueError(
            f"nemotron_h: n_routed_experts={cfg['n_routed_experts']} held by each of "
            f"{held['of']} chips is not the published {held['published']}")
    return {
        "layer_types": tuple(LETTERS[c] for c in pattern),
        "rope_theta": None,
        "router_scoring": "sigmoid",
        "router_bias": True,
        "mlp_activation": "relu2",
        "num_experts": held["published"],
        "experts_held": (held["rank"], held["of"]),
    }


# -- the engine's parameter tree as the reference's pieces ---------------------

def _held(mf: dict) -> tuple[int, int]:
    rank, of = mf.get("experts_held") or (0, 1)
    n = mf["num_experts"] // of
    return rank * n, (rank + 1) * n


def _kind_index(mf: dict, l: int) -> tuple[str, int]:
    """(published kind of layer ``l``, its index among its kind)."""
    kinds = mf["layer_types"]
    return kinds[l], sum(k == kinds[l] for k in kinds[:l])


def published_layout(params, l: int, mf: dict, shared_blocks: int = 4):
    """Layer ``l`` of the engine's tree (``layers``: the one norm a block;
    ``ssm`` / ``attn`` / ``moe``: one entry a layer of that kind) as ``(kind,
    weights)`` for ``reference.nemotron_h.forward``: the matrices in the
    engine's dtype (the reference makes them float32 inside its jitted
    pieces) at the PUBLISHED widths: ``in_proj`` put together again from the
    engine's ``w_zx`` and ``w_dt``, an expert's zero columns and rows beyond
    ``moe_intermediate_size`` cut off."""
    import jax.numpy as jnp

    kind, at = _kind_index(mf, l)
    norm = params["layers"]["attn_norm"][l]
    if kind == "mamba":
        m = params["ssm"]
        return kind, {
            "norm": norm, "in_proj": jnp.concatenate([m["w_zx"][at], m["w_dt"][at]], axis=1),
            "conv_w": m["conv_w"][at], "conv_b": m["conv_b"][at], "A_log": m["A_log"][at],
            "D": m["D"][at], "dt_bias": m["dt_bias"][at], "gate_norm": m["ssm_norm"][at],
            "out_proj": m["w_out"][at]}
    if kind == "full_attention":
        m = params["attn"]
        q_size = mf["num_heads"] * mf["head_dim"]
        kv_size = mf["num_kv_heads"] * mf["head_dim"]
        return kind, {
            "norm": norm, "wq": m["wqkv"][at, :, :q_size],
            "wk": m["wqkv"][at, :, q_size:q_size + kv_size],
            "wv": m["wqkv"][at, :, q_size + kv_size:], "wo": m["wo"][at]}
    m = {k: v[at] for k, v in params["moe"].items()}
    im = mf["moe_intermediate_size"]
    lo, hi = _held(mf)

    def experts():
        for j, e in enumerate(range(lo, hi)):
            yield e, m["w_gu"][j, :, :im], m["w_down"][j, :im]

    sw = m["shared_down"].shape[0]
    edges = [sw * i // shared_blocks for i in range(shared_blocks + 1)]
    shared = ((m["shared_wgu"][:, a:b], m["shared_down"][a:b])
              for a, b in zip(edges, edges[1:]))
    return kind, {"norm": norm, "w_router": m["w_router"], "bias": m["expert_bias"],
                  "experts": experts(), "shared": shared}


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, faults: tuple[str, ...] = ()):
    """Logits [len(rows), vocab] (float32) of the plain reference on the
    engine's own weights ``params`` at positions ``rows`` of ``ids``, the
    held experts' share only. ``faults`` changes what a control changes, for
    the comparisons that must fail (``reference.nemotron_h.FAULTS``)."""
    import jax.numpy as jnp

    from chipbench.reference import nemotron_h

    qwen2.require_tp1(params)
    return nemotron_h.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], n_kv=mf["num_kv_heads"], head_dim=mf["head_dim"],
        H=mf["ssm_num_heads"], P=mf["ssm_head_dim"], N=mf["ssm_state_size"],
        G=mf["ssm_n_groups"], eps=mf["rms_norm_eps"], top_k=mf["num_experts_per_tok"],
        scale=mf.get("routed_scaling_factor", 1.0), held=_held(mf), rows=rows, faults=faults,
    )


# -- counts from shapes, at the published widths ---------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def _layers(mf: dict, kind: str) -> int:
    return sum(k == kind for k in mf["layer_types"])


def _hpn(mf: dict) -> tuple[int, int, int]:
    return mf["ssm_num_heads"], mf["ssm_head_dim"], mf["ssm_state_size"]


def ssm_channels(mf: dict) -> int:
    """Channels of the convolution: ``[x | B | C]``."""
    H, P, N = _hpn(mf)
    return H * P + 2 * mf["ssm_n_groups"] * N


def ssm_matrix_params(mf: dict) -> int:
    """One mixer's matrices: ``in_proj [h, 2 d_in + 2 G N + H]`` and
    ``out_proj [d_in, h]``."""
    H, P, _ = _hpn(mf)
    return mf["hidden_size"] * (H * P + ssm_channels(mf) + H) + H * P * mf["hidden_size"]


def ssm_small_bytes(mf: dict) -> int:
    """The taps, their bias and the gated norm at the model's dtype;
    ``A_log``, ``D`` and ``dt_bias`` in float32."""
    H, P, _ = _hpn(mf)
    return ((mf["ssm_conv_kernel"] + 1) * ssm_channels(mf) + H * P) * _act(mf) + 3 * H * 4


def attention_params(mf: dict) -> int:
    h, d = mf["hidden_size"], mf["head_dim"]
    q, kv = mf["num_heads"] * d, mf["num_kv_heads"] * d
    return h * (q + 2 * kv) + q * h


def expert_params(mf: dict) -> int:
    """One routed expert: two matrices, no gate."""
    return 2 * mf["hidden_size"] * mf["moe_intermediate_size"]


def experts_read_per_step(mf: dict, observed: Observed = UNKNOWN) -> int:
    """Routed experts of one sparse layer whose weights a decode step reads:
    every HELD one, whatever the batch routes (the program's decode path runs
    every held expert on every row). ``observed`` has no say."""
    lo, hi = _held(mf)
    return hi - lo


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights one decode step must read from HBM: every leaf but the
    embedding table (its LOOKUP reads a row a lane): each block's norm, the
    mixers, a sparse layer's router, bias (float32), the experts it reads and
    the shared expert, the final norm and the untied output matrix."""
    if quant is not None:
        raise ValueError(f"nemotron_h is served unquantised; no count for quant {quant!r}")
    if mf.get("tie_embeddings"):
        raise ValueError("nemotron_h: the output matrix is untied as published")
    h, L, E = mf["hidden_size"], mf["num_layers"], mf["num_experts"]
    n_ssm, n_moe = _layers(mf, "mamba"), _layers(mf, "moe")
    sparse = (h * E + experts_read_per_step(mf, observed) * expert_params(mf)
              + 2 * h * mf["shared_expert_intermediate_size"])
    params = (n_ssm * ssm_matrix_params(mf)
              + _layers(mf, "full_attention") * attention_params(mf)
              + n_moe * sparse + L * h + h + h * mf["vocab_size"])
    return params * _act(mf) + n_ssm * ssm_small_bytes(mf) + n_moe * E * 4


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    """Bytes of K and V one token holds: the ATTENTION layers' only."""
    return (_layers(mf, "full_attention") * 2 * mf["num_kv_heads"] * mf["head_dim"]
            * kv_bytes)


def state_bytes_per_sequence(mf: dict) -> int:
    """Bytes of recurrent state one sequence holds over all mamba layers,
    whatever its context: the float32 state and the convolution's ``K - 1``
    newest rows at the model's dtype."""
    H, P, N = _hpn(mf)
    return _layers(mf, "mamba") * (
        H * P * N * 4 + (mf["ssm_conv_kernel"] - 1) * ssm_channels(mf) * _act(mf))


def state_step_bytes_per_layer(lanes: float, mf: dict) -> float:
    """Bytes ONE mamba layer's decode step must move: every live lane's
    float32 state read once and written once."""
    H, P, N = _hpn(mf)
    return 2 * H * P * N * 4 * lanes


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """Bytes ONE attention layer's decode call must read: K and V of every
    block in use by the batch's sequences (whole blocks: pages are what moves)."""
    blocks = sum(-(-t // block_size) for t in context_tokens)
    return blocks * block_size * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 one token needs: the mixers' matrices, the taps, a
    mamba layer's state (the decay, the rank-one update, ``S C``: 6 H P N), a
    sparse layer's router, the CHOSEN experts (``num_experts_per_tok``: the
    model's own work a token, whatever share is held) and the shared one, the
    output matrix, and attention against ``context`` tokens in the attention
    layers."""
    h = mf["hidden_size"]
    H, P, N = _hpn(mf)
    n_ssm, n_attn = _layers(mf, "mamba"), _layers(mf, "full_attention")
    sparse = (h * mf["num_experts"] + mf["num_experts_per_tok"] * expert_params(mf)
              + 2 * h * mf["shared_expert_intermediate_size"])
    matmuls = (n_ssm * (ssm_matrix_params(mf) + mf["ssm_conv_kernel"] * ssm_channels(mf))
               + n_attn * attention_params(mf) + _layers(mf, "moe") * sparse
               + h * mf["vocab_size"])
    state = n_ssm * 6 * H * P * N
    attn = n_attn * 4 * mf["num_heads"] * mf["head_dim"] * context
    return int(2 * matmuls + state + attn)
