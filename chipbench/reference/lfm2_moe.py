"""Plain reference of LFM2-24B-A2B (LiquidAI, ``model_type`` "lfm2_moe"; the
published ``config.json`` and the family's modelling code, ``Lfm2Moe*`` in
transformers): straight ``jax.numpy`` in float32 at ``highest`` matmul
precision over one whole sequence, no cache, no state, no kernels, no
batching, no dispatch.

    x = embed[ids]
    per layer l (pre-norm residual blocks; no bias anywhere):
      y = rmsnorm(x) * w_operator_norm
      layer_types[l] == "conv":                             gated short convolution
        [B, C, z] = y W_in                                  three parts of width h
        u   = B * z
        c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t           per channel (depthwise), causal,
                                                            u zero before position 0; L = 3 taps
        x   = x + (C * c) W_out
      layer_types[l] == "full_attention":                   grouped-query attention
        q, k, v = y Wq, y Wk, y Wv                          heads of width d = h / n_heads
        q = rmsnorm(q) * w_q_layernorm ; k = rmsnorm(k) * w_k_layernorm
                                                            over each head's d values, BEFORE rope
        q, k = rope(q), rope(k)                             all d values, half-split pairs, theta
        o   = softmax(q k^T * d^-0.5 + causal) v            each KV head serves n_heads / n_kv heads
        x   = x + concat(o) Wo
      y = rmsnorm(x) * w_ffn_norm
      l < num_dense_layers:  x = x + SwiGLU(y)              width intermediate_size
      else:
        sc  = sigmoid(y Wg)                                 [E], float32
        chosen = the k highest of sc + expert_bias          the bias decides the CHOICE only
        w_e = sc_e / (sum(sc_chosen) + eps_r) * routed_scaling_factor   (norm_topk_prob)
        x   = x + sum_{e chosen} w_e SwiGLU_e(y)            width moe_intermediate_size; no shared expert
    logits = (rmsnorm(x) * w_final_norm) W_lm               W_lm = embed^T (tied)

Every expert runs on every token here and the weights of those not chosen
are zero: nothing is dropped.

Departures from the published model: (1) ``eps_r`` (1e-6) and the tied
output matrix are the family's convention, not in the catalog's copy of the
file (the configuration's ``assumed``); (2) the depthwise convolution is
written as its ``L`` shifted products and not as a padded ``conv1d``: the
same numbers; (3) weights are random, from the seed. None in the
mathematics.

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout from ``chipbench/architectures/lfm2_moe.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.qwen2 import mlp_block, rms_norm, rope


def short_conv(x, w, *, eps):
    """x + conv_operator(rmsnorm(x)) over a whole sequence x [T, h]
    (float32). ``w["conv_w"]`` [L, h]: tap ``j`` multiplies ``u`` at ``L - 1
    - j`` positions back (the published ``conv.weight[:, 0, j]``)."""
    T, h = x.shape
    y = rms_norm(x, w["operator_norm"], eps)
    gate_b, gate_c, z = jnp.split(y @ w["in_proj"], 3, axis=-1)
    u = gate_b * z
    taps = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, h), u.dtype), u], axis=0)
    c = sum(w["conv_w"][j] * padded[j:j + T] for j in range(taps))
    return x + (gate_c * c) @ w["out_proj"]


def attention(x, w, *, n_heads, n_kv, head_dim, theta, eps):
    """x + attention(rmsnorm(x)) over a whole sequence x [T, h] (float32),
    q and k normed per head before rope."""
    T = x.shape[0]
    y = rms_norm(x, w["operator_norm"], eps)
    q = rms_norm((y @ w["wq"]).reshape(T, n_heads, head_dim), w["q_layernorm"], eps)
    k = rms_norm((y @ w["wk"]).reshape(T, n_kv, head_dim), w["k_layernorm"], eps)
    v = (y @ w["wv"]).reshape(T, n_kv, head_dim)
    pos = jnp.arange(T)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = n_heads // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + o.reshape(T, n_heads * head_dim) @ w["wo"]


def routing_weights(y, w_router, expert_bias, *, top_k, scale, norm_eps):
    """[T, E]: each token's weight for each expert, zero where not chosen.
    The bias is added to the scores the choice is made by, and to nothing
    else."""
    T, E = y.shape[0], w_router.shape[1]
    sc = jax.nn.sigmoid(y @ w_router)
    _, idx = jax.lax.top_k(sc + expert_bias, top_k)
    chosen = jnp.take_along_axis(sc, idx, axis=1)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + norm_eps) * scale
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], idx].set(chosen)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, n_heads, n_kv, head_dim,
            theta, eps, top_k, scale, norm_eps, rows):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(kind, operator weights, ffn_norm,
    mlp)``: ``kind`` "conv" or "full_attention"; ``mlp`` either ``("dense",
    blocks)`` or ``("sparse", w_router [h, E], expert_bias [E], experts)``,
    ``blocks`` iterating ``(w_gate, w_up, w_down)`` column blocks of one
    SwiGLU and ``experts`` iterating ``(e, w_gate, w_up, w_down)`` over all
    ``E``. A piece at a time, as in ``reference.qwen2.forward``."""
    conv = jax.jit(lambda x, w: short_conv(x, w, eps=eps))
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim, theta=theta, eps=eps))
    route = jax.jit(lambda y, w_router, bias: routing_weights(
        y, w_router, bias, top_k=top_k, scale=scale, norm_eps=norm_eps))
    block = jax.jit(mlp_block)
    expert = jax.jit(lambda y, w, g, u, d: w[:, None] * mlp_block(y, g, u, d))
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for kind, w_op, ffn_norm, mlp in layers:
            x = conv(x, w_op) if kind == "conv" else attn(x, w_op)
            y = rms_norm(x, ffn_norm, eps)
            if mlp[0] == "dense":
                for w_gate, w_up, w_down in mlp[1]:
                    x = x + block(y, w_gate, w_up, w_down)
                continue
            _, w_router, expert_bias, experts = mlp
            weights = route(y, w_router, expert_bias)
            for e, w_gate, w_up, w_down in experts:
                x = x + expert(y, weights[:, e], w_gate, w_up, w_down)
        x = rms_norm(x[jnp.asarray(rows)], final_norm, eps)
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
