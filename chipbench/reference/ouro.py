"""Plain reference of the Ouro looped decoder (ByteDance, ``model_type``
"ouro"; Zhu et al., "Scaling Latent Reasoning via Looped Language Models",
2025-10; ``modeling_ouro.py`` of the published checkpoints): straight
``jax.numpy`` in float32 at ``highest`` matmul precision, full causal
attention over the whole sequence, no cache, no kernels, no batching.

    h   = embed[ids]
    for u in 0 .. ut_steps-1:                    the SAME layers, every pass
      per layer:
        y   = rmsnorm(h) * w_attn_norm
        q,k,v = y Wq, y Wk, y Wv                  (no bias; plain multi-head)
        q,k = rope(q), rope(k)                    (rotate-half, theta)
        a   = (softmax(q k^T / sqrt(d) + causal) v) Wo
        h   = h + rmsnorm(a) * w_attn_post_norm   (sandwich: a norm on the
        y   = rmsnorm(h) * w_mlp_norm              sub-layer's OUTPUT too)
        m   = (silu(y Wg) * (y Wu)) Wd
        h   = h + rmsnorm(m) * w_mlp_post_norm
      h   = rmsnorm(h) * w_final_norm             after EVERY pass; feeds the next
      g_u = sigmoid(w_gate . h + b_gate)          the exit gate's probability
    logits = h W_lm                               h after the last pass

In a served model pass ``u`` of layer ``l`` keeps K and V of its own (cache
slot ``u * layers + l``); a reference with no cache has nothing to keep.

Departures from the published model: (1) ``early_exit_threshold`` is taken
as 1, as the published configuration has it: every token takes every pass
and the gate decides nothing (its probabilities are returned for whoever
asks); (2) weights are random, from the seed. None in the mathematics.

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout, from ``chipbench/architectures/ouro.py``; ``layers`` is
called once per pass and yields the layers anew, so that a pass holds a
layer's float32 copy only while it uses it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.qwen2 import mlp_block, rms_norm, rope


def output_norm(x, weight, eps):
    """The sandwich's second norm: on a sub-layer's output, before its
    residual add."""
    return rms_norm(x, weight, eps)


def pass_norm(x, weight, eps):
    """The model's final norm, after every pass over the layers."""
    return rms_norm(x, weight, eps)


def attention(x, w, *, n_heads, head_dim, theta, eps):
    """x + rmsnorm(attention(rmsnorm(x)) Wo) over a whole sequence x
    [T, h] (float32); multi-head, no bias."""
    T = x.shape[0]
    y = rms_norm(x, w["attn_norm"], eps)
    q = (y @ w["wq"]).reshape(T, n_heads, head_dim)
    k = (y @ w["wk"]).reshape(T, n_heads, head_dim)
    v = (y @ w["wv"]).reshape(T, n_heads, head_dim)
    pos = jnp.arange(T)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    a = attn.reshape(T, n_heads * head_dim) @ w["wo"]
    return x + output_norm(a, w["attn_post_norm"], eps)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, ut_steps,
            n_heads, head_dim, theta, eps, rows, gate=None, every_pass=False):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``,
    after the last pass.

    ``layers()`` yields, per layer, ``(attention weights with both of its
    norms, mlp_norm, mlp_post_norm, iterator of (w_gate, w_up, w_down)
    column blocks)``; ``lm_head_chunks`` yields ``[h, v_chunk]`` slices of
    the output matrix (see ``reference.qwen2.forward``: the reference runs
    in the worker beside the weights and the cache). With ``every_pass``
    the answer is ``(logits [ut_steps, len(rows), vocab], gates [ut_steps,
    len(rows)])``: what the model would emit had it stopped after each
    pass, and the exit gate's probability there (``gate`` = ``(w [h],
    b [])``; zeros without one)."""
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, head_dim=head_dim, theta=theta, eps=eps))
    block = jax.jit(mlp_block)
    rows = jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        after = []                       # normed hidden states at ``rows``, per pass
        for _ in range(ut_steps):
            for w_attn, mlp_norm, mlp_post_norm, blocks in layers():
                x = attn(x, w_attn)
                y = rms_norm(x, mlp_norm, eps)
                m = jnp.zeros_like(x)
                for w_gate, w_up, w_down in blocks:
                    m = m + block(y, w_gate, w_up, w_down)
                x = x + output_norm(m, mlp_post_norm, eps)
            x = pass_norm(x, final_norm, eps)
            after.append(x[rows])
        if not every_pass:
            return jnp.concatenate([after[-1] @ chunk for chunk in lm_head_chunks], axis=-1)
        hs = jnp.stack(after)                                    # [ut, R, h]
        logits = jnp.concatenate([hs @ chunk for chunk in lm_head_chunks], axis=-1)
        if gate is None:
            return logits, jnp.zeros(hs.shape[:2], jnp.float32)
        w, b = gate
        return logits, jax.nn.sigmoid(hs @ w + b)
