"""Plain reference of SDAR-30B-A3B-Chat (JetLM, ``model_type`` "sdar_moe"; the
published ``config.json`` and the family's description: a Qwen3-MoE body
trained to generate by diffusion over blocks): straight ``jax.numpy`` in
float32 at ``highest`` matmul precision over whole sequences, no cache, no
kernels, no dispatch.

    x = embed[ids]                                          ids may hold mask tokens
    per layer (pre-norm residual blocks; no bias anywhere):
      a = rmsnorm(x) * w_attn_norm
      q, k, v = a Wq, a Wk, a Wv                            32 heads, 4 KV heads, d = 128
      q = rmsnorm(q) * w_q_norm ; k = rmsnorm(k) * w_k_norm over each head's d values, BEFORE rope
      q, k = rope(q), rope(k)                               all d values, half-split pairs, theta
      o   = softmax(q k^T * d^-0.5 + mask) v                query p sees key j iff j // B <= p // B:
                                                            every earlier block and, BOTH ways, its own
      x   = x + concat(o) Wo
      b = rmsnorm(x) * w_mlp_norm
      s = softmax(b Wr)                                     [E] float32, over ALL experts
      chosen = the k highest of s ; w_e = s_e / sum(s_chosen)        (norm_topk_prob)
      x   = x + sum_{e chosen} w_e (silu(b Wg_e) * (b Wu_e)) Wd_e    width moe_intermediate_size
    logits = (rmsnorm(x) * w_final_norm) W_lm               untied

Every expert runs on every token here and the weights of those not chosen
are zero: nothing is dropped. No shared expert, no dense layer.

GENERATION (``architectures/sdar_moe.py:score_probe`` drives it; this file is
the forward): a block of ``B`` places starts as ``mask_token_id`` at its
hidden places behind the prompt and the blocks before it; a denoising step
runs this forward over that input and reads each hidden place from its OWN
row (no shift).

``faults`` names what to leave out or lower, for the comparisons that must
then FAIL: "causal" (the in-block mask left out: a query sees the keys at
or before its own position only), and "fp8", the CONTROL of a bfloat16
configuration (``reference/control.py``): the same mathematics with every
activation that enters a weight product and K and V as a cache would hold
them rounded to ``float8_e4m3fn``. ("order" is a fault of the schedule, not
of the forward: ``score_probe`` reads it.)

Departures from the published model: block length, schedule and threshold
are the configuration's ``assumed``; weights are random, from the seed. None
in the mathematics as ``assumed`` reads it.

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout from ``chipbench/architectures/sdar_moe.py``. Several
inputs of one length go through together (``ids [N, T]``), so that a
probe's denoising steps share one pass over the experts' weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.laguna import fp8
from chipbench.reference.qwen2 import mlp_block, rms_norm, rope


def attention(x, w, *, n_heads, n_kv, head_dim, theta, eps, block, low=lambda t: t):
    """x + attention(rmsnorm(x)) over one whole sequence x [T, h] (float32),
    q and k normed per head before rope; ``block`` the block length of the
    mask (1: plain causal)."""
    T = x.shape[0]
    a = low(rms_norm(x, w["attn_norm"], eps))
    q = rms_norm((a @ w["wq"]).reshape(T, n_heads, head_dim), w["q_norm"], eps)
    k = rms_norm((a @ w["wk"]).reshape(T, n_kv, head_dim), w["k_norm"], eps)
    v = low((a @ w["wv"]).reshape(T, n_kv, head_dim))
    pos = jnp.arange(T)
    q, k = rope(q, pos, theta), low(rope(k, pos, theta))
    group = n_heads // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    seen = pos[None, :] // block <= pos[:, None] // block
    scores = jnp.where(seen[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + low(o.reshape(T, n_heads * head_dim)) @ w["wo"]


def routing_weights(b, w_router, *, top_k):
    """[T, E]: each token's weight for each expert, zero where not chosen:
    a softmax over all experts, the ``top_k`` highest, normalised."""
    T, E = b.shape[0], w_router.shape[1]
    s = jax.nn.softmax(b @ w_router, axis=-1)
    chosen, idx = jax.lax.top_k(s, top_k)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], idx].set(chosen)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, n_heads, n_kv, head_dim,
            theta, eps, top_k, block, rows, faults=()):
    """Logits ``[N, R, vocab]`` of ``N`` sequences of one length ``ids [N,
    T]`` at each one's positions ``rows [N, R]``.

    ``layers`` yields, per layer, ``(attention weights, mlp_norm, w_router
    [h, E], experts)``, ``experts`` iterating ``(e, w_gate, w_up, w_down)``
    over all ``E``. A piece at a time, as in ``reference.qwen2.forward``."""
    low = fp8 if "fp8" in faults else (lambda t: t)
    attn = jax.jit(jax.vmap(lambda x, w: attention(
        x, w, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim, theta=theta, eps=eps,
        block=1 if "causal" in faults else block, low=low), in_axes=(0, None)))
    route = jax.jit(lambda b, w_router: routing_weights(b, w_router, top_k=top_k))
    expert = jax.jit(lambda b, w, g, u, d: w[:, None] * mlp_block(b, g, u, d))
    ids, rows = jnp.asarray(ids), jnp.asarray(rows)
    N, T = ids.shape
    with jax.default_matmul_precision("highest"):
        x = embed[ids].astype(jnp.float32)                       # [N, T, h]
        for w_attn, mlp_norm, w_router, experts in layers:
            x = attn(x, w_attn)
            b = rms_norm(x, mlp_norm, eps).reshape(N * T, -1)
            weights = route(b, w_router)
            b, out = low(b), jnp.zeros_like(b)
            for e, w_gate, w_up, w_down in experts:
                out = out + expert(b, weights[:, e], w_gate, w_up, w_down)
            x = x + out.reshape(x.shape)
        x = low(rms_norm(jnp.take_along_axis(x, rows[:, :, None], axis=1), final_norm, eps))
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
