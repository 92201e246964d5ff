"""Plain reference of the Mixtral sparse decoder (Jiang et al., "Mixtral
of Experts", 2024; ``modeling_mixtral.py`` of the published checkpoints):
the attention block is the dense decoder's without a bias (computed by
``reference.qwen2.attention`` with zero biases); the MLP of every layer is
a router over ``E`` expert SwiGLUs:

    r   = y Wr                                   router logits over all E experts
    top = the k largest of r, per token
    w   = softmax(r[top])                        over the chosen only (the published
                                                 code's softmax over all, renormalised
                                                 over the chosen: the same numbers)
    x   = x + sum_{e in top} w_e (silu(y Wg_e) * (y Wu_e)) Wd_e

Straight ``jax.numpy`` in float32 at ``highest`` precision: every expert
runs on every token and the weights of those not chosen are zero. No
dispatch, no capacity, no token is dropped. Sliding-window attention,
which some published checkpoints state, is not modelled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.qwen2 import attention, mlp_block, rms_norm


def routing_weights(router_logits, top_k: int):
    """[T, E]: each token's weight for each expert, zero where not chosen."""
    vals, idx = jax.lax.top_k(router_logits, top_k)
    chosen = jax.nn.softmax(vals, axis=-1)
    rows = jnp.arange(router_logits.shape[0])[:, None]
    return jnp.zeros_like(router_logits).at[rows, idx].set(chosen)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *,
            n_heads, n_kv, head_dim, theta, eps, top_k, rows):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(attention weights, mlp_norm, w_router
    [h, E], iterator of the experts' (w_gate, w_up, w_down))``, a piece at
    a time as in ``reference.qwen2.forward``."""
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim, theta=theta, eps=eps))
    route = jax.jit(lambda y, w_router: routing_weights(y @ w_router, top_k))
    expert = jax.jit(lambda y, w, g, u, d: w[:, None] * mlp_block(y, g, u, d))
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for w_attn, mlp_norm, w_router, experts in layers:
            x = attn(x, w_attn)
            y = rms_norm(x, mlp_norm, eps)
            weights = route(y, w_router)
            for e, (w_gate, w_up, w_down) in enumerate(experts):
                x = x + expert(y, weights[:, e], w_gate, w_up, w_down)
        x = rms_norm(x[jnp.asarray(rows)], final_norm, eps)
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
