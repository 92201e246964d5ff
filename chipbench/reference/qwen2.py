"""Plain reference of the Qwen2 dense decoder (Qwen2.5 technical report;
``modeling_qwen2.py`` of the published checkpoints): straight
``jax.numpy`` in float32 at ``highest`` matmul precision, full causal
attention over the whole sequence, no cache, no kernels, no batching.

    x   = embed[ids]
    per layer:
      y   = rmsnorm(x) * w_attn_norm
      q,k,v = y Wq + bq, y Wk + bk, y Wv + bv         (bias on q,k,v only)
      q,k = rope(q), rope(k)                          (rotate-half, theta)
      a   = softmax(q k^T / sqrt(d) + causal) v       (grouped-query: each
                                                       kv head serves
                                                       n_heads/n_kv heads)
      x   = x + a Wo
      y   = rmsnorm(x) * w_mlp_norm
      x   = x + (silu(y Wg) * (y Wu)) Wd
    logits = (rmsnorm(x) * w_final_norm) W_lm         (W_lm = embed^T if tied)

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout; :mod:`chipbench.reference.check` produces them from the
engine's parameter tree. Departures from the published model: none in
the mathematics. Weights are random (from the seed), and where the
configuration serves int8 weights the reference uses the same
de-quantised values (int8 x per-channel scale, exact in float32), so it
is the reference of the model as quantised, not of a bf16 original.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta):
    """x [T, n, d]; rotate-half convention of the published code."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * cos + rotated * sin


def attention(x, w, *, n_heads, n_kv, head_dim, theta, eps):
    """x + attention(rmsnorm(x)) over a whole sequence x [T, h] (float32)."""
    T = x.shape[0]
    y = rms_norm(x, w["attn_norm"], eps)
    q = (y @ w["wq"] + w["bq"]).reshape(T, n_heads, head_dim)
    k = (y @ w["wk"] + w["bk"]).reshape(T, n_kv, head_dim)
    v = (y @ w["wv"] + w["bv"]).reshape(T, n_kv, head_dim)
    pos = jnp.arange(T)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + attn.reshape(T, n_heads * head_dim) @ w["wo"]


def mlp_block(y, w_gate, w_up, w_down):
    """One block of columns of the gated MLP: (silu(y Wg) * (y Wu)) Wd
    with ``Wg, Wu [h, c]`` and ``Wd [c, h]``. The MLP is the sum of its
    blocks over the intermediate dimension."""
    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def forward(ids, embed, layers, final_norm, lm_head_chunks, *,
            n_heads, n_kv, head_dim, theta, eps, rows):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(attention weights, mlp_norm, iterator
    of (w_gate, w_up, w_down) column blocks)``, and ``lm_head_chunks``
    yields ``[h, v_chunk]`` slices of the output matrix: the reference
    runs inside the worker, beside 13.8 GB of weights and cache, so it
    holds a few hundred MB of float32 weights at a time and never a whole
    layer (0.93 GB at 7B widths). Summing the MLP over column blocks and
    concatenating the logits over vocabulary slices are the same
    mathematics as the unsplit products."""
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim, theta=theta, eps=eps))
    block = jax.jit(mlp_block)
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for w_attn, mlp_norm, blocks in layers:
            x = attn(x, w_attn)
            y = rms_norm(x, mlp_norm, eps)
            for w_gate, w_up, w_down in blocks:
                x = x + block(y, w_gate, w_up, w_down)
        x = rms_norm(x[jnp.asarray(rows)], final_norm, eps)
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
