"""Plain reference of MiMo-V2.5 (Xiaomi, ``model_type`` "mimo_v2"; the
published ``config.json``, whose keys name every mechanism below): straight
``jax.numpy`` in float32 at ``highest`` matmul precision over one whole
sequence, no cache, no pool, no table, no kernels, no batching, no dispatch.

    x = embed[ids]
    per layer l (pre-norm residual blocks; no bias, no QK-norm), 64 query
    heads; hybrid_layer_pattern[l] 0: FULL, n_kv = 4, theta 10,000,000;
    1: WINDOW, n_kv = 8, theta 10,000:
      y = rmsnorm(x; eps 1e-5)
      q, k = y Wq, y Wk                                   64, n_kv heads of 192
      v    = 0.707 * (y Wv)                               n_kv heads of 128
      q, k = rope(q), rope(k)                             the FIRST 64 values of a
        head (int(192 * 0.334)) in half-split pairs, the kind's theta, plain;
        the other 128 passed through
      s[i, j] = q_i . k_j / sqrt(192)                     head h against KV head
        h // (64 / n_kv); full: j <= i; window: i - 127 <= j <= i
      full:   p = softmax_j(s)
      window: p[i, j] = exp(s[i, j] - m) / (exp(b_h - m) + sum_j' exp(s[i, j'] - m)),
              b_h the head's learned sink: a column of the softmax with no value
      o_i = sum_j p[i, j] v_j                             [64, 128]
      x   = x + concat(o) Wo                              Wo [8192, 4096]
      z = rmsnorm(x)
      layer 0:  x = x + SwiGLU(z)                         width 16,384
      others:
        sc  = sigmoid(z Wr)                               [256], float32
        chosen = the 8 highest of sc + bias               e_score_correction_bias;
                                                          n_group 1: no groups
        w_e = sc_e / sum(sc_chosen)                       routed_scaling_factor null = 1
        x   = x + sum_{e chosen AND held} w_e SwiGLU_e(z) width 2,048; no shared expert
    logits = (rmsnorm(x) * w_final) W_lm                  untied

**The share.** ``held = (lo, hi)``: only the routed experts ``lo .. hi-1``
add their terms (the router still scores and chooses among all ``E``); what
the others would add is left out and that partial result goes on, as on one
chip of a deployment that spreads each layer's experts over several. With
``held = (0, E)`` this is the uncut layer.

Departures from the published model: none in the mathematics as the
configuration's ``assumed`` reads it (each in ONE place here); weights are
random, from the seed; no vision or audio tower and no MTP layer (the
catalog's copy has no key of either). ``faults`` names mechanisms to leave
out, for the comparisons that must then FAIL: "sink" (no sink: a plain
softmax on window layers too), "v_scale" (values unscaled), "window" (a
window layer attends the whole context), "wide_key" (the key's and the
query's last 64 values dropped from the scores). "fp8" leaves nothing out:
it is the CONTROL of a bfloat16 configuration (``reference/control.py``),
the same mathematics in the nearest precision below the one the
configuration states: every activation that enters a weight product (the two
normed inputs of a layer, the heads' output into ``Wo``, the final normed
row) and K and V as a cache would hold them, rounded to ``float8_e4m3fn``;
weights, the router's input, softmax and the sums stay float32.

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout from ``chipbench/architectures/mimo_v2.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.laguna import fp8, rope
from chipbench.reference.lfm2_moe import routing_weights
from chipbench.reference.qwen2 import mlp_block, rms_norm


def attention(x, w, *, n_kv, head_dim, v_head_dim, rp, window, eps, value_scale,
              scored=None, low=lambda t: t):
    """x + attention(rmsnorm(x)) over a whole sequence x [T, h] (float32);
    ``window`` None for a full layer; ``w["sink"]`` [heads] or None;
    ``scored`` the leading values of a head the scores read (None: all);
    ``low`` rounds what the control keeps in a lower precision."""
    T = x.shape[0]
    a = low(rms_norm(x, w["attn_norm"], eps))
    q = (a @ w["wq"]).reshape(T, -1, head_dim)
    k = (a @ w["wk"]).reshape(T, n_kv, head_dim)
    v = low(value_scale * (a @ w["wv"]).reshape(T, n_kv, v_head_dim))
    pos = jnp.arange(T)
    q, k = rope(q, pos, rp), low(rope(k, pos, rp))
    if scored is not None:
        q, k = q[..., :scored], k[..., :scored]
    seen = pos[:, None] >= pos[None, :]
    if window is not None:
        seen = seen & (pos[None, :] > pos[:, None] - window)
    # the sink: one more column of each head's softmax, which carries no value
    sink = w.get("sink")
    sink = jnp.full((q.shape[1],), -jnp.inf) if sink is None else sink

    def kv_head(args):
        # one KV head and the 64 / n_kv query heads it serves at a time, so
        # that a long sequence's scores fit (the same numbers as all at once)
        q_g, k_g, v_g, b_g = args                 # [T, group, d], [T, d], [T, dv], [group]
        scores = jnp.einsum("qgd,kd->gqk", q_g, k_g) * head_dim ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        b = jnp.broadcast_to(b_g[:, None, None], (*scores.shape[:2], 1))
        p = jax.nn.softmax(jnp.concatenate([scores, b], axis=-1), axis=-1)[..., :T]
        return jnp.einsum("gqk,kd->qgd", p, v_g)

    group = q.shape[1] // n_kv
    o = jax.lax.map(kv_head, (
        q.reshape(T, n_kv, group, -1).transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
        v.transpose(1, 0, 2), sink.reshape(n_kv, group)))
    o = o.transpose(1, 0, 2, 3).reshape(T, -1)               # head h = g * group + j
    return x + low(o) @ w["wo"]


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, kv_heads, head_dim,
            v_head_dim, rope_by_kind, window, eps, top_k, value_scale, held, rows, faults=()):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(kind, attention weights, mlp_norm,
    mlp)``: ``kind`` "full_attention" or "sliding_attention"; ``kv_heads``
    the KV heads of each kind; ``mlp`` either ``("dense", blocks)`` or
    ``("sparse", w_router [h, E], bias [E], experts)`` as in
    ``reference.lfm2_moe.forward``. A piece at a time, as in
    ``reference.qwen2.forward``."""
    low = fp8 if "fp8" in faults else (lambda t: t)

    def attn_of(kind):
        win = window if kind == "sliding_attention" and "window" not in faults else None
        return jax.jit(lambda x, w: attention(
            x, w, n_kv=kv_heads[kind], head_dim=head_dim, v_head_dim=v_head_dim,
            rp=rope_by_kind[kind], window=win, eps=eps,
            value_scale=1.0 if "v_scale" in faults else value_scale,
            scored=v_head_dim if "wide_key" in faults else None, low=low))

    attn = {kind: attn_of(kind) for kind in rope_by_kind}
    route = jax.jit(lambda z, w_router, bias: routing_weights(
        z, w_router, bias, top_k=top_k, scale=1.0, norm_eps=0.0))
    block = jax.jit(mlp_block)
    expert = jax.jit(lambda z, w, g, u, d: w[:, None] * mlp_block(z, g, u, d))
    lo, hi = held
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for kind, w_attn, mlp_norm, mlp in layers:
            if "sink" in faults:
                w_attn = {**w_attn, "sink": None}
            x = attn[kind](x, w_attn)
            z = rms_norm(x, mlp_norm, eps)
            if mlp[0] == "dense":
                for w_gate, w_up, w_down in mlp[1]:
                    x = x + block(low(z), w_gate, w_up, w_down)
                continue
            _, w_router, bias, experts = mlp
            weights = route(z, w_router, bias)
            z = low(z)
            for e, w_gate, w_up, w_down in experts:
                if lo <= e < hi:
                    x = x + expert(z, weights[:, e], w_gate, w_up, w_down)
        x = low(rms_norm(x[jnp.asarray(rows)], final_norm, eps))
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
