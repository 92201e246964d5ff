"""Plain reference of Laguna-S-2.1 (poolside, ``model_type`` "laguna"; the
published ``config.json``, whose keys name every mechanism below): straight
``jax.numpy`` in float32 at ``highest`` matmul precision over one whole
sequence, no cache, no pool, no table, no kernels, no batching, no dispatch.

    x = embed[ids]
    per layer l (pre-norm residual blocks; no bias anywhere), n_l =
    num_attention_heads_per_layer[l] query heads, 8 KV heads, d = 128:
      a = rmsnorm(x) * w_in
      q, k, v = a Wq, a Wk, a Wv                          n_l, n_kv, n_kv heads of d
      q, k = rope_l(q), rope_l(k)                         by layer_types[l]:
        sliding_attention: theta 10,000 over all d values of a head
        full_attention:    the first d/2 values rotated (partial_rotary_factor
                           0.5), the others passed through; YaRN frequencies
                           over those d/2 (theta 500,000, factor 128, original
                           8,192, beta_fast 32, beta_slow 1); cos and sin times
                           attention_factor
      score = q_h . k_(h // (n_l / n_kv)) * d^-0.5        causal; on a
        sliding_attention layer a query at p sees keys p - window + 1 .. p
      o_h = softmax(score) v                              float32
      g   = sigmoid(a Wg)                                 [n_l]: one a head, from
                                                          the SAME normed input
      x   = x + concat(g_h o_h) Wo
      b = rmsnorm(x) * w_post
      mlp_layer_types[l] == "dense":  x = x + SwiGLU(b)   width intermediate_size
      else:
        sc  = sigmoid(b Wr)                               [E], float32
        chosen = the k highest sc                         no bias, no groups, no soft cap
        w_e = sc_e / sum(sc_chosen) * moe_routed_scaling_factor    on the expert's OUTPUT
        x   = x + sum_{e chosen AND held} w_e SwiGLU_e(b) + SwiGLU_shared(b)
    logits = (rmsnorm(x) * w_final) W_lm                  untied

**The share.** ``held = (lo, hi)``: only the routed experts ``lo .. hi-1``
add their terms (the router still scores and chooses among all ``E``); what
the others would add is left out and that partial result goes on, as on one
chip of a deployment that spreads each layer's experts over several. With
``held = (0, E)`` this is the uncut layer. ``shared=False`` leaves the shared
expert out (for the test that adds the shares up and counts it once).

Departures from the published model: none in the mathematics as the
configuration's ``assumed`` reads it (the gate's nonlinearity and input, the
router's sigmoid, half-split rope pairs: each in ONE place here); weights
are random, from the seed. ``faults`` names mechanisms to leave out, for the
comparisons that must then FAIL: "window" (a window layer attends the whole
context), "gate" (no output gate). "fp8" leaves nothing out: it is the
CONTROL of a bfloat16 configuration (``reference/control.py``), the same
mathematics in the nearest precision below the one the configuration
states: every activation that enters a weight product (the two normed
inputs of a layer, the heads' output into ``Wo``, the final normed row) and
K and V as a cache would hold them, rounded to ``float8_e4m3fn``; weights,
the router's input, softmax and the sums stay float32.

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout from ``chipbench/architectures/laguna.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1 import yarn_inv_freq
from chipbench.reference.qwen2 import mlp_block, rms_norm


def rope(x, positions, rp: dict):
    """x [T, n, d] rotated by the rope parameters ``rp`` of its layer kind:
    the first ``d x partial_rotary_factor`` values of a head in half-split
    pairs, the rest passed through."""
    d = x.shape[-1]
    r = int(d * rp.get("partial_rotary_factor", 1))
    yarn = rp.get("rope_type", "default") == "yarn"
    inv_freq = yarn_inv_freq(r, float(rp["rope_theta"]), rp if yarn else {})
    m = float(rp.get("attention_factor", 1.0)) if yarn else 1.0
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * m
    xr = x[..., :r]
    x1, x2 = xr[..., : r // 2], xr[..., r // 2:]
    rotated = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([rotated, x[..., r:]], -1)


def gate_of(a, w_gate):
    """The per-head output gate [T, n]: sigmoid of the layer's normed input
    times ``Wg`` (the configuration's ``assumed.gating``)."""
    return jax.nn.sigmoid(a @ w_gate)


def fp8(t):
    """``t`` as an e4m3 value would hold it, in float32 again."""
    return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def attention(x, w, *, n_kv, head_dim, rp, window, eps, gated=True, low=lambda t: t):
    """x + attention(rmsnorm(x)) over a whole sequence x [T, h] (float32);
    ``window`` None for a full layer; ``low`` rounds what the control keeps
    in a lower precision."""
    T = x.shape[0]
    a = low(rms_norm(x, w["attn_norm"], eps))
    q = (a @ w["wq"]).reshape(T, -1, head_dim)
    k = (a @ w["wk"]).reshape(T, n_kv, head_dim)
    v = low((a @ w["wv"]).reshape(T, n_kv, head_dim))
    pos = jnp.arange(T)
    q, k = rope(q, pos, rp), low(rope(k, pos, rp))
    seen = pos[:, None] >= pos[None, :]
    if window is not None:
        seen = seen & (pos[None, :] > pos[:, None] - window)

    def kv_head(qkv):
        # one KV head and the n_l / n_kv query heads it serves: a block of the
        # heads at a time, so that a long sequence's scores fit beside the
        # engine's weights and cache (the same numbers as all heads at once)
        q_g, k_g, v_g = qkv                      # [T, group, d], [T, d], [T, d]
        scores = jnp.einsum("qgd,kd->gqk", q_g, k_g) * head_dim ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v_g)

    q_by_kv = q.reshape(T, n_kv, -1, head_dim).transpose(1, 0, 2, 3)
    o = jax.lax.map(kv_head, (q_by_kv, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, -1, head_dim)     # [T, n_l, d], head h = g * group + j
    if gated:
        o = o * gate_of(a, w["wg"])[:, :, None]
    return x + low(o.reshape(T, -1)) @ w["wo"]


def routing_weights(b, w_router, *, top_k, scale):
    """[T, E]: each token's weight for each expert, zero where not chosen."""
    T, E = b.shape[0], w_router.shape[1]
    sc = jax.nn.sigmoid(b @ w_router)
    _, idx = jax.lax.top_k(sc, top_k)
    chosen = jnp.take_along_axis(sc, idx, axis=1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], idx].set(chosen)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, n_kv, head_dim, rope_by_kind,
            window, eps, top_k, scale, held, rows, shared=True, faults=()):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(kind, attention weights, mlp_norm,
    mlp)``: ``kind`` the published "full_attention" or "sliding_attention";
    ``mlp`` either ``("dense", blocks)`` or ``("sparse", w_router [h, E],
    experts, shared_blocks)`` as in ``reference.axk1.forward``. A piece at a
    time, as in ``reference.qwen2.forward``."""
    low = fp8 if "fp8" in faults else (lambda t: t)

    def attn_of(kind):
        win = window if kind == "sliding_attention" and "window" not in faults else None
        return jax.jit(lambda x, w: attention(
            x, w, n_kv=n_kv, head_dim=head_dim, rp=rope_by_kind[kind], window=win,
            eps=eps, gated="gate" not in faults, low=low))

    attn = {kind: attn_of(kind) for kind in rope_by_kind}
    route = jax.jit(lambda b, w_router: routing_weights(b, w_router, top_k=top_k, scale=scale))
    block = jax.jit(mlp_block)
    expert = jax.jit(lambda b, w, g, u, d: w[:, None] * mlp_block(b, g, u, d))
    lo, hi = held
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for kind, w_attn, mlp_norm, mlp in layers:
            x = attn[kind](x, w_attn)
            b = rms_norm(x, mlp_norm, eps)
            if mlp[0] == "dense":
                for w_gate, w_up, w_down in mlp[1]:
                    x = x + block(low(b), w_gate, w_up, w_down)
                continue
            _, w_router, experts, shared_blocks = mlp
            weights = route(b, w_router)
            b = low(b)
            for e, w_gate, w_up, w_down in experts:
                if lo <= e < hi:
                    x = x + expert(b, weights[:, e], w_gate, w_up, w_down)
            for w_gate, w_up, w_down in shared_blocks:
                if shared:
                    x = x + block(b, w_gate, w_up, w_down)
        x = low(rms_norm(x[jnp.asarray(rows)], final_norm, eps))
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
