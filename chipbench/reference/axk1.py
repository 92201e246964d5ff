"""Plain reference of A.X-K1 (SKT, ``model_type`` "axk1"; the published
``config.json`` and the DeepSeek-V3 family's modelling code it follows):
straight ``jax.numpy`` in float32 at ``highest`` matmul precision, full
causal attention over the whole sequence with every head EXPANDED, no
cache, no absorbed projections, no kernels, no batching, no dispatch.

    h = embed[ids]
    per layer:
      y   = rmsnorm(h) * w_attn_norm
      cq  = rmsnorm(y Wqa) * w_q_norm                     [rq]
      q   = cq Wqb  -> per head [q_nope (dn), q_rope (dr)]
      [ckv (rkv), kr (dr)] = y Wkva ; ckv = rmsnorm(ckv) * w_kv_norm
      q_rope, kr = rope(q_rope), rope(kr)                 one kr for all heads
      [k_nope (dn), v (dv)] per head = ckv Wkvb
      score = (q_nope . k_nope + q_rope . kr) * s ; causal softmax ; o = P v
      h   = h + concat(o) Wo
      y   = rmsnorm(h) * w_mlp_norm
      layer < first_k_dense_replace:  h = h + SwiGLU(y)   width intermediate_size
      else:
        sc  = sigmoid(y Wg)                               [E], float32
        a group's score = the sum of its two highest sc   n_group groups of E / n_group
        keep the topk_group best groups; choose the k highest sc among their experts
        w_e = sc_e / sum(sc_chosen) * routed_scaling_factor
        h   = h + sum_{e chosen AND held} w_e SwiGLU_e(y) + SwiGLU_shared(y)
    logits = (rmsnorm(h) * w_final_norm) W_lm

Rope is YaRN's, static: pair ``i`` of the ``dr / 2`` turns at
``theta^(-2i/dr)`` blended with itself over ``factor`` by the linear ramp
between the correction dims of ``beta_fast`` and ``beta_slow``
(``yarn_find_correction_range``); cos and sin are scaled by ``mscale(factor,
mscale) / mscale(factor, mscale_all_dim)``; ``s = (dn + dr)^-0.5 *
mscale(factor, mscale_all_dim)^2`` with ``mscale(f, m) = 0.1 m ln f + 1``.

**The share.** ``held = (lo, hi)``: only the routed experts ``lo .. hi-1``
add their terms (the router still scores and chooses among all ``E``); what
the others would add is left out and that partial result goes on, as on one
chip of a deployment that spreads each layer's experts over several. With
``held = (0, E)`` this is the uncut layer. Every expert runs on every token
here and the weights of those not chosen are zero: nothing is dropped.

Departures from the published model: (1) ``topk_method`` "none" is read as
"no bias on the choice" (the family's "noaux_tc" adds a learned
``e_score_correction_bias`` to the scores it chooses by; this file has none);
(2) the rope pairs are the half-split ones (``x[i]`` with ``x[i + dr/2]``),
the published code's interleaved pairs after one permutation of the columns
of ``Wqb``'s and ``Wkva``'s rope parts, the same permutation in the program;
(3) weights are random, from the seed. None in the mathematics.

Weights arrive a piece at a time as float32 arrays in the published
(unfused) layout from ``chipbench/architectures/axk1.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.qwen2 import mlp_block, rms_norm


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, scaling: dict):
    """[d/2] frequencies; plain rope's where ``scaling`` is empty."""
    base = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not scaling:
        return base
    factor, orig = scaling["factor"], scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return base / factor * ramp + base * (1 - ramp)


def rope(x, positions, theta, scaling):
    """x [T, n, d]; half-split pairs; YaRN frequencies and cos/sin scale."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(d, theta, scaling)[None, :]
    m = 1.0
    if scaling:
        m = (mscale(scaling["factor"], scaling.get("mscale", 1))
             / mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * m
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def softmax_scale(dn: int, dr: int, scaling: dict) -> float:
    s = (dn + dr) ** -0.5
    if scaling and scaling.get("mscale_all_dim", 0):
        s *= mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return s


def attention(x, w, *, n_heads, dn, dr, dv, rkv, theta, scaling, eps):
    """x + attention(rmsnorm(x)) over a whole sequence x [T, h] (float32),
    every head's K and V expanded from the compressed vector."""
    T = x.shape[0]
    pos = jnp.arange(T)
    y = rms_norm(x, w["attn_norm"], eps)
    q = (rms_norm(y @ w["wq_a"], w["q_norm"], eps) @ w["wq_b"]).reshape(T, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, theta, scaling)
    kva = y @ w["wkv_a"]
    ckv = rms_norm(kva[:, :rkv], w["kv_norm"], eps)
    kr = rope(kva[:, None, rkv:], pos, theta, scaling)          # [T, 1, dr]
    kv = (ckv @ w["wkv_b"]).reshape(T, n_heads, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, kr[:, 0])) * softmax_scale(dn, dr, scaling)
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + o.reshape(T, n_heads * dv) @ w["wo"]


def routing_weights(y, w_router, *, n_group, topk_group, top_k, scale):
    """[T, E]: each token's weight for each expert, zero where not chosen."""
    T, E = y.shape[0], w_router.shape[1]
    sc = jax.nn.sigmoid(y @ w_router)
    rows = jnp.arange(T)[:, None]
    allowed = jnp.ones((T, E), bool)
    if n_group > 1:
        grp = sc.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(grp, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        kept = jnp.zeros((T, n_group), bool).at[rows, best].set(True)
        allowed = jnp.repeat(kept, E // n_group, axis=1)
    _, idx = jax.lax.top_k(jnp.where(allowed, sc, -jnp.inf), top_k)
    chosen = jnp.take_along_axis(sc, idx, axis=1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return jnp.zeros((T, E), jnp.float32).at[rows, idx].set(chosen)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, n_heads, dn, dr, dv,
            rkv, theta, scaling, eps, n_group, topk_group, top_k, scale, held, rows):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(attention weights, mlp_norm, mlp)``
    with ``mlp`` either ``("dense", blocks)`` or ``("sparse", w_router [h,
    E], experts, shared_blocks)``: ``blocks`` iterate ``(w_gate, w_up,
    w_down)`` column blocks of one SwiGLU, ``experts`` iterates ``(e,
    w_gate, w_up, w_down)`` over the experts whose weights exist here,
    ``e`` the expert's index among all ``E``. An expert outside ``held =
    (lo, hi)`` is skipped; one inside it that ``experts`` does not yield
    is an error of the caller's, not checked here. A piece at a time, as
    in ``reference.qwen2.forward``."""
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, dn=dn, dr=dr, dv=dv, rkv=rkv, theta=theta,
        scaling=scaling, eps=eps))
    route = jax.jit(lambda y, w_router: routing_weights(
        y, w_router, n_group=n_group, topk_group=topk_group, top_k=top_k, scale=scale))
    block = jax.jit(mlp_block)
    expert = jax.jit(lambda y, w, g, u, d: w[:, None] * mlp_block(y, g, u, d))
    lo, hi = held
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        for w_attn, mlp_norm, mlp in layers:
            x = attn(x, w_attn)
            y = rms_norm(x, mlp_norm, eps)
            if mlp[0] == "dense":
                for w_gate, w_up, w_down in mlp[1]:
                    x = x + block(y, w_gate, w_up, w_down)
                continue
            _, w_router, experts, shared_blocks = mlp
            weights = route(y, w_router)
            for e, w_gate, w_up, w_down in experts:
                if lo <= e < hi:
                    x = x + expert(y, weights[:, e], w_gate, w_up, w_down)
            for w_gate, w_up, w_down in shared_blocks:
                x = x + block(y, w_gate, w_up, w_down)
        x = rms_norm(x[jnp.asarray(rows)], final_norm, eps)
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
