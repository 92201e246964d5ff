"""Plain reference of Olmo-Hybrid-7B (allenai, ``model_type`` "olmo_hybrid";
the published ``config.json``, whose ``linear_*`` keys are those of the
``GatedDeltaNet`` block in the flash-linear-attention code the family builds
on, and Olmo 2/3's block): straight ``jax.numpy`` in float32 at ``highest``
matmul precision over one whole sequence, the recurrence TOKEN BY TOKEN
(``lax.scan`` over positions), no cache, no slab, no chunked form, no
kernels, no batching, no dispatch.

    x = embed[ids]
    per layer l (the norm is on each sub-layer's OUTPUT; no bias anywhere):
      layer_types[l] == "linear_attention":                 gated delta rule, H heads
        [q~ | k~ | v~] = x [Wq | Wk | Wv]                   H dk, H dk, H dv wide
        c_t = silu(sum_{j=0..K-1} w_j c~_{t-(K-1)+j})       depthwise over the channels of
                                                            [q~ | k~ | v~], causal, zero before
                                                            position 0; K = 4 taps; then split
        q = q / sqrt(|q|^2 + 1e-6) * dk^-1/2 ; k = k / sqrt(|k|^2 + 1e-6)    per head
        beta_t  = 2 sigmoid(x Wb)                           per head (linear_allow_neg_eigval)
        alpha_t = exp(-exp(A_log) softplus(x Wa + dt_bias)) per head, in (0, 1)
        S' = alpha_t S_{t-1} ; u_t = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u_t^T ; o_t = S_t^T q_t              S [dk, dv] a head, zero before 0
        y = rmsnorm_dv(o) * w_o_norm * silu(x Wz)           per head
        x = x + rmsnorm(y Wo) * w_post_attention
      layer_types[l] == "full_attention":                   multi-head, NO rotary embedding
        q = rmsnorm(x Wq) * w_q_norm ; k = rmsnorm(x Wk) * w_k_norm    over the WHOLE projection
        v = x Wv
        o = softmax(q k^T * d^-0.5 + causal) v              per head
        x = x + rmsnorm(concat(o) Wo) * w_post_attention
      x = x + rmsnorm(SwiGLU(x)) * w_post_feedforward       SwiGLU(x) = (silu(x Wg) * (x Wu)) Wd
    logits = (rmsnorm(x) * w_final_norm) W_lm               W_lm untied

Departures from the published model: (1) the norm's placement, the
whole-projection QK-norm, the convolution without bias and with SiLU, the
L2 norm's 1e-6 and the float32 state are the family's conventions, not keys
of the catalog's copy of the file (the configuration's ``assumed``); (2) the
depthwise convolution is written as its ``K`` shifted products and not as a
padded ``conv1d``: the same numbers; (3) weights are random, from the seed,
the decays drawn to spread over about 0.9-0.999. None in the mathematics.

``faults`` names what a CONTROL changes, for the comparisons that must come
out as not correct (``chipbench/reference/control.py``): "fp8" leaves
nothing out, it is the reference one precision below the configuration's
(what each mixer reads and gives, and q, k, v, rounded through e4m3);
"decay" takes alpha as 1; "neg_eigval" takes beta without its factor 2;
"state_bf16" rounds the state to bfloat16 after every update (one
precision below the float32 the configuration states for it).

Weights arrive a layer at a time in the published (unfused) layout from
``chipbench/architectures/olmo_hybrid.py``: the matrices as the engine holds
them (bfloat16 on the chip) and made float32 INSIDE each jitted piece, where
the conversion feeds its product and is never a copy of the whole matrix; the
small leaves float32. The reference runs inside the worker beside 14 GB of
weights, pages and slab: a linear layer's matrices as float32 copies are
0.35 GB, two layers' worth and the sliced leaves behind them were 1.47 GB at
once and WERE the worker's peak (the v5e, PR 50, call r6), so ``forward``
also lets a layer's pieces go before it asks for the next.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.qwen2 import mlp_block, rms_norm

FAULTS = ("fp8", "decay", "neg_eigval", "state_bf16")
L2_EPS = 1e-6


def fp8(t):
    """``t`` as an e4m3 value would hold it, in float32 again."""
    return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def bf16(t):
    """``t`` as a bfloat16 value would hold it, in float32 again
    (``reduce_precision``: a pair of converts the compiler may drop as excess
    precision, and did on the v5e)."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def delta_rule(q, k, v, alpha, beta, *, keep=lambda S: S):
    """The recurrence over one sequence, a position a turn. ``q``, ``k``
    ``[T, H, dk]``, ``v [T, H, dv]``, ``alpha``, ``beta`` ``[T, H]``; ``o [T,
    H, dv]``. ``keep``: what the state is rounded to after every update (the
    ``state_bf16`` control)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        q, k, v, alpha, beta = x
        S = alpha[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S))
        S = keep(S + k[:, :, None] * u[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q, S)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta))
    return o


def linear_mixer(x, w, *, H, dk, dv, eps, neg_eigval=True, faults=()):
    """x + rmsnorm(gated_delta_rule(x)) over a whole sequence x [T, h]
    (float32). ``w["conv_w"]`` [K, channels]: tap ``j`` multiplies the input
    ``K - 1 - j`` positions back (the published ``conv1d.weight[:, 0, j]``)."""
    low = fp8 if "fp8" in faults else (lambda t: t)
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    T = x.shape[0]
    a = low(x)
    pre = jnp.concatenate([a @ w["wq"], a @ w["wk"], a @ w["wv"]], axis=-1)
    K = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), pre.dtype), pre], axis=0)
    c = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + T] for j in range(K)))
    q = low(_l2(c[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5)
    k = low(_l2(c[:, H * dk:2 * H * dk].reshape(T, H, dk)))
    v = low(c[:, 2 * H * dk:].reshape(T, H, dv))
    beta = jax.nn.sigmoid(a @ w["wb"]) * (2.0 if neg_eigval and "neg_eigval" not in faults else 1.0)
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(a @ w["wa"] + w["dt_bias"]))
    if "decay" in faults:
        alpha = jnp.ones_like(alpha)
    o = delta_rule(q, k, v, alpha, beta, **({"keep": bf16} if "state_bf16" in faults else {}))
    y = rms_norm(o, w["o_norm"], eps) * jax.nn.silu(a @ w["wz"]).reshape(T, H, dv)
    return x + rms_norm(low(y.reshape(T, H * dv)) @ w["wo"], w["post_norm"], eps)


def attention(x, w, *, n_heads, head_dim, eps, faults=()):
    """x + rmsnorm(attention(x)) over a whole sequence x [T, h] (float32):
    multi-head, q and k normed over the whole projection, no rope."""
    low = fp8 if "fp8" in faults else (lambda t: t)
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    T = x.shape[0]
    a = low(x)
    q = rms_norm(a @ w["wq"], w["q_norm"], eps).reshape(T, n_heads, head_dim)
    k = low(rms_norm(a @ w["wk"], w["k_norm"], eps)).reshape(T, n_heads, head_dim)
    v = low(a @ w["wv"]).reshape(T, n_heads, head_dim)
    pos = jnp.arange(T)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + rms_norm(low(o.reshape(T, n_heads * head_dim)) @ w["wo"], w["post_norm"], eps)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, n_heads, head_dim, H, dk, dv,
            eps, neg_eigval, rows, faults=()):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(kind, mixer weights, ffn post norm,
    blocks)``: ``kind`` "linear_attention" or "full_attention", ``blocks``
    iterating ``(w_gate, w_up, w_down)`` float32 column blocks of the SwiGLU.
    A piece at a time, as in ``reference.qwen2.forward``, and a layer's
    pieces let go before the next layer's are asked for."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"olmo_hybrid reference: unknown faults {sorted(unknown)}; it names {FAULTS}")
    lin = jax.jit(lambda x, w: linear_mixer(
        x, w, H=H, dk=dk, dv=dv, eps=eps, neg_eigval=neg_eigval, faults=faults))
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, head_dim=head_dim, eps=eps, faults=faults))
    block = jax.jit(mlp_block)
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        layers = iter(layers)
        while (piece := next(layers, None)) is not None:
            kind, w_mixer, ffn_norm, blocks = piece
            x = lin(x, w_mixer) if kind == "linear_attention" else attn(x, w_mixer)
            m = 0.0
            for w_gate, w_up, w_down in blocks:
                m = m + block(x, w_gate, w_up, w_down)
            x = x + rms_norm(m, ffn_norm, eps)
            del piece, w_mixer, blocks      # before the next layer's pieces are made
        x = rms_norm(x[jnp.asarray(rows)], final_norm, eps)
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
