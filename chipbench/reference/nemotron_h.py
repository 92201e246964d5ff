"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type``
"nemotron_h"; the published ``config.json`` and the family's modelling code:
a Mamba-2 mixer as the ``mamba_ssm`` reference writes it, DeepSeek-V3's
router): straight ``jax.numpy`` in float32 at ``highest`` matmul precision
over one whole sequence, the recurrence TOKEN BY TOKEN (``lax.scan`` over
positions), no cache, no slab, no chunked form, no kernels, no batching, no
dispatch.

    x = embed[ids]
    per layer l: EVERY block is ONE sub-layer, x = x + f(rmsnorm(x) * w_norm), f by
    the pattern's letter (no bias in any matrix):
      "M", a Mamba-2 mixer (H heads of P channels, d_in = H P; G groups of H / G heads;
      a state N wide; K taps):
        [z | xBC | dt~] = u W_in                            d_in | d_in + 2 G N | H wide
        xBC_t = silu(b + sum_{j=0..K-1} w_j xBC~_{t-(K-1)+j})   depthwise over the channels,
                                                            causal, zero before position 0
        x [H, P], B [G, N], C [G, N] = split(xBC)
        dt_t = softplus(dt~ + dt_bias)                      per head, float32, NOT clamped
        a_t  = exp(-exp(A_log) dt_t)                        per head, in (0, 1)
        S_t  = a_t S_{t-1} + dt_t x_t B_t^T                 S [P, N] a head, FLOAT32, zero before
                                                            0; B, C of the head's group
        y_t  = S_t C_t + D x_t
        y    = y * silu(z) ; y = y / rms_group(y) * w_gate_norm   the gate BEFORE the norm; the
                                                            norm over each group of d_in / G
                                                            channels, ONE weight [d_in]
        f    = y W_out
      "*", attention: n query heads over n_kv KV heads of d, NO rotary embedding
        o = softmax(q k^T * d^-0.5 + causal) v ; f = concat(o) Wo
      "E", experts:
        s   = sigmoid(u W_r)                                [E], float32
        choose the k highest of s + bias                    no groups (n_group = 1)
        w_e = s_e / sum(s_chosen) * routed_scaling_factor   the chosen s THEMSELVES
        f   = sum_{e chosen AND held} w_e relu(u Wu_e)^2 Wd_e + relu(u Wu_s)^2 Wd_s
    logits = (rmsnorm(x) * w_final_norm) W_lm               W_lm untied

**The share.** ``held = (lo, hi)``: only the routed experts ``lo .. hi-1`` add
their terms (the router still scores and chooses among all ``E``); what the
others would add is left out and that partial result goes on, as on one chip
of a deployment that spreads each layer's experts over several. With ``held =
(0, E)`` this is the uncut layer. Every held expert runs on every token here
and the weights of those not chosen are zero: nothing is dropped.

Departures from the published model: (1) no rotary embedding, ``d_in = H P``
(not ``expand x hidden``), no clamp on ``dt``, the gate before the grouped
norm and the float32 state are the family's code's, not keys of the catalog's
copy of the file (the configuration's ``assumed``); (2) the depthwise
convolution is written as its ``K`` shifted products and not as a padded
``conv1d``: the same numbers; (3) weights are random, from the seed. None in
the mathematics.

``faults`` names what a CONTROL changes, for the comparisons that must come
out as not correct (``chipbench/reference/control.py``): "fp8" leaves nothing
out, it is the reference one precision below the configuration's (what each
sub-layer reads and gives, and x, B, C, q, k, v, rounded through e4m3);
"state_bf16" rounds the state to bfloat16 after every update (one precision
below the float32 the configuration states for it); "conv_bias" leaves the
convolution's bias out; "skip_d" leaves ``D x`` out; "gate_after_norm" norms
first and gates then; "relu" takes ``relu`` for ``relu^2`` (routed and shared);
"scale" leaves the routed scaling factor out; "choice_bias" chooses by ``s``
alone.

Weights arrive a layer at a time in the published layout from
``chipbench/architectures/nemotron_h.py``: the matrices as the engine holds
them (bfloat16 on the chip) and made float32 INSIDE each jitted piece; the
small leaves float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.olmo_hybrid import bf16, fp8
from chipbench.reference.qwen2 import rms_norm

FAULTS = ("fp8", "state_bf16", "conv_bias", "skip_d", "gate_after_norm", "relu", "scale",
          "choice_bias")


def _low(faults):
    return fp8 if "fp8" in faults else (lambda t: t)


def ssd(x, dt, a, B, C, D, *, keep=lambda S: S):
    """The recurrence over one sequence, a position a turn. ``x [T, H, P]``,
    ``dt``, ``a`` ``[T, H]``, ``B``, ``C`` ``[T, H, N]`` (each head's group's),
    ``D [H]``; ``y [T, H, P]``. ``keep``: what the state is rounded to after
    every update (the ``state_bf16`` control)."""
    H, P, N = x.shape[1], x.shape[2], B.shape[2]

    def step(S, t):
        x, dt, a, B, C = t
        S = keep(a[:, None, None] * S + (dt[:, None] * x)[:, :, None] * B[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C) + D[:, None] * x

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, dt, a, B, C))
    return y


def mamba_mixer(x, w, *, H, P, N, G, eps, faults=()):
    """x + mixer(rmsnorm(x)) over a whole sequence x [T, h] (float32).
    ``w["in_proj"]`` [h, 2 d_in + 2 G N + H] (columns ``[z | x | B | C | dt]``);
    ``w["conv_w"]`` [K, channels]: tap ``j`` multiplies the input ``K - 1 - j``
    positions back (the published ``conv1d.weight[:, 0, j]``)."""
    low = _low(faults)
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    T, d_in = x.shape[0], H * P
    u = low(rms_norm(x, w["norm"], eps))
    zxbcdt = u @ w["in_proj"]
    z, pre, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in:-H], zxbcdt[:, -H:]
    K = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), pre.dtype), pre], axis=0)
    c = sum(w["conv_w"][j] * padded[j:j + T] for j in range(K))
    if "conv_bias" not in faults:
        c = c + w["conv_b"]
    c = low(jax.nn.silu(c))
    xs = c[:, :d_in].reshape(T, H, P)
    B = jnp.repeat(c[:, d_in:d_in + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(c[:, d_in + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["A_log"]) * dt)
    D = jnp.zeros_like(w["D"]) if "skip_d" in faults else w["D"]
    y = ssd(xs, dt, a, B, C, D, **({"keep": bf16} if "state_bf16" in faults else {}))
    y = y.reshape(T, d_in)
    gate = jax.nn.silu(z)

    def group_norm(t):
        t = t.reshape(T, G, d_in // G)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
        return t.reshape(T, d_in) * w["gate_norm"]

    y = group_norm(y) * gate if "gate_after_norm" in faults else group_norm(y * gate)
    return x + low(y) @ w["out_proj"]


def attention(x, w, *, n_heads, n_kv, head_dim, eps, faults=()):
    """x + attention(rmsnorm(x)) over a whole sequence x [T, h] (float32):
    grouped queries, no rope."""
    low = _low(faults)
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    T = x.shape[0]
    u = low(rms_norm(x, w["norm"], eps))
    q = (u @ w["wq"]).reshape(T, n_kv, n_heads // n_kv, head_dim)
    k = low(u @ w["wk"]).reshape(T, n_kv, head_dim)
    v = low(u @ w["wv"]).reshape(T, n_kv, head_dim)
    pos = jnp.arange(T)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) * head_dim ** -0.5
    scores = jnp.where((pos[:, None] >= pos[None, :])[None, None], scores, -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)
    return x + low(o.reshape(T, n_heads * head_dim)) @ w["wo"]


def routing_weights(u, w_router, bias, *, top_k, scale, faults=()):
    """[T, E]: each token's weight for each expert, zero where not chosen."""
    T, E = u.shape[0], w_router.shape[1]
    s = jax.nn.sigmoid(u @ w_router.astype(jnp.float32))
    pick = s if "choice_bias" in faults else s + bias
    _, idx = jax.lax.top_k(pick, top_k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    if "scale" not in faults:
        chosen = chosen * scale
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], idx].set(chosen)


def relu2_mlp(u, w_up, w_down, faults=()):
    """``relu(u Wu)^2 Wd`` (float32)."""
    a = jnp.maximum(u @ w_up.astype(jnp.float32), 0.0)
    if "relu" not in faults:
        a = a * a
    return _low(faults)(a) @ w_down.astype(jnp.float32)


def forward(ids, embed, layers, final_norm, lm_head_chunks, *, n_heads, n_kv, head_dim,
            H, P, N, G, eps, top_k, scale, held, rows, faults=()):
    """Logits [len(rows), vocab] of one sequence at the positions ``rows``.

    ``layers`` yields, per layer, ``(kind, weights)``: ``kind`` "mamba" or
    "full_attention" with the mixer's matrices and its block's ``norm``; or
    "moe" with ``{"norm", "w_router", "bias", "experts", "shared"}``:
    ``experts`` iterates ``(e, w_up, w_down)`` over the experts whose weights
    exist here (``e`` the expert's index among all ``E``), ``shared`` iterates
    ``(w_up, w_down)`` column blocks of the shared expert. An expert outside
    ``held = (lo, hi)`` is skipped. A piece at a time, as in
    ``reference.qwen2.forward``, and a layer's pieces let go before the next
    layer's are asked for."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise ValueError(f"nemotron_h reference: unknown faults {sorted(unknown)}; it names {FAULTS}")
    low = _low(faults)
    mixer = jax.jit(lambda x, w: mamba_mixer(x, w, H=H, P=P, N=N, G=G, eps=eps, faults=faults))
    attn = jax.jit(lambda x, w: attention(
        x, w, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim, eps=eps, faults=faults))
    route = jax.jit(lambda u, w_router, bias: routing_weights(
        u, w_router, bias, top_k=top_k, scale=scale, faults=faults))
    block = jax.jit(lambda u, w_up, w_down: relu2_mlp(u, w_up, w_down, faults))
    expert = jax.jit(lambda u, w, w_up, w_down: w[:, None] * relu2_mlp(u, w_up, w_down, faults))
    lo, hi = held
    with jax.default_matmul_precision("highest"):
        x = embed[jnp.asarray(ids)].astype(jnp.float32)
        layers = iter(layers)
        while (piece := next(layers, None)) is not None:
            kind, w = piece
            if kind == "mamba":
                x = mixer(x, w)
            elif kind == "full_attention":
                x = attn(x, w)
            else:
                u = low(rms_norm(x, w["norm"].astype(jnp.float32), eps))
                weights = route(u, w["w_router"], w["bias"])
                for e, w_up, w_down in w["experts"]:
                    if lo <= e < hi:
                        x = x + expert(u, weights[:, e], w_up, w_down)
                for w_up, w_down in w["shared"]:
                    x = x + block(u, w_up, w_down)
            del piece, w      # before the next layer's pieces are made
        x = rms_norm(x[jnp.asarray(rows)], final_norm, eps)
        return jnp.concatenate([x @ chunk for chunk in lm_head_chunks], axis=-1)
