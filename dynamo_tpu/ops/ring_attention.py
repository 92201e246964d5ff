"""Ring attention: causal self-attention with the sequence sharded over a
mesh axis — the long-context prefill primitive.

The reference has NO sequence/context parallelism anywhere (SURVEY.md
§2.6: "ABSENT"); on TPU it is first-class. Each device holds one sequence
chunk of Q, K, V. K/V chunks rotate around the ring via `ppermute` (ICI
neighbor exchange) while every device folds each visiting chunk into an
online-softmax accumulator — full causal attention materializing only
[T_local, T_local] scores at a time, so context scales linearly with the
ring size. (Blockwise ring attention; see PAPERS.md.)

GQA-aware: q [T, n_q, d], k/v [T, n_kv, d]. Computation is f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def causal_attention_reference(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Single-device full causal attention (ground truth)."""
    T, n_q, d = q.shape
    n_kv = k.shape[1]
    group = n_q // n_kv
    scale = d ** -0.5
    qg = q.reshape(T, n_kv, group, d).astype(jnp.float32) * scale
    s = jnp.einsum("thgd,shd->thgs", qg, k.astype(jnp.float32))
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(mask[:, None, None, :], s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("thgs,shd->thgd", w, v.astype(jnp.float32))
    return out.reshape(T, n_q, d).astype(q.dtype)


def _ring_attention_local(
    q: jax.Array,   # [T_loc, n_q, d] — this device's query chunk
    k: jax.Array,   # [T_loc, n_kv, d]
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
) -> jax.Array:
    T_loc, n_q, d = q.shape
    n_kv = k.shape[1]
    group = n_q // n_kv
    scale = d ** -0.5
    my = jax.lax.axis_index(axis_name)

    qg = (q.astype(jnp.float32) * scale).reshape(T_loc, n_kv, group, d)
    q_pos = my * T_loc + jnp.arange(T_loc, dtype=jnp.int32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    m = jnp.full((T_loc, n_kv, group, 1), _NEG, jnp.float32)
    l = jnp.zeros((T_loc, n_kv, group, 1), jnp.float32)
    acc = jnp.zeros((T_loc, n_kv, group, d), jnp.float32)
    k_cur, v_cur = k.astype(jnp.float32), v.astype(jnp.float32)

    for p in range(axis_size):
        # The chunk in hand after p rotations originated on device my - p.
        src = (my - p) % axis_size
        kv_pos = src * T_loc + jnp.arange(T_loc, dtype=jnp.int32)
        s = jnp.einsum("thgd,shd->thgs", qg, k_cur)
        mask = q_pos[:, None] >= kv_pos[None, :]
        s = jnp.where(mask[:, None, None, :], s, _NEG)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(pexp, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("thgs,shd->thgd", pexp, v_cur)
        m = m_new

        if p + 1 < axis_size:
            # Neighbor exchange over ICI; overlapping this with the next
            # pass's compute is XLA's latency-hiding scheduler's job.
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(T_loc, n_q, d).astype(q.dtype)


def sequence_parallel_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = n_devices or len(devices)
    return Mesh(np.asarray(devices[:n]), ("sp",))


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, axis_name: str = "sp"
) -> jax.Array:
    """Causal self-attention over a sequence sharded on ``axis_name``.

    q/k/v are full [T, heads, d] arrays (or already sharded); T must be
    divisible by the axis size. Runs as shard_map over the mesh.
    """
    axis_size = mesh.shape[axis_name]
    if q.shape[0] % axis_size:
        raise ValueError(f"sequence {q.shape[0]} not divisible by {axis_size}-way sp")
    spec = P(axis_name, None, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, axis_size=axis_size
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
