"""The Mamba-2 recurrence alone (``dynamo_tpu/ops/ssm.py``): the chunked scan of
a ragged batch and the decode step against the recurrence token by token, the
step's Pallas kernel (``interpret=True``) against its ``jax.numpy`` twin, the
slab's rules (zeros at position 0 by a select, the garbage slot), and the
chooser. The layer through the engine: tests/test_nemotron_h.py; the kernel
compiled for a v5e at the published widths: tests/test_decode_attention.py."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import ssm

H, P, G, N = 8, 8, 2, 128
# (jitted: op by op these tests spend their time in a hundred small compiles)
scan = jax.jit(ssm.ssd_scan_jnp, static_argnames=("chunk",))
step = jax.jit(ssm.ssd_step_jnp)
recurrence = jax.jit(ssm.ssd_recurrence)
# float32 on both sides; a chunk is a handful of products where the recurrence
# takes a turn a row: the readings are 5e-6 to 1e-5
TIGHT = 5e-5


def draw(seed: int, T: int):
    """A mixer's inputs as the layer draws them: ``dt`` log-uniform in [0.001,
    0.1], ``A`` uniform in [1, 16]."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (T, H, P))
    dt = jnp.exp(jax.random.uniform(k[1], (T, H), minval=math.log(1e-3), maxval=math.log(0.1)))
    la = -jax.random.uniform(k[2], (H,), minval=1.0, maxval=16.0) * dt
    B, C = jax.random.normal(k[3], (T, G, N)), jax.random.normal(k[4], (T, G, N))
    return x, dt, la, B, C, 1.0 + 0.1 * jax.random.normal(k[5], (H,))


def slab(seed: int, slots: int = 7, nan_in: int | None = None):
    state = jax.random.normal(jax.random.PRNGKey(seed), (slots, H, P, N))
    return state if nan_in is None else state.at[nan_in].set(jnp.nan)


# Three sequences in one flat batch: a fresh one into a slot that held NaN, one
# that goes on from its slot's state, a fresh one; a fourth entry with no rows.
CU, SLOTS, FRESH = [0, 40, 53, 75, 75], [1, 3, 0, 6], [True, False, True, False]


@pytest.mark.parametrize("chunk", [8, 16, 7, 128], ids=[
    "chunks-divide-40", "a-boundary-inside-each-prompt", "chunks-divide-nothing", "one-chunk-each"])
def test_the_scan_of_a_mixed_batch_is_the_recurrence_of_each_sequence(chunk):
    x, dt, la, B, C, D = draw(2, 75)
    st = slab(3, nan_in=1)
    y, s = scan(st, jnp.array(SLOTS), jnp.array(FRESH), x, dt, la, B, C, D, jnp.array(CU),
                chunk=chunk)
    for q, (a, b) in enumerate(zip(CU[:3], CU[1:4])):
        S0 = jnp.zeros((H, P, N)) if FRESH[q] else st[SLOTS[q]]
        yr, Sr = recurrence(x[a:b], dt[a:b], la[a:b], B[a:b], C[a:b], D, S0)
        np.testing.assert_allclose(y[a:b], yr, atol=TIGHT)
        np.testing.assert_allclose(s[SLOTS[q]], Sr, atol=TIGHT)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_array_equal(s[2], st[2])          # a slot no sequence names is untouched


def test_steps_go_on_from_where_a_scan_stopped():
    x, dt, la, B, C, D = draw(4, 43)
    st = slab(5)
    _, s = scan(st, jnp.array([2]), jnp.array([True]), x[:40], dt[:40], la[:40], B[:40], C[:40],
                D, jnp.array([0, 40]), chunk=16)
    ys = []
    for t in range(40, 43):
        y, s = step(s, jnp.array([2]), x[t:t + 1], dt[t:t + 1], jnp.exp(la[t:t + 1]),
                    B[t:t + 1], C[t:t + 1], D, jnp.array([False]))
        ys.append(y[0])
    yr, Sr = recurrence(x, dt, la, B, C, D)
    np.testing.assert_allclose(jnp.stack(ys), yr[40:], atol=TIGHT)
    np.testing.assert_allclose(s[2], Sr, atol=TIGHT)


@pytest.mark.parametrize("heads_per_block", [4, 8], ids=["one-group-a-block", "all-heads-a-block"])
def test_the_step_kernel_is_its_jnp_twin(heads_per_block):
    """Five lanes: one goes on, one is fresh over a slot that held NaN, two fall
    on the garbage slot, one goes on; the slots the live lanes name and every
    read-out must agree."""
    x, dt, la, B, C, D = draw(0, 5)
    st = slab(1, nan_in=3)
    slots, fresh = jnp.array([2, 3, 6, 6, 0]), jnp.array([False, True, False, False, False])
    args = (st, slots, x, dt, jnp.exp(la), B, C, D, fresh)
    y1, s1 = step(*args)
    y2, s2 = ssm.ssd_step_pallas(*args, heads_per_block=heads_per_block, interpret=True)
    np.testing.assert_allclose(y2, y1, atol=TIGHT)
    live = jnp.array([0, 2, 3])
    np.testing.assert_allclose(s2[live], s1[live], atol=1e-6)
    assert bool(jnp.isfinite(s2[live]).all())
    for untouched in (1, 4, 5):
        np.testing.assert_array_equal(s2[untouched], st[untouched])


def test_the_chooser_takes_the_kernel_on_a_tpu_at_whole_vregs_and_counts_what_was_traced():
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    assert ssm.step_impl("tpu", f32(129, 64, 64, 128)) == "pallas"
    assert ssm.step_impl("cpu", f32(129, 64, 64, 128)) == "jnp"
    assert ssm.step_impl("tpu", f32(9, 4, 16, 32)) == "jnp"          # a state 32 wide: no lane row
    assert ssm.step_impl("tpu", jax.ShapeDtypeStruct((9, 4, 16, 128), jnp.bfloat16)) == "jnp"
    assert ssm._heads_a_block(64, 8, 32) == 32 and ssm._heads_a_block(4, 2, 1) == 2
    before = ssm.traced_calls()
    x, dt, la, B, C, D = draw(6, 2)
    ssm.ssd_step(slab(7), jnp.array([0, 1]), x, dt, jnp.exp(la), B, C, D, jnp.array([True, False]))
    ssm.ssd_scan(slab(7), jnp.array([0]), jnp.array([True]), x, dt, la, B, C, D,
                 jnp.array([0, 2]), chunk=16)
    after = ssm.traced_calls()
    assert after["step", "jnp"] == before.get(("step", "jnp"), 0) + 1
    assert after["scan", "jnp"] == before.get(("scan", "jnp"), 0) + 1
    assert ssm.traced_impl("step") == "jnp"
