"""The gated delta rule of ops/linear_attention.py: the chunked scan of a
ragged batch and the decode step (``jax.numpy`` and the Pallas kernel under
``interpret=True``) against the recurrence token by token, the slab's
fresh / garbage / dead-lane rules, the chooser and its counter. That Mosaic
takes the kernel for a described v5e is in tests/test_decode_attention.py
(the one file that loads the TPU compiler)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import linear_attention as la

TIGHT = 2e-5   # float32 on both sides at `highest`: the readings are ~1e-6


def draw(seed: int, T: int, H: int, dk: int, dv: int):
    """q, k, v, g (log alpha, alpha in ~0.9-0.999), beta (in (0, 2))."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = la.l2_normalize(jax.random.normal(ks[0], (T, H, dk)), 1e-6) * dk ** -0.5
    k = la.l2_normalize(jax.random.normal(ks[1], (T, H, dk)), 1e-6)
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (T, H), minval=np.log(0.001), maxval=np.log(0.1)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta


def close(got, want, tol=TIGHT):
    return float(jnp.max(jnp.abs(got - want))) <= tol


# -- the scan against the recurrence --------------------------------------------

@pytest.mark.parametrize("chunk,lens", [
    (8, (37, 20)),          # whole chunks and ragged tails
    (8, (8, 16, 1)),        # exactly a chunk, two chunks, one row
    (8, (7, 9, 15, 17)),    # a chunk edge +- 1, twice
    (16, (57,)),            # one sequence, the batch to its end
    (64, (37, 20)),         # the serving chunk, wider than either sequence
])
def test_the_chunked_scan_is_the_recurrence(chunk, lens):
    H, dk, dv, T = 4, 16, 32, 64        # four heads side by side in a tile of 128 lanes
    q, k, v, g, beta = draw(0, T, H, dk, dv)
    S = 4
    cu = np.zeros(S + 1, np.int32)
    cu[1:len(lens) + 1] = np.cumsum(lens)
    cu[len(lens) + 1:] = cu[len(lens)]
    # sequence 1 goes on from a state; the others open theirs; NaN wherever nothing may be read
    prior = jax.random.normal(jax.random.PRNGKey(5), (H, dk, dv))
    slots = jnp.asarray([2, 0, 3, 1][:S], jnp.int32)
    fresh = jnp.asarray([True, False, True, True])
    p = la.heads_per_tile(H, dv)
    assert p == 4
    state = la.pack_heads(jnp.full((6, H, dk, dv), jnp.nan).at[0].set(prior), p)
    assert state.shape == (6, 1, dk, 128)
    o, out = la.gdn_scan_jnp(state, slots, fresh, q, k, v, g, beta, jnp.asarray(cu), chunk=chunk)
    out = la.unpack_heads(out, p)
    for s, n in enumerate(lens):
        a, b = int(cu[s]), int(cu[s + 1])
        want_o, want_S = la.gdn_recurrence(
            q[a:b], k[a:b], v[a:b], g[a:b], beta[a:b], prior if s == 1 else None)
        assert close(o[a:b], want_o), (s, n)
        assert close(out[int(slots[s])], want_S), (s, n)
    assert not np.asarray(o[int(cu[len(lens)]):]).any()          # rows of no sequence
    assert bool(jnp.isnan(out[4]).all())                         # a slot no one holds


@pytest.mark.parametrize("cut", [1, 7, 8, 9, 15, 16, 17, 30])
def test_a_sequence_cut_at_any_row_gives_the_uncut_scan(cut):
    """The state a prompt's next chunk needs is read from, and written to,
    the slab: two calls give one call's rows and end state."""
    H, dk, dv, T = 2, 16, 32, 31
    q, k, v, g, beta = draw(1, T, H, dk, dv)
    state = jnp.full((3, H, dk, dv), jnp.nan)
    slots, yes, no = jnp.asarray([1], jnp.int32), jnp.asarray([True]), jnp.asarray([False])
    whole, end = la.gdn_scan_jnp(state, slots, yes, q, k, v, g, beta,
                                 jnp.asarray([0, T], jnp.int32), chunk=8)
    first, mid = la.gdn_scan_jnp(state, slots, yes, q[:cut], k[:cut], v[:cut], g[:cut],
                                 beta[:cut], jnp.asarray([0, cut], jnp.int32), chunk=8)
    rest, got = la.gdn_scan_jnp(mid, slots, no, q[cut:], k[cut:], v[cut:], g[cut:], beta[cut:],
                                jnp.asarray([0, T - cut], jnp.int32), chunk=8)
    assert close(jnp.concatenate([first, rest]), whole) and close(got[1], end[1])


def test_the_layout_never_lets_a_chunk_straddle_two_sequences():
    cu = jnp.asarray([0, 5, 5, 22, 22], jnp.int32)
    rows, slot, zero = la.chunk_layout(
        cu, jnp.asarray([3, 9, 1, 9], jnp.int32), jnp.asarray([True, True, False, True]),
        T=32, chunk=8, garbage=9)
    rows, slot, zero = np.asarray(rows), np.asarray(slot), np.asarray(zero)
    assert rows.shape == (32 // 8 + 4, 8)
    assert rows[0].tolist() == [0, 1, 2, 3, 4, 32, 32, 32]        # 5 rows, then padding
    assert [r[0] for r in rows[1:4]] == [5, 13, 21] and rows[3, 1] == 32
    assert slot.tolist() == [3, 1, 1, 1, 9, 9, 9, 9]               # chunks no sequence uses
    assert zero.tolist() == [True] + [False] * 7                   # sequence 2 goes on


# -- the step ---------------------------------------------------------------------

def step_case(B, H, dk, dv, n_slots, seed=2):
    q, k, v, g, beta = draw(seed, B, H, dk, dv)
    state = jax.random.normal(jax.random.PRNGKey(seed + 10), (n_slots, H, dk, dv))
    return state, q, k, v, jnp.exp(g), beta


@pytest.mark.parametrize("H,dv,p", [(3, 32, 1), (4, 64, 2), (4, 32, 4)])
def test_the_jnp_step_is_one_turn_of_the_recurrence(H, dv, p):
    dk = 16
    assert la.heads_per_tile(H, dv) == p
    state, q, k, v, alpha, beta = step_case(3, H, dk, dv, 5)
    slots = jnp.asarray([4, 0, 2], jnp.int32)
    fresh = jnp.asarray([False, True, False])
    o, out = la.gdn_step_jnp(la.pack_heads(state, p), slots, q, k, v, alpha, beta, fresh)
    assert out.shape == (5, H // p, dk, p * dv)
    out = la.unpack_heads(out, p)
    np.testing.assert_array_equal(la.unpack_heads(la.pack_heads(state, p), p), state)
    for b in range(3):
        want_o, want_S = la.gdn_recurrence(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], jnp.log(alpha[b:b + 1]), beta[b:b + 1],
            None if bool(fresh[b]) else state[int(slots[b])])
        assert close(o[b], want_o[0]) and close(out[int(slots[b])], want_S)
    assert close(out[1], state[1], 0) and close(out[3], state[3], 0)   # slots no lane named


@pytest.mark.parametrize("heads_per_block", [10, 30, 7])
def test_the_kernel_is_the_jnp_step_at_the_published_tile(heads_per_block):
    """30 heads of ``[96, 192]`` float32, two side by side in a tile of ``[96,
    384]``; a slot with NaN planted that a fresh lane takes; two dead lanes on
    the garbage slot (the last)."""
    B, H, dk, dv = 4, 30, 96, 192
    state, q, k, v, alpha, beta = step_case(B, H, dk, dv, 6)
    state = la.pack_heads(state.at[3].set(jnp.nan), 2)
    assert state.shape == (6, 15, 96, 384)
    slots = jnp.asarray([1, 3, 5, 5], jnp.int32)
    fresh = jnp.asarray([False, True, True, True])
    want_o, want = la.gdn_step_jnp(state, slots, q, k, v, alpha, beta, fresh)
    o, got = jax.block_until_ready(la.gdn_step_pallas(
        state, slots, q, k, v, alpha, beta, fresh,
        heads_per_block=heads_per_block, interpret=True))
    assert bool(jnp.isfinite(o).all()) and close(o[:2], want_o[:2])
    assert close(got[:5], want[:5]) and bool(jnp.isfinite(got[5]).all())
    assert close(got[0], state[0], 0) and close(got[2], state[2], 0)   # untouched slots


def test_a_stale_nan_stays_out_by_a_select_not_a_product():
    rows = jnp.asarray([[jnp.nan, 1.0], [2.0, jnp.inf]])
    got = la.zero_where_fresh(rows, jnp.asarray([True, False]))
    assert got.tolist() == [[0.0, 0.0], [2.0, float("inf")]]


@pytest.mark.parametrize("backend,shape,dtype,impl", [
    ("tpu", (49, 15, 96, 384), jnp.float32, "pallas"),  # the published tile, two heads wide
    ("cpu", (49, 15, 96, 384), jnp.float32, "jnp"),
    ("tpu", (9, 1, 32, 128), jnp.float32, "pallas"),    # the tiny preset's, two heads of 64
    ("tpu", (9, 3, 32, 64), jnp.float32, "jnp"),        # three heads of 64: no whole lane row
    ("tpu", (49, 15, 96, 384), jnp.bfloat16, "jnp"),    # the state is float32 or not the kernel's
])
def test_the_step_gets_the_kernel_on_a_tpu_by_geometry(backend, shape, dtype, impl):
    assert la.step_impl(backend, jax.ShapeDtypeStruct(shape, dtype)) == impl


def test_the_traced_counter_names_shape_and_implementation():
    before = la.traced_calls()
    H, dk, dv = 2, 8, 16
    state, q, k, v, alpha, beta = step_case(2, H, dk, dv, 3)
    slots, fresh = jnp.asarray([0, 1], jnp.int32), jnp.asarray([False, False])
    jax.jit(la.gdn_step)(state, slots, q, k, v, alpha, beta, fresh)
    jax.jit(la.gdn_scan)(state, slots[:1], fresh[:1], q, k, v, jnp.log(alpha), beta,
                         jnp.asarray([0, 2], jnp.int32))
    after = la.traced_calls()
    assert after["step", "jnp"] == before.get(("step", "jnp"), 0) + 1
    assert after["scan", "jnp"] == before.get(("scan", "jnp"), 0) + 1
    assert "jnp" in la.traced_impl("step") and la.traced_impl("scan") == "jnp"


def test_the_step_bench_refuses_the_cpu(monkeypatch):
    """``tools/linear_step_bench.py`` times the TPU kernel against the
    ``jax.numpy`` step; on the CPU it would time the interpreter."""
    from tools import linear_step_bench

    monkeypatch.setattr("sys.argv", ["linear_step_bench", "--shape", "olmo"])
    with pytest.raises(SystemExit, match="chiprun"):
        linear_step_bench.main()
