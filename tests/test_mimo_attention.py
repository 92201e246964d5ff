"""``ops/gqa_attention.py`` on the CPU: the wide-key page (a key of 1.5
values' width), its writer, and the two shapes of its attention against the
whole-gather definition and the textbook: groups of 16 and 8, the sink,
shifted (window) tables, ragged pieces; the decode kernel and the wave kernel
under Pallas's TPU interpret mode at the published page (192 / 128, a page of
32 tokens)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import gqa_attention as ga
from dynamo_tpu.ops import ragged_attention


def _case(lens, q_lens=None, *, n_kv=4, G=16, dk=192, dv=128, ps=32, width=8,
          dtype=jnp.float32, seed=0, sink=False):
    """Shuffled block tables whose every slot is filled through the page's
    own writer with random keys and values (those past a context scaled up
    so that a leak shows), random queries, and the sequences' K and V as the
    cache holds them, in float64."""
    rng = np.random.RandomState(seed)
    S, H = len(lens), n_kv * G
    lens = np.asarray(lens, np.int32)
    q_lens = np.ones(S, np.int32) if q_lens is None else np.asarray(q_lens, np.int32)
    n_pages = S * width + 3
    tables = rng.permutation(n_pages)[: S * width].reshape(S, width).astype(np.int32)
    k = rng.randn(S, width * ps, n_kv, dk).astype(np.float32)
    v = rng.randn(S, width * ps, n_kv, dv).astype(np.float32)
    for s, n in enumerate(lens):
        k[s, n:] *= 100.0
        v[s, n:] *= 100.0
    slot = np.tile(np.arange(width * ps), S)
    page = tables[np.repeat(np.arange(S), width * ps), slot // ps]
    pages = ga.write_gqa_rows(
        jnp.asarray(rng.randn(n_pages, *ga.gqa_page_shape(ps, n_kv, dk, dv)), dtype),
        jnp.asarray(page), jnp.asarray(slot % ps, jnp.int32),
        jnp.asarray(k.reshape(-1, n_kv, dk)), jnp.asarray(v.reshape(-1, n_kv, dv)))
    T = int(q_lens.sum())
    q = jnp.asarray(rng.randn(T, H, dk) * 0.5, dtype)
    sinks = jnp.asarray(rng.randn(H) + 1.0, jnp.float32) if sink else None
    held = lambda a: np.asarray(jnp.asarray(a, dtype), np.float64)  # noqa: E731
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    return dict(q=q, pages=pages, lens=jnp.asarray(lens), tables=jnp.asarray(tables),
                cu=jnp.asarray(cu), sinks=sinks, k=held(k), v=held(v), n_kv=n_kv)


def _textbook(c, *, sm_scale, window=None):
    """softmax(q k^T) v a query at a time in float64, the sink a column
    without a value."""
    q = np.asarray(c["q"], np.float64)
    T, H, _ = q.shape
    n_kv = c["n_kv"]
    G = H // n_kv
    cu, lens = np.asarray(c["cu"]), np.asarray(c["lens"])
    out = np.zeros((T, H, c["v"].shape[-1]))
    for s in range(len(lens)):
        for i in range(cu[s], cu[s + 1]):
            p = lens[s] - (cu[s + 1] - cu[s]) + (i - cu[s])
            first = 0 if window is None else max(0, p - window + 1)
            for h in range(H):
                sc = c["k"][s, first:p + 1, h // G] @ q[i, h] * sm_scale
                if c["sinks"] is not None:
                    sc = np.concatenate([sc, [float(c["sinks"][h])]])
                w = np.exp(sc - sc.max())
                w = w / w.sum()
                out[i, h] = w[: p + 1 - first] @ c["v"][s, first:p + 1, h // G]
    return out


def _args(c, decode=False):
    return (c["q"], c["pages"], c["lens"], c["tables"], None if decode else c["cu"],
            jnp.asarray([len(np.asarray(c["lens"]))], jnp.int32))


def test_a_page_holds_the_published_values_and_not_one_more():
    """320 values a KV head a token: 10 rows of 128 lanes a token at 4 KV
    heads, 20 at 8; a page of 32 tokens is 80 KB and 160 KB in bf16."""
    assert ga.gqa_page_shape(32, 4, 192, 128) == (320, 128)
    assert ga.gqa_page_shape(32, 8, 192, 128) == (640, 128)
    assert ga.gqa_page_shape(4, 2, 24, 16) == (20, 16)
    for bad in ((32, 4, 128, 128), (32, 3, 192, 128), (32, 4, 192, 64)):
        with pytest.raises(ValueError, match="1.5 values' width"):
            ga.gqa_page_shape(*bad)


def test_the_writer_and_the_reader_of_a_page_are_inverse():
    rng = np.random.RandomState(1)
    n_kv, dk, dv, ps = 4, 24, 16, 4
    pages = jnp.zeros((3, *ga.gqa_page_shape(ps, n_kv, dk, dv)))
    k, v = rng.randn(ps, n_kv, dk).astype(np.float32), rng.randn(ps, n_kv, dv).astype(np.float32)
    pages = ga.write_gqa_rows(pages, jnp.full((ps,), 1), jnp.arange(ps), jnp.asarray(k),
                              jnp.asarray(v))
    got_k, got_v = ga._split(pages[1], n_kv)
    np.testing.assert_array_equal(np.asarray(got_k), k.transpose(1, 0, 2))
    np.testing.assert_array_equal(np.asarray(got_v), v.transpose(1, 0, 2))
    assert not np.asarray(pages[0]).any() and not np.asarray(pages[2]).any()


@pytest.mark.parametrize("n_kv,G,sink,window", [
    (4, 16, False, None), (8, 8, True, 128), (2, 4, True, None), (4, 2, False, 40),
], ids=["full-groups-of-16", "window-groups-of-8-sink", "sink-on-full", "window-no-sink"])
def test_the_definition_is_the_textbook(n_kv, G, sink, window):
    c = _case([5, 37, 200, 129], n_kv=n_kv, G=G, sink=sink, seed=n_kv)
    got = ga.gqa_attention_ref(*_args(c, decode=True), n_kv=n_kv, sm_scale=0.07,
                               window=window, sinks=c["sinks"])
    np.testing.assert_allclose(np.asarray(got), _textbook(c, sm_scale=0.07, window=window),
                               atol=2e-5)


@pytest.mark.parametrize("n_kv,G,sink,window", [
    (4, 16, False, None), (8, 8, True, 128), (2, 4, True, 8),
], ids=["full-groups-of-16", "window-groups-of-8-sink", "tiny-window"])
def test_the_ragged_walk_is_the_definition(n_kv, G, sink, window):
    """Ragged pieces: a decode row, a chunk behind a context, a whole prompt,
    a chunk longer than a block of queries, and dead sequences behind
    ``num_seqs``."""
    lens, q_lens = [70, 256, 33, 200, 9], [1, 140, 33, 17, 9]
    c = _case(lens, q_lens, n_kv=n_kv, G=G, sink=sink, seed=3, dk=24, dv=16, ps=4, width=64)
    kw = dict(n_kv=n_kv, sm_scale=0.2, window=window, sinks=c["sinks"])
    q, pages, kv_lens, tables, cu, _ = _args(c)
    live = jnp.asarray([4], jnp.int32)
    got = jax.jit(lambda *a: ga.gqa_ragged_jnp(*a, **kw))(q, pages, kv_lens, tables, cu, live)
    want = ga.gqa_attention_ref(q, pages, kv_lens, tables, cu, live, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got[int(cu[4]):]).any()          # the fifth sequence is not live
    np.testing.assert_allclose(np.asarray(got[: int(cu[4])]),
                               _textbook(c, sm_scale=0.2, window=window)[: int(cu[4])], atol=2e-5)


def test_a_shifted_table_gives_the_unshifted_result():
    """A window call is handed the table from the page of the oldest visible
    key and ``kv_lens`` less the tokens before it (model.split_tables):
    positions are relative, so the result is the whole table's (to the
    rounding of a walk whose chunks start elsewhere)."""
    c = _case([300, 77, 190], n_kv=4, G=4, dk=24, dv=16, ps=4, width=80, sink=True, seed=5)
    kw = dict(n_kv=4, sm_scale=0.2, window=16, sinks=c["sinks"])
    whole = ga.gqa_ragged_jnp(*_args(c, decode=True), **kw)
    lens, tables = np.asarray(c["lens"]), np.asarray(c["tables"])
    first = np.maximum(lens - 16, 0) // 4
    shifted = np.stack([np.roll(tables[s], -first[s])[:8] for s in range(3)])
    got = ga.gqa_ragged_jnp(c["q"], c["pages"], jnp.asarray(lens - 4 * first),
                            jnp.asarray(shifted), None, jnp.asarray([3], jnp.int32), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=2e-6)


# -- the decode kernel under Pallas's TPU interpret mode -----------------------

def _kernel(c, **kw):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        # Ended HERE: the interpreter's callbacks run jitted ops of their own, and
        # deadlock against a main thread that goes on to dispatch while they run.
        return jax.block_until_ready(ga.gqa_decode_pallas(
            c["q"], c["pages"], c["lens"], c["tables"], c["sinks"],
            n_kv=c["n_kv"], sm_scale=192 ** -0.5, **kw))


@pytest.mark.parametrize("lens,width,grid", [
    ([5, 37, 70, 50], 8, dict(pages_per_block=2)),
    ([32, 64, 96, 160], 8, dict(pages_per_block=2)),
    ([1, 1, 1], 8, dict(pages_per_block=2)),
    ([1, 250, 33, 64, 7, 129], 8, dict(pages_per_block=2, blocks_in_ring=2)),
    ([3, 40], 40, dict(pages_per_block=64)),
    ([1 + (37 * i) % 131 for i in range(32)], 5, dict(pages_per_block=4, blocks_in_ring=4)),
    ([100, 256, 31], 8, {}),
], ids=["ends-on-a-slot", "ends-on-a-page", "context-of-1", "very-unequal-lanes",
        "a-block-wider-than-the-table", "32-lanes", "the-modules-constants"])
def test_the_decode_kernel_on_a_full_layer_is_the_definition(lens, width, grid):
    """4 KV heads, groups of 16, no sink: pages by DMA through the block
    table, a ring of KV blocks, the mask in a lane's last block."""
    c = _case(lens, n_kv=4, G=16, width=width)
    got = _kernel(c, **grid)
    assert got.shape == (len(lens), 64, 128) and got.dtype == c["q"].dtype
    want = ga.gqa_attention_ref(*_args(c, decode=True), n_kv=4, sm_scale=192 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), _textbook(c, sm_scale=192 ** -0.5), atol=2e-5)


@pytest.mark.parametrize("lens", [[5, 128, 129, 159], [160, 191, 192, 97]],
                         ids=["inside-and-past-the-window", "up-to-six-pages"])
def test_the_decode_kernel_on_a_window_layer_is_the_definition(lens):
    """8 KV heads, groups of 8 (padded to a tile's 16 rows), the sink in the
    running maximum and denominator from the start, the keys before ``kv_len
    - 128`` masked: the call a window layer makes, its table shifted by the
    caller."""
    c = _case(lens, n_kv=8, G=8, width=6, sink=True, seed=7)
    got = _kernel(c, window=128)
    want = ga.gqa_attention_ref(*_args(c, decode=True), n_kv=8, sm_scale=192 ** -0.5,
                                window=128, sinks=c["sinks"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got), _textbook(c, sm_scale=192 ** -0.5, window=128), atol=2e-5)
    no_sink = _kernel({**c, "sinks": None}, window=128)
    assert np.abs(np.asarray(no_sink) - np.asarray(got)).max() > 1e-2


def test_the_kernel_holds_bfloat16_pages_to_the_textbook():
    """bfloat16 pages and queries, float32 scores and sums: the textbook on
    the values the cache holds, to the rounding of the weights."""
    c = _case([70, 200, 33], n_kv=4, G=16, dtype=jnp.bfloat16, sink=True)
    got = _kernel(c, pages_per_block=2)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _textbook(c, sm_scale=192 ** -0.5), atol=1e-2)


# -- the wave kernel under Pallas's TPU interpret mode --------------------------

_KINDS = {"full": dict(n_kv=4, G=16, sink=False, window=None),
          "window": dict(n_kv=8, G=8, sink=True, window=128)}


def _wave(c, window, num=None, **kw):
    from jax.experimental.pallas import tpu as pltpu

    q, pages, kv_lens, tables, cu, live = _args(c)
    if num is not None:
        live = jnp.asarray([num], jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        # ended here, as the decode kernel's call is (:func:`_kernel`)
        got = jax.block_until_ready(ga.gqa_ragged_pallas(
            q, pages, kv_lens, tables, cu, live, c["sinks"], n_kv=c["n_kv"],
            sm_scale=192 ** -0.5, window=window, **kw))
    want = ga.gqa_attention_ref(q, pages, kv_lens, tables, cu, live, n_kv=c["n_kv"],
                                sm_scale=192 ** -0.5, window=window, sinks=c["sinks"])
    return got, want


_SMALL = dict(queries_per_item=32, pages_per_block=4, product_rows=128)


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("lens,q_lens,num,grid", [
    ([363], [263], None, {}),
    ([171], [71], None, _SMALL),
    ([40, 9, 30, 64, 100], [20, 9, 5, 30, 17], None, _SMALL),
    ([70, 200, 1, 33], [1, 40, 1, 1], None, _SMALL),
    ([70, 200, 33, 9, 50], [33, 40, 33, 9, 50], 3, _SMALL),
    ([250, 31], [90, 31], None, dict(queries_per_item=64, pages_per_block=8, blocks_in_ring=2,
                                     product_rows=128)),
], ids=["two-items-and-7-rows-behind-a-prefix-at-the-modules-constants",
        "two-items-and-7-rows-behind-a-prefix", "sequences-share-a-tile", "sequences-of-one-row",
        "dead-sequences-behind-num-seqs", "another-grid"])
def test_the_wave_kernel_is_the_definition(kind, lens, q_lens, num, grid):
    """The ragged call at the published page, a full layer's (4 KV heads, no
    sink) and a window layer's (8 KV heads, the sink, window 128): items of
    one sequence's rows in one tile of the flat batch, the KV blocks an item
    can see by DMA through the table, the mask in the blocks the bounds cut,
    a tile two sequences share stored under each one's rows, the rows past
    the last live sequence zero."""
    k = _KINDS[kind]
    c = _case(lens, q_lens, n_kv=k["n_kv"], G=k["G"], sink=k["sink"], width=12, seed=11)
    got, want = _wave(c, k["window"], num, **grid)
    assert got.shape == (sum(q_lens), 64, 128) and got.dtype == c["q"].dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    n = int(c["cu"][len(lens) if num is None else num])
    assert not np.asarray(got[n:]).any()
    np.testing.assert_allclose(np.asarray(got[:n]),
                               _textbook(c, sm_scale=192 ** -0.5, window=k["window"])[:n],
                               atol=2e-5)


def test_the_wave_kernel_on_a_shifted_table_gives_the_unshifted_result():
    """A window layer's wave: the table from the page of the oldest key the
    chunk's FIRST query sees and ``kv_lens`` less the tokens before it."""
    lens, q_lens = np.asarray([300, 77, 190]), np.asarray([40, 77, 3])
    c = _case(lens, q_lens, **{k: v for k, v in _KINDS["window"].items() if k != "window"},
              width=12, seed=5)
    whole, _ = _wave(c, 128, **_SMALL)
    first = np.maximum(lens - q_lens - 127, 0) // 32
    tables = np.asarray(c["tables"])
    shifted = np.stack([np.roll(tables[s], -first[s])[:8] for s in range(3)])
    got, want = _wave({**c, "lens": jnp.asarray(lens - 32 * first),
                       "tables": jnp.asarray(shifted)}, 128, **_SMALL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), atol=2e-6)


def test_the_wave_kernel_holds_bfloat16_pages_to_the_textbook():
    """bfloat16 pages and queries, float32 scores and sums, the weights in
    bfloat16 against the values: the textbook on the values the cache holds,
    at the tolerance the decode kernel is held to."""
    c = _case([70, 200, 33], [33, 72, 1], n_kv=8, G=8, dtype=jnp.bfloat16, sink=True)
    got, _ = _wave(c, 128, **_SMALL)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _textbook(c, sm_scale=192 ** -0.5, window=128), atol=1e-2)


def test_calls_are_counted_by_shape_and_the_gather_is_refused_on_a_tpu(monkeypatch):
    c = _case([5, 9], n_kv=2, G=4, dk=24, dv=16, ps=4, width=4, sink=True)
    before = ragged_attention.traced_calls()
    kw = dict(n_kv=2, sm_scale=0.2, sinks=c["sinks"])
    ga.gqa_decode_attention(c["q"], c["pages"], c["lens"], c["tables"], **kw)
    ga.gqa_decode_attention(c["q"], c["pages"], c["lens"], c["tables"], window=8, **kw)
    ga.gqa_ragged_attention(*_args(c), window=8, **kw)
    ga.gqa_ragged_attention(*_args(c), **kw)
    after = ragged_attention.traced_calls()
    for shape in ("gqa-decode", "window-gqa-decode", "window-gqa-ragged", "gqa-ragged"):
        assert after.get((shape, "jnp"), 0) == before.get((shape, "jnp"), 0) + 1
    # the published page on a TPU is the kernel's; the tiny page is the walk's
    big = jax.ShapeDtypeStruct((9, 320, 128), jnp.bfloat16)
    assert ga.decode_impl("tpu", big, 4) == "pallas" and ga.decode_impl("cpu", big, 4) == "jnp"
    assert ga.decode_impl("tpu", c["pages"], 2) == "jnp"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="never a TPU program's path"):
        ga.gqa_attention_ref(*_args(c), **kw)
    # ... in BOTH shapes: a wave's call on a TPU traces the wave kernel (traced
    # only: nothing here can run it), and still the walk at the tiny page
    sds = jax.ShapeDtypeStruct
    wave = (sds((48, 32, 192), jnp.bfloat16), big, sds((3,), jnp.int32), sds((3, 5), jnp.int32),
            sds((4,), jnp.int32), sds((1,), jnp.int32))
    assert ga.ragged_impl("tpu", big, 4, 32) == "pallas" and ga.ragged_impl("cpu", big, 4, 32) == "jnp"
    assert ga.ragged_impl("tpu", big, 4, 16) == "jnp"     # a group of 4: no whole tile of rows
    for window in (None, 128):
        out = jax.eval_shape(lambda *a: ga.gqa_ragged_attention(
            *a, n_kv=4, sm_scale=0.07, window=window), *wave)
        assert out.shape == (48, 32, 128)
        jax.eval_shape(lambda *a: ga.gqa_ragged_attention(*a, window=window, **kw), *_args(c))
    last = ragged_attention.traced_calls()
    for shape in ("gqa-ragged", "window-gqa-ragged"):
        assert last.get((shape, "pallas"), 0) == after.get((shape, "pallas"), 0) + 1
        assert last.get((shape, "jnp"), 0) == after.get((shape, "jnp"), 0) + 1
