"""A.X-K1's attention calls alone (split off ``tests/test_axk1.py`` in PR 45
by that file's own sections so that six workers balance: ROADMAP D17):
absorbed decode attention is the expanded one; the first-party decode
kernel (PR 34) under Pallas's TPU interpret mode is the ``jnp`` path and
the textbook, at toy pages and at the published page in bfloat16; the
decode call chooses by backend and geometry and says so, and
``chip_smoke.py`` fails a worker that chose wrongly; the ragged call is
the textbook's over rows, prefixes, blocks and chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import latent_attention, ragged_attention


# -- absorbed against expanded ----------------------------------------------

@pytest.mark.parametrize("lanes_per_group", [32, 2], ids=["one-group", "groups-of-2"])
def test_absorbed_decode_attention_is_the_expanded_one(lanes_per_group, monkeypatch):
    """One query token a sequence over latent pages: the decode call
    (absorbed, chunked over pages, the lanes sorted by context and walked a
    group at a time) and the ragged call (expanded heads, cached keys too)
    give the same heads, and both are plain softmax(q k^T) v on the expanded
    K and V."""
    monkeypatch.setattr(latent_attention, "_DECODE_LANES_PER_GROUP", lanes_per_group)
    rng = np.random.RandomState(4)
    H, dn, dr, dv, r, ps, P, S = 4, 16, 8, 16, 32, 8, 12, 3
    lens = np.asarray([70, 9, 33], np.int32)       # contexts incl. the new token
    rows_, lanes = latent_attention.latent_page_shape(ps, r, dr)
    assert (rows_, lanes) == (20, 16) and latent_attention.latent_page_shape(32, 512, 64) == (
        144, 128) and 144 * 128 == 32 * 576
    flat = rng.randn(S * P * ps, r + dr).astype(np.float32)          # every slot's [ckv | kr]
    slot = np.arange(S * P * ps)
    pages = latent_attention.write_latent_rows(
        jnp.zeros((S * P + 1, rows_, lanes), jnp.float32), jnp.asarray(slot // ps, jnp.int32),
        jnp.asarray(slot % ps, jnp.int32), jnp.asarray(flat[:, :r]), jnp.asarray(flat[:, r:]))
    # a page holds its tokens' values and nothing else, each exactly once
    assert sorted(np.asarray(pages[3]).ravel()) == sorted(flat[3 * ps: 4 * ps].ravel())
    tables = jnp.asarray(np.arange(S * P).reshape(S, P), jnp.int32)
    wk = jnp.asarray(rng.randn(H, dn, r) * r ** -0.5, jnp.float32)
    wv = jnp.asarray(rng.randn(H, r, dv) * r ** -0.5, jnp.float32)
    q_nope = jnp.asarray(rng.randn(S, H, dn), jnp.float32)
    q_rope = jnp.asarray(rng.randn(S, H, dr), jnp.float32)
    q_lat = jnp.einsum("bhd,hdr->bhr", q_nope, wk)
    o_lat = latent_attention.latent_decode_attention(
        q_lat, q_rope, pages, jnp.asarray(lens), tables, sm_scale=0.2)
    absorbed = jnp.einsum("bhr,hrd->bhd", o_lat, wv)
    expanded = latent_attention.latent_ragged_attention(
        q_nope, q_rope, wk, wv, pages,
        jnp.asarray(lens), tables, jnp.arange(S + 1, dtype=jnp.int32),
        jnp.asarray([S], jnp.int32), sm_scale=0.2)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=2e-5)
    for s in range(S):                               # and both are the textbook's
        ctx = flat[s * P * ps: s * P * ps + lens[s]]
        k = np.einsum("tr,hdr->thd", ctx[:, :r], np.asarray(wk))
        v = np.einsum("tr,hrd->thd", ctx[:, :r], np.asarray(wv))
        sc = (np.einsum("hd,thd->ht", np.asarray(q_nope[s]), k)
              + np.einsum("hd,td->ht", np.asarray(q_rope[s]), ctx[:, r:])) * 0.2
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(absorbed[s]), want, atol=2e-5)


# -- the first-party decode kernel (PR 34), under Pallas's TPU interpret mode ---------

def _kernel_case(lens, H=8, r=128, dr=64, ps=32, width=8, dtype=jnp.float32, seed=0):
    """Every slot of shuffled block tables filled through the page's own
    writer with random ``[ckv | kr]`` (the rows past a context scaled up so
    that a leak shows), the absorbed queries of random ``Wkvb``, and the
    textbook's heads on the expanded K and V in float64."""
    rng = np.random.RandomState(seed)
    S, dn, dv = len(lens), 16, 16
    lens = np.asarray(lens, np.int32)
    rows_, lanes = latent_attention.latent_page_shape(ps, r, dr)
    n_pages = S * width + 3
    tables = rng.permutation(n_pages)[: S * width].reshape(S, width).astype(np.int32)
    flat = rng.randn(S, width * ps, r + dr).astype(np.float32)
    for s, n in enumerate(lens):
        flat[s, n:] *= 100.0
    slot = np.tile(np.arange(width * ps), S)
    page = tables[np.repeat(np.arange(S), width * ps), slot // ps]
    both = flat.reshape(-1, r + dr)
    pages = latent_attention.write_latent_rows(
        jnp.asarray(rng.randn(n_pages, rows_, lanes), dtype), jnp.asarray(page),
        jnp.asarray(slot % ps, jnp.int32), jnp.asarray(both[:, :r]), jnp.asarray(both[:, r:]))
    wk = rng.randn(H, dn, r) * r ** -0.5
    wv = rng.randn(H, r, dv) * r ** -0.5
    q_nope, q_rope = rng.randn(S, H, dn), rng.randn(S, H, dr)
    q_lat = jnp.asarray(np.einsum("bhd,hdr->bhr", q_nope, wk), dtype)
    q_rope = jnp.asarray(q_rope, dtype)
    # the textbook sees the values the cache and the call hold (rounded, in bf16)
    held = np.asarray(jnp.asarray(flat, dtype), np.float64)
    want = np.zeros((S, H, dv))
    for s, n in enumerate(lens):
        ctx = held[s, :n]
        sc = (np.einsum("hr,tr->ht", np.asarray(q_lat[s], np.float64), ctx[:, :r])
              + np.einsum("hd,td->ht", np.asarray(q_rope[s], np.float64), ctx[:, r:])) * 0.2
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want[s] = np.einsum("ht,tr,hrd->hd", p / p.sum(-1, keepdims=True), ctx[:, :r], wv)
    args = (q_lat, q_rope, pages, jnp.asarray(lens), jnp.asarray(tables))
    return args, wv, want


def _kernel(args, **grid):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return latent_attention.latent_decode_pallas(*args, sm_scale=0.2, **grid)


# 2 pages a KV block: a block is 64 tokens, a page 32, half a page 16
@pytest.mark.parametrize("lens,width", [
    ([5, 37, 70, 50], 8), ([32, 64, 96, 160], 8), ([64, 128, 192, 256], 8), ([1, 1, 1], 8),
    ([1, 250, 33, 64, 7, 129], 8), ([3, 40], 40), ([16, 17, 48, 49], 8),
    ([1 + (37 * i) % 131 for i in range(32)], 5), ([1 + (53 * i) % 97 for i in range(128)], 4),
], ids=["ends-on-a-slot", "ends-on-a-page", "ends-on-a-kv-block", "context-of-1",
        "very-unequal-lanes", "table-wider-than-any-context", "ends-on-half-a-page",
        "32-lanes", "128-lanes"])
def test_the_decode_kernel_is_the_jnp_path_and_the_textbook(lens, width):
    """The Pallas kernel (pages by DMA through the block table, a ring of
    KV blocks, halves of a page, the mask in a lane's last block) called
    directly: the ``jnp`` path's heads, and plain softmax(q k^T) v on the
    expanded K and V."""
    args, wv, want = _kernel_case(lens, width=width)
    got = _kernel(args, pages_per_block=2, blocks_in_ring=3)
    assert got.shape == args[0].shape and got.dtype == args[0].dtype
    assert np.all(np.isfinite(np.asarray(got)))
    jnp_path = latent_attention.latent_decode_jnp(*args, sm_scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp_path), atol=2e-5)
    np.testing.assert_allclose(
        np.einsum("bhr,hrd->bhd", np.asarray(got, np.float64), wv), want, atol=2e-5)


@pytest.mark.parametrize("grid", [
    {}, {"pages_per_block": 1, "blocks_in_ring": 2}, {"pages_per_block": 8, "blocks_in_ring": 4},
    {"pages_per_block": 64, "blocks_in_ring": 2},
], ids=["the-modules-constants", "one-page-two-buffers", "eight-pages-four-buffers",
        "a-block-wider-than-the-table"])
def test_the_decode_kernel_at_the_published_page_in_bfloat16(grid):
    """A.X-K1's own geometry (64 heads, ``[144, 128]`` pages of bf16),
    contexts around a page, the constants' KV block and the table's end:
    against the textbook to bf16's rounding of the weights and the output."""
    span = 32 * latent_attention._KERNEL_PAGES_PER_BLOCK
    args, wv, want = _kernel_case(
        [1, 33, span, span + 1, 2 * span - 31], H=64, r=512, dr=64,
        width=2 * span // 32 + 1, dtype=jnp.bfloat16, seed=3)
    assert args[2].shape[1:] == (144, 128)
    got = np.asarray(_kernel(args, **grid), np.float64)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(np.einsum("bhr,hrd->bhd", got, wv), want, atol=0.03)
    jnp_path = np.asarray(latent_attention.latent_decode_jnp(*args, sm_scale=0.2), np.float64)
    assert np.abs(got - jnp_path).max() < 0.05


def test_a_lanes_digits_do_not_depend_on_the_other_lanes():
    """A lane walks its own pages: its output is the same bits whatever the
    other lanes' lengths are (the benchmark's ``repeat_identical``)."""
    args, _, _ = _kernel_case([70, 150, 9, 200], seed=5)
    q_lat, q_rope, pages, lens, tables = args
    first = np.asarray(_kernel(args, pages_per_block=2))
    other = np.asarray(_kernel(
        (q_lat, q_rope, pages, jnp.asarray([1, 150, 256, 33], jnp.int32), tables),
        pages_per_block=2))
    np.testing.assert_array_equal(first[1], other[1])
    assert np.abs(first[0] - other[0]).max() > 1e-3


@pytest.mark.parametrize("backend,page,dtype,r,impl", [
    ("tpu", (144, 128), jnp.bfloat16, 512, "pallas"),   # a.x-k1-ep16-bf16
    ("tpu", (144, 128), jnp.float32, 512, "pallas"),
    ("cpu", (144, 128), jnp.bfloat16, 512, "jnp"),
    ("tpu", (20, 16), jnp.float32, 32, "jnp"),          # tiny-axk1-rehearsal's page
    ("tpu", (36, 128), jnp.bfloat16, 512, "jnp"),       # 8 tokens: half a page is no bf16 tile
    ("tpu", (144, 128), jnp.int8, 512, "jnp"),
], ids=["tpu-published", "tpu-float32", "cpu", "tpu-rehearsal-page", "tpu-8-token-page",
        "tpu-int8"])
def test_the_decode_call_chooses_by_backend_and_geometry_and_says_so(
        backend, page, dtype, r, impl, monkeypatch):
    """``"pallas"`` only on a TPU and for a page whose slices are whole
    tiles; the counter /metrics exports carries the choice."""
    pages = jnp.zeros((5, *page), dtype)
    assert latent_attention.decode_impl(backend, pages, r) == impl
    monkeypatch.setattr(latent_attention.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(ragged_attention, "_TRACED", ragged_attention._TRACED.copy())
    monkeypatch.setattr(ragged_attention, "_TRACED_IMPLS", dict(ragged_attention._TRACED_IMPLS))
    before = ragged_attention.traced_calls()
    dr = page[1] // 2
    traced = jax.make_jaxpr(lambda q, qr: latent_attention.latent_decode_attention(
        q, qr, pages, jnp.ones((4,), jnp.int32), jnp.zeros((4, 3), jnp.int32), sm_scale=0.2))(
        jnp.zeros((4, 8, r), jnp.float32), jnp.zeros((4, 8, dr), jnp.float32))
    after = ragged_attention.traced_calls()
    assert {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)} == {
        ("latent-decode", impl): 1}
    assert impl in ragged_attention.traced_impl("latent-decode").split("+")
    def kernels(jaxpr):
        return [name for e in jaxpr.eqns for name in (
            [e.params["name"]] if e.primitive.name == "pallas_call" else
            [n for sub in jax.core.jaxprs_in_params(e.params) for n in kernels(sub)])]

    assert kernels(traced.jaxpr) == (
        ["latent_decode_attention_kernel"] if impl == "pallas" else [])


@pytest.mark.parametrize("got,platform,fails", [
    ({"latent-decode/pallas": 7.0, "latent-ragged/jnp": 7.0}, "tpu", None),
    ({"latent-decode/jnp": 7.0, "latent-ragged/jnp": 7.0}, "tpu", "jnp path on a TPU"),
    ({"latent-decode/jnp": 7.0, "latent-decode/pallas": 7.0}, "tpu", "jnp path on a TPU"),
    ({"latent-decode/jnp": 7.0, "latent-ragged/jnp": 7.0}, "cpu", None),
    ({"latent-ragged/jnp": 7.0}, "tpu", "no decode-shaped"),
], ids=["kernel", "jnp-on-a-tpu", "both-on-a-tpu", "jnp-on-the-cpu", "never-decoded"])
def test_chip_smoke_fails_a_latent_worker_that_decoded_with_jnp_on_a_tpu(got, platform, fails):
    import chip_smoke

    if fails is None:
        chip_smoke.judge_attention_traced("aggregated", got, platform)
    else:
        with pytest.raises(chip_smoke.PhaseFailed, match=fails):
            chip_smoke.judge_attention_traced("aggregated", got, platform)


@pytest.mark.parametrize("got,platform,fails", [
    ({"wave/grouped/pallas": 6.0, "step/all_rows": 12.0}, "tpu", None),
    ({"wave/grouped/ragged_dot": 6.0, "step/all_rows": 12.0}, "tpu", None),   # widths no tile divides
    ({"wave/all_rows": 6.0, "step/all_rows": 12.0}, "tpu", "every held expert on every row"),
    ({"wave/all_rows": 6.0, "wave/grouped/pallas": 6.0}, "tpu", "every held expert on every row"),
    ({"wave/all_rows": 6.0}, "cpu", None),
    ({}, "tpu", None),                                                         # a dense model
], ids=["kernel", "xla", "all-rows-on-a-tpu", "both-on-a-tpu", "cpu", "dense"])
def test_chip_smoke_fails_a_sparse_worker_whose_waves_ran_every_row_on_a_tpu(got, platform, fails):
    import chip_smoke

    if fails is None:
        chip_smoke.judge_experts_traced("aggregated", got, platform)
    else:
        with pytest.raises(chip_smoke.PhaseFailed, match=fails):
            chip_smoke.judge_experts_traced("aggregated", got, platform)


@pytest.mark.parametrize("rows_per_block,pages_per_chunk", [(128, 8), (4, 2), (3, 1)],
                         ids=["one-block", "blocks-of-4", "blocks-of-3"])
def test_the_ragged_call_is_the_textbooks_over_rows_prefixes_blocks_and_chunks(
        rows_per_block, pages_per_chunk, monkeypatch):
    """Three sequences in one flat batch (10 rows behind 13 cached tokens, one
    row behind 20, 7 rows behind none) and padding rows after them: every row
    is causal softmax(q k^T) v over its own sequence's expanded keys, however
    the rows fall into blocks and the pages into chunks; padding rows are 0."""
    monkeypatch.setattr(latent_attention, "_RAGGED_QUERIES_PER_BLOCK", rows_per_block)
    monkeypatch.setattr(latent_attention, "_RAGGED_PAGES_PER_CHUNK", pages_per_chunk)
    rng = np.random.RandomState(11)
    H, dn, dr, dv, r, ps, P = 4, 16, 8, 16, 32, 8, 4
    q_lens, before = [10, 1, 7], [13, 20, 0]
    S, T = 4, 24                                   # a fourth table row unused; 6 padding rows
    rows_, lanes = latent_attention.latent_page_shape(ps, r, dr)
    flat = rng.randn(S * P * ps, r + dr).astype(np.float32)
    slot = np.arange(S * P * ps)
    pages = latent_attention.write_latent_rows(
        jnp.zeros((S * P + 1, rows_, lanes), jnp.float32), jnp.asarray(slot // ps, jnp.int32),
        jnp.asarray(slot % ps, jnp.int32), jnp.asarray(flat[:, :r]), jnp.asarray(flat[:, r:]))
    tables = jnp.asarray(np.arange(S * P).reshape(S, P), jnp.int32)
    wk = jnp.asarray(rng.randn(H, dn, r) * r ** -0.5, jnp.float32)
    wv = jnp.asarray(rng.randn(H, r, dv) * r ** -0.5, jnp.float32)
    q_nope = jnp.asarray(rng.randn(T, H, dn), jnp.float32)
    q_rope = jnp.asarray(rng.randn(T, H, dr), jnp.float32)
    cu = np.concatenate([[0], np.cumsum(q_lens), [sum(q_lens)]]).astype(np.int32)
    lens = np.asarray([b + n for b, n in zip(before, q_lens)] + [0], np.int32)
    got = np.asarray(latent_attention.latent_ragged_attention(
        q_nope, q_rope, wk, wv, pages, jnp.asarray(lens), tables, jnp.asarray(cu),
        jnp.asarray([3], jnp.int32), sm_scale=0.2))
    for s in range(3):
        ctx = flat[s * P * ps: s * P * ps + lens[s]]
        k = np.einsum("tr,hdr->thd", ctx[:, :r], np.asarray(wk))
        v = np.einsum("tr,hrd->thd", ctx[:, :r], np.asarray(wv))
        for i in range(q_lens[s]):
            row, seen = cu[s] + i, before[s] + i + 1
            sc = (np.einsum("hd,thd->ht", np.asarray(q_nope[row]), k[:seen])
                  + np.einsum("hd,td->ht", np.asarray(q_rope[row]), ctx[:seen, r:])) * 0.2
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), v[:seen])
            np.testing.assert_allclose(got[row], want, atol=2e-5)
    assert (got[sum(q_lens):] == 0).all()


@pytest.mark.parametrize("cuts", [[0, 44], [0, 3, 4, 9, 21, 44], [0, 4, 44]],
                         ids=["one-step", "odd-chunks", "half-page"])
def test_a_kr_row_keeps_both_its_tokens_however_the_steps_cut_them(cuts):
    """A page's ``kr`` rows hold two tokens each (slots ``t`` and ``t + ps /
    2``) and are written whole: the partner's half comes from the same step's
    rows or from the page as it stands, wherever a chunk ends."""
    rng = np.random.RandomState(7)
    r, dr, ps, n = 32, 8, 8, 44                      # two sequences of n tokens, 6 pages each
    rows_, lanes = latent_attention.latent_page_shape(ps, r, dr)
    flat = rng.randn(2, n, r + dr).astype(np.float32)
    tables = np.asarray([[3, 9, 1, 7, 5, 11], [2, 10, 0, 8, 6, 4]], np.int32)
    pages = jnp.full((13, rows_, lanes), 99.0, jnp.float32)
    for a, b in zip(cuts, cuts[1:]):                 # a ragged step: each sequence's rows a..b
        t = np.tile(np.arange(a, b), 2)
        s = np.repeat([0, 1], b - a)
        pages = latent_attention.write_latent_rows(
            pages, jnp.asarray(tables[s, t // ps]), jnp.asarray(t % ps, jnp.int32),
            jnp.asarray(flat[s, t, :r]), jnp.asarray(flat[s, t, r:]))
    ckv, kr = latent_attention._split(pages[jnp.asarray(tables)], r)   # [2, 6, tiles, ps, w]
    got = np.concatenate([np.moveaxis(np.asarray(ckv), 2, 3).reshape(2, 6 * ps, r),
                          np.asarray(kr).reshape(2, 6 * ps, dr)], axis=-1)
    np.testing.assert_array_equal(got[:, :n], flat)
    assert (got[:, n:] == 99.0).all() and (np.asarray(pages[12]) == 99.0).all()
