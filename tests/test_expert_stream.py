"""``ops/expert_stream.py`` on the CPU: the kernel under ``interpret=True``
at toy widths against ``model._experts_all_rows`` (the definition), the
chooser, and the counter and annotation that say which path a step got.
Mosaic's own verdict on the kernel at the served widths is
``tests/test_decode_attention.py``'s (a described v5e)."""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineCore, tiny_engine
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import tiny_lfm2
from dynamo_tpu.ops import expert_stream as es
from dynamo_tpu.ops import grouped_matmul as gm


def _case(rows: int, held: int, h: int, im: int, routing: str, seed: int = 0):
    """bf16 rows and weights; ``w_held`` zero where a row is not routed:
    ``random``: two held experts a row; ``one``: every row to expert 0
    alone; ``none``: as ``random`` but row 1 routed to no held expert."""
    rs = np.random.RandomState(seed)
    w = np.zeros((rows, held), np.float32)
    if routing == "one":
        w[:, 0] = rs.rand(rows) + 0.1
    else:
        picks = np.argsort(rs.rand(rows, held), axis=1)[:, :2]
        w[np.arange(rows)[:, None], picks] = rs.rand(rows, 2) + 0.1
        if routing == "none":
            w[1] = 0.0
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    normal = lambda key, dims, scale: (
        jax.random.normal(key, dims, jnp.float32) * scale).astype(jnp.bfloat16)
    return (normal(keys[0], (rows, h), 1.0), jnp.asarray(w),
            normal(keys[1], (held, h, 2 * im), h ** -0.5),
            normal(keys[2], (held, im, h), im ** -0.5))


@pytest.mark.parametrize("routing", ["random", "one", "none"])
@pytest.mark.parametrize("held,h,im", [(16, 256, 128), (3, 256, 128)], ids=["16-held", "3-held"])
@pytest.mark.parametrize("rows", [8, 24, 128, 256])
def test_the_kernel_is_every_held_expert_on_every_row(rows, held, h, im, routing):
    """Against ``_experts_all_rows`` with gate/up's K in two blocks (``tk``
    128 of 256), so that the one thing the kernel changes is exercised: the
    order of a product's float32 partial sums. That moves a gate/up sum by
    an ulp of float32, which now and then rounds ``silu(g) * u`` to the
    other bf16 neighbour: one part in 2^8 of ONE activation, times a down
    weight of ~im^-0.5, so the results may lie 2^-8 of the largest of them
    apart and no further (the readings are 0 to 6e-4 of ~4)."""
    xf, w_held, w_gu, w_down = _case(rows, held, h, im, routing)
    want = model_mod._experts_all_rows(xf, w_held, w_gu, w_down)
    got = es.expert_stream(xf, w_held, w_gu, w_down, tk=128, ti=128, interpret=True)
    assert got.shape == want.shape == (rows, h) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= 2.0 ** -8 * float(jnp.max(jnp.abs(want)))
    unrouted = np.flatnonzero(~np.asarray(w_held).any(axis=1))
    assert (routing == "none") == (1 in unrouted)
    assert not np.asarray(got)[unrouted].any()          # exact zeros, not small numbers
    assert np.asarray(got)[np.asarray(w_held).any(axis=1)].any(axis=1).all()
    again = es.expert_stream(xf, w_held, w_gu, w_down, tk=128, ti=128, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(again))
    # K whole in both products (the module's own blocks at these widths) and a
    # ring of two: the same sums
    whole = es.expert_stream(xf, w_held, w_gu, w_down, ring=2, interpret=True)
    assert float(jnp.max(jnp.abs(whole - want))) <= 2.0 ** -8 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("backend,dtype,rows,held,h,im,impl", [
    ("tpu", jnp.bfloat16, 128, 64, 2048, 1536, "stream/pallas"),     # LFM2's step
    ("tpu", jnp.bfloat16, 256, 64, 2048, 1536, "stream/pallas"),     # ... its widest
    ("tpu", jnp.bfloat16, 32, 12, 7168, 2048, "stream/pallas"),      # A.X-K1
    ("tpu", jnp.bfloat16, 256, 12, 7168, 2048, "stream/pallas"),
    ("tpu", jnp.float32, 128, 8, 256, 128, "stream/pallas"),
    ("cpu", jnp.bfloat16, 128, 64, 2048, 1536, "all_rows"),
    ("tpu", jnp.int8, 128, 64, 2048, 1536, "all_rows"),
    ("tpu", jnp.float32, 128, 8, 256, 64, "all_rows"),               # tiny-lfm2: im no whole lanes
    ("tpu", jnp.bfloat16, 128, 4, 64, 128, "all_rows"),              # h no whole lanes
    ("tpu", jnp.bfloat16, 256, 2, 32768, 16384, "all_rows"),         # the sums outgrow the VMEM
], ids=["tpu-lfm2-128", "tpu-lfm2-256", "tpu-axk1-32", "tpu-axk1-256", "tpu-f32", "cpu",
        "tpu-int8", "tpu-odd-im", "tpu-odd-h", "tpu-no-fit"])
def test_the_path_is_chosen_by_backend_dtype_and_shapes(backend, dtype, rows, held, h, im, impl):
    w_gu = jax.ShapeDtypeStruct((held, h, 2 * im), dtype)
    w_down = jax.ShapeDtypeStruct((held, im, h), dtype)
    assert es.impl(backend, dtype, rows, w_gu, w_down) == impl
    # rows of another width than the weights: the loop of XLA products
    other = jnp.float32 if dtype != jnp.float32 else jnp.bfloat16
    assert es.impl(backend, other, rows, w_gu, w_down) == "all_rows"
    if impl == "stream/pallas":
        tk, ti = es.blocks(rows, h, im, jnp.dtype(dtype).itemsize)
        assert h % tk == 0 and im % ti == 0 and tk % 128 == 0 and ti % 128 == 0
        size = jnp.dtype(dtype).itemsize
        assert max(tk * 2 * im, ti * h) * size <= es._BLOCK_BYTES
        assert es.vmem_bytes(rows, h, im, size, tk, ti) <= es._VMEM_LIMIT < 128 * 2 ** 20


def test_the_served_shapes_blocks_are_whole_rows_of_the_stored_arrays():
    assert es.blocks(128, 2048, 1536, 2) == (512, 768)       # LFM2: 3 MB slabs, 6 an expert
    assert es.blocks(128, 7168, 2048, 2) == (512, 256)       # A.X-K1: 4 and 3.5 MB, 22 an expert
    assert es.blocks(128, 2048, 1536, 2) == es.blocks(32, 2048, 1536, 2) == es.blocks(256, 2048, 1536, 2)


def _pallas_names(jaxpr) -> list[str]:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_names(sub)
    return names


def test_a_traced_step_counts_the_path_it_got_and_the_annotation_carries_it(monkeypatch):
    """``dynamo_engine_expert_calls_traced_total{shape="step"}`` and the
    ``experts`` of a megastep's ``engine/dispatch`` annotation read what
    ``impl`` chose where the layer was traced: ``all_rows`` on the CPU,
    ``stream/pallas`` where the backend says TPU and the widths fit."""
    cfg = dataclasses.replace(tiny_lfm2(), moe_intermediate_size=128)
    lp = model_mod.layer_params(model_mod.init_params(jax.random.PRNGKey(5), cfg), 2, cfg)
    x = jnp.zeros((128, cfg.hidden_size), jnp.float32)
    trace = lambda c, p: jax.make_jaxpr(lambda a: model_mod._shared_sparse_mlp(a, p, c))(x)
    monkeypatch.setattr(gm, "_TRACED", collections.Counter())
    assert _pallas_names(trace(cfg, lp).jaxpr) == []
    assert gm.traced_calls() == {("step", "all_rows"): 1}
    core = EngineCore(tiny_lfm2(), tiny_engine(), seed=5)
    assert core._experts_traced("megastep", 1024) == {"experts": "all_rows"}

    gm._TRACED.clear()
    monkeypatch.setattr(model_mod.jax, "default_backend", lambda: "tpu")
    assert _pallas_names(trace(cfg, lp).jaxpr) == ["expert_stream_kernel"]
    assert gm.traced_calls() == {("step", "stream/pallas"): 1}
    assert gm.traced_impl("step") == "stream/pallas" and gm.traced_impl("wave") == ""
    marks = []
    monkeypatch.setattr(core.clock, "mark", lambda *a, **kw: marks.append(kw))
    core._mark_dispatch("megastep", 128, 128, 8, 1024, 1024)
    assert marks[0]["experts"] == "stream/pallas"
    # the tiny rehearsal's own width (im 64: no whole lanes) keeps the loop of products there too
    tiny = tiny_lfm2()
    tiny_lp = model_mod.layer_params(model_mod.init_params(jax.random.PRNGKey(5), tiny), 2, tiny)
    assert _pallas_names(trace(tiny, tiny_lp).jaxpr) == []
    assert gm.traced_calls() == {("step", "stream/pallas"): 1, ("step", "all_rows"): 1}


# -- the grouped kernel: a wave's (and a block pass's) chosen pairs alone (PR 47) ----------


def _routed(rows: int, experts: int, held: int, k: int, routing: str, h: int = 256,
            im: int = 128, seed: int = 0):
    """(xf, w_held, chosen_held, w_gu, w_down): each row's ``k`` of
    ``experts``, the first ``held`` of them here. ``random``; ``one``: every
    row on held expert 0 first; ``few``: the odd held experts chosen by no
    row."""
    rs = np.random.RandomState(seed)
    score = rs.rand(rows, experts)
    if routing == "one":
        score[:, 0] = 2.0
    if routing == "few":
        score[:, 1:held:2] = -1.0
    idx = np.argsort(-score, axis=1)[:, :k]
    chosen = np.zeros((rows, experts), bool)
    chosen[np.arange(rows)[:, None], idx] = True
    chosen_held = jnp.asarray(chosen[:, :held])
    w_held = jnp.where(chosen_held, jnp.asarray(rs.rand(rows, held) + 0.1, jnp.float32), 0.0)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    normal = lambda key, dims, scale: (
        jax.random.normal(key, dims, jnp.float32) * scale).astype(jnp.bfloat16)
    return (normal(keys[0], (rows, h), 1.0), w_held, chosen_held,
            normal(keys[1], (held, h, 2 * im), h ** -0.5),
            normal(keys[2], (held, im, h), im ** -0.5))


_KERNEL = es.expert_stream_grouped


def _grouped_stream(case, k: int, all_held: bool, monkeypatch, **blocks):
    """``model._experts_grouped`` with the streamed kernel interpreted, at
    the blocks stated (the module's own where not)."""
    monkeypatch.setattr(es, "expert_stream_grouped",
                        functools.partial(_KERNEL, interpret=True, **blocks))
    return model_mod._experts_grouped.__wrapped__(*case, k=k, impl="stream", all_held=all_held)


@pytest.mark.parametrize("rows,experts,held,k,routing", [
    (300, 16, 16, 4, "random"),       # groups of ~75 rows: ends off the tile
    (300, 16, 16, 4, "one"),          # one expert holds every row: chunks of 256 and 64
    (700, 16, 16, 4, "one"),          # ... and two work items (700 rows > 512)
    (300, 48, 3, 8, "random"),        # a chip that holds 3 of 48: few places live
    (384, 8, 8, 2, "few"),            # experts no row chose among the others
    (257, 6, 6, 3, "random"),         # an odd count of rows
], ids=["random", "one-2-chunks", "one-2-items", "3-of-48-held", "untouched", "odd-rows"])
def test_the_grouped_kernel_is_every_chosen_pair_through_the_combine(
        rows, experts, held, k, routing, monkeypatch):
    """Against ``_experts_all_rows``, the definition, with gate/up's K in
    two blocks (``tk`` 128 of 256): the bound is the step kernel's (one
    bf16 rounding of ONE activation)."""
    case = _routed(rows, experts, held, k, routing)
    xf, w_held, chosen_held, w_gu, w_down = case
    want = model_mod._experts_all_rows(xf, w_held, w_gu, w_down)
    got = _grouped_stream(case, min(k, held), held == experts, monkeypatch, tk=128, ti=128)
    assert got.shape == want.shape == (rows, 256) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= 2.0 ** -8 * float(jnp.max(jnp.abs(want)))
    unrouted = ~np.asarray(chosen_held).any(axis=1)
    assert not np.asarray(got)[unrouted].any()           # exact zeros
    assert np.asarray(got)[~unrouted].any(axis=1).all()
    # K whole in both products (the module's own blocks at these widths): all_rows' own sums
    whole = _grouped_stream(case, min(k, held), held == experts, monkeypatch)
    assert float(jnp.max(jnp.abs(whole - want))) <= 1e-5


def test_an_expert_no_row_chose_is_never_read(monkeypatch):
    """Their weights are NaN: a finite result says the chain skipped them,
    fetch and product alike."""
    xf, w_held, chosen_held, w_gu, w_down = _routed(384, 8, 8, 2, "few")
    untouched = ~np.asarray(chosen_held).any(axis=0)
    assert untouched.sum() == 4
    poison = jnp.asarray(np.where(untouched, np.nan, 1.0), jnp.bfloat16)[:, None, None]
    got = _grouped_stream((xf, w_held, chosen_held, w_gu * poison, w_down * poison), 2, True,
                          monkeypatch, tk=128, ti=128)
    assert np.isfinite(np.asarray(got)).all()
    want = _grouped_stream((xf, w_held, chosen_held, w_gu, w_down), 2, True, monkeypatch,
                           tk=128, ti=128)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_rows_digits_are_its_own_whatever_its_neighbours(monkeypatch):
    """A row's result depends on that row and its experts alone: the other
    rows in another order, or as many rows again beside them, and its
    digits are the same (the blocking of K follows the widths, never a
    group's size)."""
    rows, k = 200, 4
    xf, w_held, chosen_held, w_gu, w_down = _routed(2 * rows, 16, 16, k, "random", seed=3)
    run = lambda x, w, c: np.asarray(_grouped_stream(
        (x, w, c, w_gu, w_down), k, True, monkeypatch, tk=128, ti=128))
    narrow = run(xf[:rows], w_held[:rows], chosen_held[:rows])
    wide = run(xf, w_held, chosen_held)                       # twice as wide: taller groups
    assert np.array_equal(narrow, wide[:rows])
    order = np.random.RandomState(4).permutation(rows)
    shuffled = run(xf[order], w_held[order], chosen_held[order])
    assert np.array_equal(narrow[order], shuffled)


@pytest.mark.parametrize("rows", [24, 128, 256])
def test_at_a_steps_shape_the_grouped_kernel_gives_the_step_kernels_digits(rows, monkeypatch):
    """Every held expert's range ``(0, N)`` is the step kernel's case: on
    the rows a step routes, the grouped kernel through the combine and
    ``expert_stream`` agree to the digit (the same blocks of K, the same
    order of float32 sums, an unchosen expert's term an exact zero)."""
    case = _routed(rows, 16, 16, 4, "random", seed=rows)
    xf, w_held, chosen_held, w_gu, w_down = case
    step = es.expert_stream(xf, w_held, w_gu, w_down, tk=128, ti=128, interpret=True)
    grouped = _grouped_stream(case, 4, True, monkeypatch, tk=128, ti=128)
    assert np.array_equal(np.asarray(step), np.asarray(grouped))


def test_the_grouped_kernels_places_start_on_whole_sublane_tiles():
    """``_sorted_pairs`` at the streamed kernel's alignment: a group's first
    place a multiple of ``GROUP_ALIGN``, a row's places ascending with the
    expert, and what the rounding costs in
    places (``Eh x 15`` at most) and in rows multiplied
    (``grouped_rows_visited``: each group's rows rounded up to whole 64s
    from ITS first row, so under ``pairs + Eh x 64``)."""
    xf, w_held, chosen_held, *_ = _routed(300, 16, 16, 4, "random")
    rows, counts, place, weight = model_mod._sorted_pairs(
        chosen_held, w_held, 4, es.SLAB_ROWS, es.GROUP_ALIGN)
    counts = np.asarray(counts)
    padded = -(-counts // es.GROUP_ALIGN) * es.GROUP_ALIGN
    start = np.cumsum(padded) - padded
    P = -(-(300 * 4 + 15 * 16) // es.SLAB_ROWS) * es.SLAB_ROWS
    assert rows.shape == (P,) and padded.sum() <= 300 * 4 + 15 * 16
    place, rows = np.asarray(place), np.asarray(rows)
    assert (np.diff(place, axis=1) > 0).all() and place.max() < padded.sum()
    for e in range(16):
        mine = np.flatnonzero(np.asarray(chosen_held)[:, e])
        assert start[e] % es.GROUP_ALIGN == 0
        assert np.array_equal(rows[start[e]:start[e] + counts[e]], mine)   # in row order
    visited = int(es.grouped_rows_visited(jnp.asarray(counts)))
    assert counts.sum() <= visited < counts.sum() + 64 * 16 and visited % 64 == 0
    # 130 rows: 128 + 64; 20: 64; none: 0; 1: 64; 700: 704 (an item of 512 and 192, or 704 of 1,024)
    assert int(es.grouped_rows_visited(jnp.asarray([130, 20, 0, 1, 700]))) == (
        192 + 64 + 0 + 64 + 704)
    # the unaligned places of the two-product paths: unchanged
    flat = model_mod._sorted_pairs(chosen_held, w_held, 4, 128)
    assert flat[0].shape == (1280,) and int(np.asarray(flat[2]).max()) == 1199


# rows of the call, the experts a token, the experts routed among, held, h, im
_CELLS = {
    "sdar-step": (1024, 8, 128, 128, 2048, 768),
    "sdar-wave": (2048, 8, 128, 128, 2048, 768),
    "lfm2-wave": (2048, 4, 64, 64, 2048, 1536),
    "laguna-wave": (2048, 10, 256, 32, 3072, 1024),
    "axk1-wave": (2048, 8, 192, 12, 7168, 2048),
    "mimo-wave": (2048, 8, 256, 16, 4096, 2048),
}


@pytest.mark.parametrize("cell,backend,dtype,label", [
    ("sdar-step", "tpu", jnp.bfloat16, "grouped/stream"),
    ("sdar-wave", "tpu", jnp.bfloat16, "grouped/stream"),
    ("lfm2-wave", "tpu", jnp.bfloat16, "grouped/stream"),
    ("laguna-wave", "tpu", jnp.bfloat16, "grouped/stream"),
    ("axk1-wave", "tpu", jnp.bfloat16, "grouped/stream"),
    ("mimo-wave", "tpu", jnp.bfloat16, "grouped/stream"),
    ("lfm2-wave", "tpu", jnp.int8, "grouped/ragged_dot"),
    ("lfm2-wave", "cpu", jnp.bfloat16, "grouped/ragged_dot"),
    ("sdar-step", "cpu", jnp.bfloat16, "grouped/ragged_dot"),
    ("odd-width", "tpu", jnp.bfloat16, "grouped/ragged_dot"),
    ("tall-groups", "tpu", jnp.bfloat16, "grouped/pallas"),
])
def test_a_waves_path_is_chosen_by_backend_dtype_widths_and_rows_a_group(
        cell, backend, dtype, label):
    """The five sparse cells' shapes each get the streamed kernel on a TPU
    (a later edit cannot move one to another path unseen); int8, the CPU
    and a width of no whole lanes keep XLA's ``ragged_dot``; groups many
    tiles tall, where the products and not the bytes bind, keep the
    library's grouped matmul."""
    rows, k, experts, held, h, im = {
        **_CELLS, "odd-width": (2048, 4, 64, 64, 2048, 1504),
        "tall-groups": (8192, 8, 16, 16, 2048, 1536)}[cell]
    w_gu = jax.ShapeDtypeStruct((held, h, 2 * im), dtype)
    w_down = jax.ShapeDtypeStruct((held, im, h), dtype)
    assert model_mod.expert_call_shape(rows) == "wave"
    got = model_mod.wave_impl(backend, dtype, rows * k / experts, w_gu, w_down)
    assert f"grouped/{got}" == label
    if got == "stream":
        tk, ti, item_rows, ring = es.grouped_blocks(h, im, 2)
        assert (tk, ti) == es.blocks(128, h, im, 2)          # a step's blocks of K: one row, one sum
        assert es.grouped_vmem_bytes(h, im, 2, tk, ti, item_rows, ring) <= es._VMEM_LIMIT
        assert item_rows in (512, 1024) and es._RING <= ring <= es._GROUPED_RING
        assert rows * k / experts <= es._GROUPED_ROWS_MAX


def test_a_traced_wave_counts_the_streamed_kernel_where_the_backend_is_a_tpu(monkeypatch):
    """``dynamo_engine_expert_calls_traced_total{shape="wave",
    impl="grouped/stream"}``: counted where the layer is traced, and the
    kernel in the program is the one kernel (no library product beside it)."""
    cfg = dataclasses.replace(tiny_lfm2(), moe_intermediate_size=128)
    lp = model_mod.layer_params(model_mod.init_params(jax.random.PRNGKey(5), cfg), 2, cfg)
    x = jnp.zeros((300, cfg.hidden_size), jnp.float32)
    stats = []
    trace = lambda: jax.make_jaxpr(
        lambda a: model_mod._shared_sparse_mlp(a, lp, cfg, expert_stats=stats))(x)
    monkeypatch.setattr(gm, "_TRACED", collections.Counter())
    assert _pallas_names(trace().jaxpr) == []
    assert gm.traced_calls() == {("wave", "grouped/ragged_dot"): 1}
    gm._TRACED.clear()
    monkeypatch.setattr(model_mod.jax, "default_backend", lambda: "tpu")
    assert _pallas_names(trace().jaxpr) == ["expert_stream_grouped_kernel"]
    assert gm.traced_calls() == {("wave", "grouped/stream"): 1}
    assert gm.traced_impl("wave") == "grouped/stream" and len(stats) == 2
