"""``ops/expert_stream.py`` on the CPU: the kernel under ``interpret=True``
at toy widths against ``model._experts_all_rows`` (the definition), the
chooser, and the counter and annotation that say which path a step got.
Mosaic's own verdict on the kernel at the served widths is
``tests/test_decode_attention.py``'s (a described v5e)."""

import collections
import dataclasses

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
