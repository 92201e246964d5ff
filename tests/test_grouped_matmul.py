"""``ops/grouped_matmul.py`` on the CPU: the grouped product against a loop
over the groups, the choice of implementation and of the Pallas kernel's
blocks at the two sparse cells' shapes, the rows a call visits, and the
tool that times one sparse layer alone (``tools/experts_bench.py``) at toy
shapes, the streamed grouped kernel of ``ops/expert_stream.py`` among its
variants. (Mosaic compiling the kernel for a described v5e at the cells'
shapes: ``tests/test_decode_attention.py``, which holds the topology.)"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import grouped_matmul as gm


@pytest.mark.parametrize("sizes", [
    [128, 128, 128],                 # whole tiles
    [3, 0, 200, 1, 0, 77],           # groups that do not fill a tile, empty ones among them
    [0, 0, 300],                     # the first groups empty
    [5] * 40,                        # many groups a tile
], ids=["whole-tiles", "ragged", "leading-empty", "many-a-tile"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_grouped_product_is_a_loop_over_the_groups(sizes, dtype):
    rs = np.random.RandomState(len(sizes))
    M, K, N = 384, 32, 48
    lhs = jnp.asarray(rs.randn(M, K), dtype)
    rhs = jnp.asarray(rs.randn(len(sizes), K, N), dtype)
    got = gm.grouped_matmul(lhs, rhs, jnp.asarray(sizes), impl="ragged_dot")
    assert got.shape == (M, N) and got.dtype == jnp.float32
    start = 0
    for g, n in enumerate(sizes):
        want = jnp.dot(lhs[start:start + n], rhs[g], preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got[start:start + n]), np.asarray(want),
                                   atol=1e-5 if dtype == jnp.float32 else 1e-2)
        start += n
    assert start <= M      # rows past the groups: unspecified, the caller masks them


def test_the_kernels_blocks_keep_k_whole_and_fit_the_scoped_vmem():
    """``K`` whole, so a group's weights are fetched once; the widest
    column block under the budget: the two cells' four products."""
    assert gm.tiling(2048, 3072, 2) == (128, 2048, 1024)     # LFM2 gate/up
    assert gm.tiling(1536, 2048, 2) == (128, 1536, 1024)     # LFM2 down
    assert gm.tiling(7168, 4096, 2) == (128, 7168, 256)      # A.X-K1 gate/up
    assert gm.tiling(2048, 7168, 2) == (128, 2048, 1024)     # A.X-K1 down
    for k, n in ((2048, 3072), (1536, 2048), (7168, 4096), (2048, 7168)):
        tm, tk, tn = gm.tiling(k, n, 2)
        assert tk == k and n % tn == 0 and tn % 128 == 0
        assert 2 * (tm * tk + tk * tn) * 2 + 3 * tm * tn * 4 <= gm._VMEM_BUDGET < 16 * 2 ** 20
    assert gm.tiling(100, 256, 4) is None and gm.tiling(256, 100, 4) is None   # no whole lanes
    assert gm.tiling(2 ** 16, 128, 4) is None                                  # K alone too large


@pytest.mark.parametrize("backend,dtype,dims,impl", [
    ("tpu", jnp.bfloat16, [(64, 2048, 3072), (64, 1536, 2048)], "pallas"),     # LFM2
    ("tpu", jnp.bfloat16, [(12, 7168, 4096), (12, 2048, 7168)], "pallas"),     # A.X-K1
    ("cpu", jnp.bfloat16, [(64, 2048, 3072), (64, 1536, 2048)], "ragged_dot"),
    ("tpu", jnp.bfloat16, [(8, 256, 256), (8, 128, 100)], "ragged_dot"),       # one product does not tile
    ("tpu", jnp.int8, [(8, 256, 256)], "ragged_dot"),
], ids=["tpu-lfm2", "tpu-axk1", "cpu", "tpu-no-tiling", "tpu-int8"])
def test_the_implementation_is_chosen_by_backend_dtype_and_shapes(backend, dtype, dims, impl):
    import jax

    weights = [jax.ShapeDtypeStruct(d, dtype) for d in dims]
    assert gm.impl(backend, dtype, *weights) == impl
    assert gm.tile_rows(impl) == (128 if impl == "pallas" else 1)
    # rows of another width than the weights: XLA's own
    assert gm.impl(backend, jnp.float32, *weights) == (
        "ragged_dot" if dtype != jnp.float32 else impl)


def test_the_traced_counter_joins_the_paths_of_a_shape(monkeypatch):
    monkeypatch.setattr(gm, "_TRACED", gm._TRACED.copy())
    gm._TRACED.clear()
    assert gm.traced_impl("wave") == "" and gm.traced_calls() == {}
    gm.count_traced("wave", "grouped/ragged_dot")
    gm.count_traced("wave", "grouped/pallas")
    gm.count_traced("wave", "grouped/pallas")
    gm.count_traced("step", "all_rows")
    assert gm.traced_calls() == {("wave", "grouped/ragged_dot"): 1, ("wave", "grouped/pallas"): 2,
                                 ("step", "all_rows"): 1}
    assert gm.traced_impl("wave") == "grouped/pallas+grouped/ragged_dot"
    assert gm.traced_impl("step") == "all_rows"


def test_the_experts_bench_runs_at_toy_shapes_and_refuses_the_cpu_otherwise(tmp_path, capsys):
    from tools import experts_bench

    with pytest.raises(SystemExit, match="no TPU"):
        experts_bench.main(["--shapes", "lfm2", "--rows", "128"])
    rc = experts_bench.main(["--toy", "--shapes", "lfm2,axk1", "--rows", "128,512",
                             "--routing", "random,one", "--pieces", "--out", str(tmp_path)])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "toy shapes" in printed and "grouped/ragged_dot" in printed and "permutation" in printed
    table = json.loads((tmp_path / "table.json").read_text())
    assert table["toy"] and not any("error" in line for line in table["lines"])
    by = {(l["shape"], l["rows"], l["routing"], l["variant"]): l for l in table["lines"]}
    # a step's width gets the stream kernel (interpreted here) beside the nine, a wave's does not
    assert len(by) == 2 * 2 * (10 + 9)
    for shape in ("lfm2", "axk1"):
        for routing in ("random", "one"):
            assert by[shape, 128, routing, "stream/pallas auto"]["max_abs_diff"] < 5e-2
            assert (shape, 512, routing, "stream/pallas auto") not in by
    for shape, held in (("lfm2", 16), ("axk1", 3)):
        for routing in ("random", "one"):
            wide = by[shape, 512, routing, "grouped/ragged_dot"]
            assert wide["max_abs_diff"] < 5e-2 and wide["rows_computed"] == wide["pairs_held"]
            assert by[shape, 512, routing, "serving"]["rows_computed"] == wide["pairs_held"]
            assert by[shape, 512, routing, "all_rows"]["rows_computed"] == held * 512
            assert "rows_computed" not in by[shape, 128, routing, "serving"]
            # the streamed kernel (interpreted): a touched expert's rows rounded up by under 64
            stream = by[shape, 512, routing, "grouped/stream auto"]
            assert stream["max_abs_diff"] < 5e-2 and stream["wave_impl"] == "ragged_dot"
            assert wide["pairs_held"] <= stream["rows_computed"] < (
                wide["pairs_held"] + 64 * stream["experts_touched"])
            assert stream["rows_computed"] % 64 == 0 and 0 < stream["experts_touched"] <= held
            assert stream["touched_bytes_ms"] == pytest.approx(
                stream["bytes_ms"] * stream["experts_touched"] / held)
    # every row on held expert 0 first: a share then holds more pairs than an even router sends it
    assert by["axk1", 512, "one", "all_rows"]["pairs_held"] > by["axk1", 512, "random", "all_rows"]["pairs_held"]
    assert by["lfm2", 512, "one", "all_rows"]["pairs_held"] == 512 * 4      # every expert held: every pair
    # as a megastep holds the layer: two dependent calls in one loop, the kernel at stated blocks
    rc = experts_bench.main(["--toy", "--shapes", "lfm2", "--rows", "32", "--in-loop", "2",
                             "--blocks", "auto,128x128x2", "--out", str(tmp_path / "loop")])
    looped = {l["variant"]: l for l in
              json.loads((tmp_path / "loop" / "table.json").read_text())["lines"]}
    assert rc == 0 and all(l["in_loop"] == 2 and "error" not in l for l in looped.values())
    assert looped["stream/pallas auto"]["max_abs_diff"] < 5e-2
    assert looped["stream/pallas 128x128x2"]["max_abs_diff"] < 5e-2
    assert set(experts_bench.SHAPES) == set(experts_bench.TOY) == {
        "lfm2", "axk1", "sdar", "laguna", "mimo"}
    assert experts_bench.SHAPES["lfm2"] == (2048, 1536, 64, 64, 4)
    assert experts_bench.SHAPES["axk1"] == (7168, 2048, 192, 12, 8)
    assert experts_bench.SHAPES["sdar"] == (2048, 768, 128, 128, 8)
    assert experts_bench.SHAPES["laguna"] == (3072, 1024, 256, 32, 10)
    assert experts_bench.SHAPES["mimo"] == (4096, 2048, 256, 16, 8)


@pytest.mark.parametrize("shape", ["sdar", "laguna", "mimo"])
def test_the_experts_bench_times_the_streamed_kernel_at_the_other_cells_toy_shapes(
        shape, tmp_path):
    """``sdar`` (every expert held, a router as skewed as the cell's),
    ``laguna`` and ``mimo`` (a chip's share of the experts): the streamed
    kernel at stated items beside the module's own, and its pieces."""
    from tools import experts_bench

    rc = experts_bench.main(["--toy", "--shapes", shape, "--rows", "384", "--routing", "skewed",
                             "--items", "auto,256x2", "--out", str(tmp_path)])
    lines = {l["variant"]: l for l in json.loads((tmp_path / "table.json").read_text())["lines"]}
    assert rc == 0 and not any("error" in l for l in lines.values())
    assert set(lines) == {"serving", "all_rows", "grouped/ragged_dot", "grouped/stream auto",
                          "grouped/stream 256x2"}
    for tag in ("grouped/stream auto", "grouped/stream 256x2"):
        assert lines[tag]["max_abs_diff"] < 5e-2
        assert lines[tag]["rows_computed"] >= lines[tag]["pairs_held"] > 0
    assert lines["serving"]["experts_touched"] <= experts_bench.TOY[shape][3]
