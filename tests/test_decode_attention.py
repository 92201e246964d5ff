"""The decode-shaped attention path (PR 28): ``cu_q_lens=None`` through
the serving entry against an independent dense softmax, that Mosaic takes
the library kernel at the decode grid and the served shapes (compiled for
a described v5e, no chip; the latent model's first-party kernel with them),
and how a program's shape routes a call."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import config as cfgmod
from dynamo_tpu.engine.model import decode_tokens, init_cache, init_params
from dynamo_tpu.ops import ragged_attention as ra
from tests.model_harness import prefill_chunk

# As chip_smoke.py's kernel checks: bf16 inputs and outputs against an
# f64 softmax over the same bf16 values.
ATOL, RTOL = 2.0 ** -5, 2e-2
PAGE_SIZE, HEAD_DIM = 32, 128
SM_SCALE = HEAD_DIM ** -0.5
# An inactive lane as decode_tokens writes it (1), page and block
# boundaries (32 / 33; 256 / 257; 512 / 513 with 16-page blocks), partly
# filled last pages, and contexts of several blocks.
KV_LENS = [1, 32, 33, 256, 257, 100, 511, 7, 64, 255, 513, 129, 2, 31, 512, 97]
WIDTH = 17


def _case(lanes: int, n_q: int, n_kv: int, seed: int = 0):
    """Shuffled page tables over a cache that is random everywhere, so a
    sequence's last page holds stale finite rows past ``kv_len`` (scaled
    up so that a leak shows)."""
    rng = np.random.RandomState(seed)
    lens = np.asarray((KV_LENS * 2)[:lanes], np.int32)
    n_pages = lanes * WIDTH + 1
    q = rng.randn(lanes, n_q, HEAD_DIM)
    kv = rng.randn(n_pages, PAGE_SIZE, 2 * n_kv, HEAD_DIM).astype(np.float32)
    tables = rng.permutation(n_pages)[: lanes * WIDTH].reshape(lanes, WIDTH)
    for s, n in enumerate(lens):
        for j in range(WIDTH):
            live = int(np.clip(n - j * PAGE_SIZE, 0, PAGE_SIZE))
            kv[tables[s, j], live:] *= 100.0
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(kv, jnp.bfloat16),
            jnp.asarray(lens), jnp.asarray(tables, jnp.int32))


def _dense_softmax(q, kv, lens, tables):
    """One query token a sequence, in numpy float64, sharing no code with
    the repo's reference."""
    q, kv = np.asarray(q, np.float64), np.asarray(kv, np.float64)
    lanes, n_q, d = q.shape
    n_kv = kv.shape[2] // 2
    out = np.zeros((lanes, n_q, d))
    for s in range(lanes):
        n = int(lens[s])
        rows = kv[np.asarray(tables[s])].reshape(-1, 2 * n_kv, d)[:n]
        for h in range(n_q):
            k, v = rows[:, 2 * (h // (n_q // n_kv))], rows[:, 2 * (h // (n_q // n_kv)) + 1]
            w = np.exp((scores := k @ q[s, h] * SM_SCALE) - scores.max())
            out[s, h] = (w / w.sum()) @ v
    return out


def _close(got, want):
    got = np.asarray(got, np.float64)
    assert np.all(np.isfinite(got))
    bad = np.abs(got - want) > ATOL + RTOL * np.abs(want)
    assert not bad.any(), f"max |diff| {np.max(np.abs(got - want))}"


@pytest.mark.parametrize("lanes", [8, 16, 32])
@pytest.mark.parametrize("n_q,n_kv", [(28, 4), (12, 2), (16, 16), (4, 4)])
def test_the_decode_shape_gives_one_tokens_attention_a_sequence(n_q, n_kv, lanes):
    """``cu_q_lens=None`` through the serving entry (on the CPU: the jnp
    reference reading it as ``arange(S + 1)``)."""
    q, kv, lens, tables = _case(lanes, n_q, n_kv, seed=lanes + n_q)
    got = ra.ragged_paged_attention(
        q, kv, lens, tables, None, jnp.asarray([lanes], jnp.int32),
        sm_scale=SM_SCALE)
    assert got.shape == q.shape and got.dtype == q.dtype
    _close(got, _dense_softmax(q, kv, lens, tables))


def test_the_decode_shape_is_what_an_arange_said():
    q, kv, lens, tables = _case(16, 12, 2, seed=3)
    num_seqs = jnp.asarray([16], jnp.int32)
    stated = ra.ragged_paged_attention(
        q, kv, lens, tables, None, num_seqs, sm_scale=SM_SCALE)
    spelled = ra.ragged_paged_attention(
        q, kv, lens, tables, jnp.arange(17, dtype=jnp.int32), num_seqs,
        sm_scale=SM_SCALE)
    np.testing.assert_array_equal(np.asarray(stated), np.asarray(spelled))


def test_int8_pages_give_the_attention_of_their_dequantised_values(monkeypatch):
    """``kv_scales`` (int8 pages) at the decode shape, 7 query heads a KV
    head: the reference dequantises as it gathers, the TPU path (its
    kernel stood in for by the reference) dequantises the pages the
    tables name and renumbers them. Both give the attention of the
    dequantised pages, and lie as near the unquantised pages' as int8
    allows: a value is off by at most amax / 254
    (``test_quantize_dequantize_error_bound``), |v| <= ~5 here."""
    from dynamo_tpu.engine.kv_quant import dequantize_kv, quantize_kv

    q, kv, lens, tables = _case(8, 28, 4, seed=8)
    kv8, scales = quantize_kv(kv)
    num_seqs = jnp.asarray([8], jnp.int32)
    want = _dense_softmax(q, dequantize_kv(kv8, scales), lens, tables)
    exact = _dense_softmax(q, kv, lens, tables)

    on_cpu = ra.ragged_paged_attention(
        q, kv8, lens, tables, None, num_seqs, sm_scale=SM_SCALE, kv_scales=scales)

    handed = []

    def kernel(q, pages, *args, **kw):
        handed.append(pages)
        return ra.ragged_paged_attention_ref(q, pages, *args, **kw)

    monkeypatch.setattr(ra.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ra, "pallas_ragged_attention", kernel)
    on_tpu = ra.ragged_paged_attention(
        q, kv8, lens, tables, None, num_seqs, sm_scale=SM_SCALE, kv_scales=scales)
    assert handed[0].dtype == q.dtype and handed[0].shape[0] == tables.size

    for got in (on_cpu, on_tpu):
        assert got.dtype == q.dtype
        _close(got, want)
        assert np.max(np.abs(np.asarray(got, np.float64) - exact)) < 0.06


# -- Mosaic takes the decode grid at the served shapes (a described v5e) -----------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("lanes,n_q,n_kv,page_size,width,n_pages", [
    (32, 28, 4, 32, 256, 3073),      # qwen7b-decode-batch
    (8, 12, 2, 32, 256, 11265),      # qwen1p5b-chat-steady, narrowest
    (32, 12, 2, 32, 256, 11265),     # ... and widest
    (8, 16, 16, 32, 64, 676),        # ouro2p6b-reason-decode
    (8, 4, 4, 32, 2, 9),             # a table narrower than the grid's pages
    # `--block-size` 64 / 128 / 256 at 8 KV heads or more (the llama-3-8b
    # preset's 32/8, Ouro's 16/16): 16 pages a block of these are refused
    # for VMEM, which is why the block is sized in tokens.
    (32, 32, 8, 64, 128, 2049),
    (32, 32, 8, 128, 64, 1025),
    (32, 32, 8, 256, 32, 513),
    (8, 16, 16, 64, 32, 338),
    (8, 16, 16, 128, 16, 169),
    (32, 28, 4, 128, 64, 769),
    (32, 32, 8, 16, 256, 4097),      # small pages: 16 of them, not 32
])
def test_mosaic_compiles_the_decode_grid_for_a_v5e(
        one_chip, lanes, n_q, n_kv, page_size, width, n_pages):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: ra.pallas_ragged_attention(*a, sm_scale=SM_SCALE)
    ).lower(
        sds((lanes, n_q, HEAD_DIM), jnp.bfloat16),
        sds((n_pages, page_size, 2 * n_kv, HEAD_DIM), jnp.bfloat16),
        sds((lanes,), jnp.int32), sds((lanes, width), jnp.int32),
        None, sds((1,), jnp.int32),
    ).compile()
    assert "ragged_paged_attention_kernel" in compiled.as_text()


@pytest.mark.parametrize("rows,decode,n_q,width,n_pages,window", [
    (48, True, 48, 338, 16385, None),     # laguna-s21-longctx-agents: a full layer's decode call
    (48, True, 72, 82, 1025, 512),        # ... a window layer's: groups of 9 heads a KV head
    (2048, False, 48, 338, 16385, None),  # ... its widest wave through a full layer
    (2048, False, 72, 82, 1025, 512),     # ... and through a window layer
    (256, False, 72, 82, 1025, 512),      # ... its narrowest
], ids=["full-decode", "window-decode", "full-wave", "window-wave", "window-wave-256"])
def test_mosaic_compiles_lagunas_attention_calls_for_a_v5e(
        one_chip, rows, decode, n_q, width, n_pages, window):
    """48 and 72 query heads on 8 KV heads (groups of 6 and of 9) at the
    cell's tables: the decode grid, and a wave's query block, which holds all
    the heads at once and is 32 queries for them (ops/ragged_attention.py,
    ``_WIDE_HEADS_QUERIES_PER_BLOCK``: 128 are refused for VMEM) beside a KV
    block of 1,024 tokens; ``sliding_window`` handed to the kernel for a
    window layer."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lanes = rows if decode else 8

    def call(q, kv, lens, tables, cu, ns):
        if not decode:   # a wave goes in pieces of a quarter window (model.dense_layer)
            lens, tables, cu, ns = ra.split_query_chunks(
                rows, lens, tables, cu, ns, chunk=128, page_size=32, window=window)
        return ra.pallas_ragged_attention(
            q, kv, lens, tables, None if decode else cu, ns, sm_scale=SM_SCALE, window=window)

    compiled = jax.jit(call).lower(
        sds((rows, n_q, HEAD_DIM), jnp.bfloat16),
        sds((n_pages, 32, 16, HEAD_DIM), jnp.bfloat16),
        sds((lanes,), jnp.int32), sds((lanes, width), jnp.int32),
        sds((lanes + 1,), jnp.int32), sds((1,), jnp.int32),
    ).compile()
    assert "ragged_paged_attention_kernel" in compiled.as_text()


def _ragged_case(q_lens, befores, *, window, page_size=4, width=13, rows=48, n_q=6, n_kv=2,
                 d=8, seed=0):
    """A ragged call as the engine states it: ``befores[s]`` keys of
    sequence ``s`` lie in the table before its first query (for a window
    call: from the table's first page on)."""
    rng = np.random.RandomState(seed)
    S = len(q_lens) + 1   # a dead sequence behind the live ones
    n_pages = S * width + 1
    tables = rng.permutation(n_pages)[: S * width].reshape(S, width)
    cu = np.zeros(S + 1, np.int32)
    cu[1:len(q_lens) + 1] = np.cumsum(q_lens)
    cu[len(q_lens) + 1:] = cu[len(q_lens)]
    lens = np.zeros(S, np.int32)
    lens[:len(q_lens)] = np.asarray(q_lens) + np.asarray(befores)
    assert lens.max() <= width * page_size and cu[-1] <= rows
    return (jnp.asarray(rng.randn(rows, n_q, d), jnp.float32),
            jnp.asarray(rng.randn(n_pages, page_size, 2 * n_kv, d), jnp.float32),
            jnp.asarray(lens), jnp.asarray(tables, jnp.int32), jnp.asarray(cu),
            jnp.asarray([len(q_lens)], jnp.int32)), int(cu[-1])


@pytest.mark.parametrize("chunk", [1, 2, 5, 8, 16])
@pytest.mark.parametrize("window,q_lens,befores", [
    (8, [13, 1, 20], [5, 8, 7]),     # a window call: at most window - 1 + page_size - 1 keys before
    (8, [40], [10]),                 # one sequence, many pieces
    (None, [13, 1, 20], [27, 8, 13]),
    (None, [13, 0, 20, 7], [27, 9, 13, 0]),   # a live sequence with no query
], ids=["window", "window-one", "full", "full-empty"])
def test_a_call_in_pieces_gives_the_whole_calls_attention(window, q_lens, befores, chunk):
    """``query_chunk``: every sequence goes on as pieces of at most that
    many queries, each with ``kv_lens`` up to its own last query and, for a
    window, a table from the page of its first query's oldest key: the
    same masks over fewer keys, so the same numbers (the kernel walks what
    the table holds: ops/ragged_attention.py, ``split_query_chunks``)."""
    args, live = _ragged_case(q_lens, befores, window=window)
    whole = ra.ragged_paged_attention(*args, sm_scale=0.3, window=window)
    pieces = jax.jit(lambda *a: ra.ragged_paged_attention(
        *a, sm_scale=0.3, window=window, query_chunk=chunk))(*args)
    np.testing.assert_allclose(pieces[:live], whole[:live], atol=2e-6)
    assert not np.any(np.asarray(pieces[live:]))


def test_a_windows_pieces_walk_the_window_and_not_the_chunk():
    """What the split is for: a piece's table is ``(window - 1 + chunk) /
    page_size + 2`` columns at most whatever the call's, and its
    ``kv_lens`` end at its own last query."""
    q, kv, lens, tables, cu, ns = _ragged_case([40], [10], window=8)[0]
    sub_lens, sub_tables, sub_cu, sub_ns = ra.split_query_chunks(
        q.shape[0], lens, tables, cu, ns, chunk=4, page_size=4, window=8)
    assert sub_tables.shape == (48 // 4 + 2, (3 + 7 + 4 + 3) // 4)
    assert int(sub_ns[0]) == 10 and sub_cu[:11].tolist() == list(range(0, 44, 4))
    # piece j's first query is at 10 + 4 j: its oldest key at 3 + 4 j, page (3 + 4 j) // 4 = j
    assert sub_lens[:10].tolist() == [10 + 4 * (j + 1) - 4 * j for j in range(10)]
    assert sub_tables[:10, 0].tolist() == tables[0, :10].tolist()


def test_the_wave_bench_refuses_the_cpu(monkeypatch):
    """``tools/attn_wave_bench.py`` times the TPU kernel whole against in
    pieces; on the CPU it would time the reference path under the kernel's
    name."""
    from tools import attn_wave_bench

    monkeypatch.setattr("sys.argv", ["attn_wave_bench"])
    with pytest.raises(SystemExit, match="chiprun"):
        attn_wave_bench.main()


@pytest.mark.parametrize("rows", [48, 2048])
def test_mosaic_compiles_lagunas_expert_layer_for_a_v5e(one_chip, rows):
    """The sparse layer A.X-K1 and LFM2 run, at Laguna's shape (32 held
    experts of 3072 x 1024, 10 a token of 256): a decode step's stream
    kernel and a wave's grouped product."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import expert_stream as es
    from dynamo_tpu.ops import grouped_matmul as gm

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    w_gu, w_down = sds((32, 3072, 2048), jnp.bfloat16), sds((32, 1024, 3072), jnp.bfloat16)
    x = sds((rows, 3072), jnp.bfloat16)
    if model.expert_call_shape(rows) == "step":
        assert es.impl("tpu", jnp.bfloat16, rows, w_gu, w_down) == "stream/pallas"
        compiled = jax.jit(es.expert_stream).lower(
            x, sds((rows, 32), jnp.float32), w_gu, w_down).compile()
        assert "expert_stream_kernel" in compiled.as_text()
    else:
        assert gm.impl("tpu", jnp.bfloat16, w_gu, w_down) == "pallas"
        compiled = jax.jit(
            lambda *a: model._experts_grouped(*a, k=10, impl="pallas", all_held=False)
        ).lower(x, sds((rows, 32), jnp.float32), sds((rows, 32), jnp.bool_), w_gu, w_down).compile()
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("lanes", [128, 32])
def test_mosaic_compiles_the_latent_decode_kernel_for_a_v5e(one_chip, lanes):
    """A.X-K1's absorbed decode call at the cell's two decode widths (64
    heads, pages of ``[144, 128]``, a table of 128 pages): the first-party
    kernel of ops/latent_attention.py (PR 34) at the module's constants."""
    from dynamo_tpu.ops import latent_attention as la

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pages = sds((12289, *la.latent_page_shape(32, 512, 64)), jnp.bfloat16)
    assert la.decode_impl("tpu", pages, 512) == "pallas"
    compiled = jax.jit(
        lambda *a: la.latent_decode_pallas(*a, sm_scale=192 ** -0.5)
    ).lower(
        sds((lanes, 64, 512), jnp.bfloat16), sds((lanes, 64, 64), jnp.bfloat16), pages,
        sds((lanes,), jnp.int32), sds((lanes, 128), jnp.int32),
    ).compile()
    assert "latent_decode_attention_kernel" in compiled.as_text()


@pytest.mark.parametrize("lanes", [48, 32])
def test_mosaic_compiles_the_linear_state_step_kernel_for_a_v5e(one_chip, lanes):
    """Olmo-Hybrid's decode step of ONE linear layer at the cell's two decode
    widths (30 heads, a float32 tile of ``[96, 192]`` a head, two side by
    side, a slab of 49 lane slots): the first-party kernel of
    ops/linear_attention.py at the module's constants, the state aliased in
    place. Beside it the full layers' library call at group 1 on the page as
    KEPT: 32 KV heads, ``(32, 64, 128)`` (the kernel refuses the published 30:
    "can not be XLA fully tiled"; ``ModelConfig.cache_kv_heads``)."""
    from dynamo_tpu.ops import linear_attention as la

    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    state = sds((49, 15, 96, 384))
    assert la.step_impl("tpu", state) == "pallas"
    compiled = jax.jit(la.gdn_step_pallas).lower(
        state, sds((lanes,), jnp.int32), sds((lanes, 30, 96)), sds((lanes, 30, 96)),
        sds((lanes, 30, 192)), sds((lanes, 30)), sds((lanes, 30)), sds((lanes,), jnp.bool_),
    ).compile()
    assert "gdn_step_kernel" in compiled.as_text()
    # in place: the slab is not copied to make the output (106 MB a layer a step)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20
    attn = jax.jit(lambda *a: ra.pallas_ragged_attention(*a, sm_scale=SM_SCALE)).lower(
        sds((lanes, 32, HEAD_DIM), jnp.bfloat16), sds((2049, 32, 64, HEAD_DIM), jnp.bfloat16),
        sds((lanes,), jnp.int32), sds((lanes, 128), jnp.int32), None, sds((1,), jnp.int32),
    ).compile()
    assert "ragged_paged_attention_kernel" in attn.as_text()
    with pytest.raises(ValueError, match="fully tiled"):
        jax.jit(lambda *a: ra.pallas_ragged_attention(*a, sm_scale=SM_SCALE)).lower(
            sds((lanes, 30, HEAD_DIM), jnp.bfloat16), sds((2049, 32, 60, HEAD_DIM), jnp.bfloat16),
            sds((lanes,), jnp.int32), sds((lanes, 128), jnp.int32), None, sds((1,), jnp.int32))


@pytest.mark.parametrize("kind,lanes", [("full", 32), ("full", 16), ("window", 32)])
def test_mosaic_compiles_the_wide_key_decode_kernel_for_a_v5e(one_chip, kind, lanes):
    """MiMo's decode call at the cell's two decode widths (64 query heads on
    4 KV heads of a full layer, pages of ``[320, 128]``, a table of 466
    pages; on 8 of a window layer with a sink, pages of ``[640, 128]``, the
    70-column window table): the first-party kernel of ops/gqa_attention.py
    (PR 46) at the module's constants."""
    from dynamo_tpu.ops import gqa_attention as ga

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    n_kv, width, n_pages, window = (4, 466, 15361, None) if kind == "full" else (8, 70, 273, 128)
    pages = sds((n_pages, *ga.gqa_page_shape(32, n_kv, 192, 128)), jnp.bfloat16)
    assert ga.decode_impl("tpu", pages, n_kv) == "pallas"
    compiled = jax.jit(
        lambda q, p, lens, tables, sinks: ga.gqa_decode_pallas(
            q, p, lens, tables, sinks if window else None, n_kv=n_kv, sm_scale=192 ** -0.5,
            window=window)
    ).lower(
        sds((lanes, 64, 192), jnp.bfloat16), pages, sds((lanes,), jnp.int32),
        sds((lanes, width), jnp.int32), sds((64,), jnp.float32),
    ).compile()
    assert "gqa_decode_attention_kernel" in compiled.as_text()


@pytest.mark.parametrize("rows", [2048, 512])
@pytest.mark.parametrize("kind", ["full", "window"])
def test_mosaic_compiles_the_wide_key_wave_kernel_for_a_v5e(one_chip, kind, rows):
    """MiMo's wave call at the cell's widest bucket and at the probe's (64
    query heads on 4 KV heads of a full layer over the 466-column table; on
    8 of a window layer with a sink over the 70-column window table; a
    wave's 8 table rows): the first-party kernel of ops/gqa_attention.py (PR
    48) at the module's constants, its states and a tile's queries inside
    the VMEM limit it asks for."""
    from dynamo_tpu.ops import gqa_attention as ga

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    n_kv, width, n_pages, window = (4, 466, 15361, None) if kind == "full" else (8, 70, 273, 128)
    pages = sds((n_pages, *ga.gqa_page_shape(32, n_kv, 192, 128)), jnp.bfloat16)
    assert ga.decode_impl("tpu", pages, n_kv) == "pallas"
    compiled = jax.jit(
        lambda q, p, lens, tables, cu, live, sinks: ga.gqa_ragged_pallas(
            q, p, lens, tables, cu, live, sinks if window else None, n_kv=n_kv,
            sm_scale=192 ** -0.5, window=window)
    ).lower(
        sds((rows, 64, 192), jnp.bfloat16), pages, sds((8,), jnp.int32),
        sds((8, width), jnp.int32), sds((9,), jnp.int32), sds((1,), jnp.int32),
        sds((64,), jnp.float32),
    ).compile()
    assert "gqa_ragged_attention_kernel" in compiled.as_text()


@pytest.mark.parametrize("rows,k,held,h,im", [
    (2048, 4, 64, 2048, 1536),      # lfm2-24b-hybrid-decode, its widest wave
    (512, 4, 64, 2048, 1536),       # ... and a narrow one
    (2048, 8, 12, 7168, 2048),      # axk1-ep16-decode
], ids=["lfm2-2048", "lfm2-512", "axk1-2048"])
def test_mosaic_compiles_the_grouped_expert_layer_for_a_v5e(one_chip, rows, k, held, h, im):
    """A sparse prefill wave's expert layer (``model._experts_grouped``, PR
    36) at the two sparse cells' shapes: the library's grouped matmul at the
    blocks ``ops/grouped_matmul.py:tiling`` chooses (K whole, under the
    scoped VMEM), once for gate/up and once for down, and no branch."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import grouped_matmul as gm

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    w_gu, w_down = sds((held, h, 2 * im), jnp.bfloat16), sds((held, im, h), jnp.bfloat16)
    assert gm.impl("tpu", jnp.bfloat16, w_gu, w_down) == "pallas"
    compiled = jax.jit(
        lambda *a: model._experts_grouped(*a, k=k, impl="pallas", all_held=held == 64)
    ).lower(
        sds((rows, h), jnp.bfloat16), sds((rows, held), jnp.float32), sds((rows, held), jnp.bool_),
        w_gu, w_down,
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "conditional(" not in text
    # the sorted rows, both products' results and the activation: the wave's temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < rows * k * (2 * h + 12 * im + 6 * h)


@pytest.mark.parametrize("rows,k,experts,held,h,im", [
    (1024, 8, 128, 128, 2048, 768),     # sdar30b-block-decode, a pass of its megastep
    (2048, 8, 128, 128, 2048, 768),     # ... and its widest wave
    (2048, 4, 64, 64, 2048, 1536),      # lfm2-24b-hybrid-decode
    (2048, 10, 256, 32, 3072, 1024),    # laguna-s21-longctx-agents
    (2048, 8, 192, 12, 7168, 2048),     # axk1-ep16-decode: three slabs
    (2048, 8, 256, 16, 4096, 2048),     # mimo-v25-ep16-longctx
], ids=["sdar-1024", "sdar-2048", "lfm2-2048", "laguna-2048", "axk1-2048", "mimo-2048"])
def test_mosaic_compiles_the_streamed_grouped_layer_for_a_v5e(
        one_chip, rows, k, experts, held, h, im):
    """The grouped expert layer as ONE kernel (``ops/expert_stream.py:
    expert_stream_grouped``, PR 47) at the five sparse cells' shapes, at the
    blocks the module chooses: one Mosaic call a slab, the gate/up sums and
    the activation in its VMEM, so the layer's temporaries are the sorted
    rows and the result and nothing ``[places, 2 im]``."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import expert_stream as es

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    w_gu, w_down = sds((held, h, 2 * im), jnp.bfloat16), sds((held, im, h), jnp.bfloat16)
    assert es.grouped_impl("tpu", jnp.bfloat16, rows * k / experts, w_gu, w_down) == "stream"
    compiled = jax.jit(
        lambda *a: model._experts_grouped(*a, k=min(k, held), impl="stream",
                                          all_held=held == experts)
    ).lower(
        sds((rows, h), jnp.bfloat16), sds((rows, held), jnp.float32), sds((rows, held), jnp.bool_),
        w_gu, w_down,
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "expert_stream_grouped_kernel" in text
    places = model._slab_places(rows * min(k, held) + 15 * held, h, im, 2, es.SLAB_ROWS)
    # (1.02-1.04 x the bf16 rows and the float32 result of one slab, by this reading)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.15 * places * (2 * h + 4 * h)


@pytest.mark.parametrize("rows", [32, 128, 256])
@pytest.mark.parametrize("held,h,im", [(64, 2048, 1536), (12, 7168, 2048)], ids=["lfm2", "axk1"])
def test_mosaic_compiles_the_expert_stream_kernel_for_a_v5e(one_chip, held, h, im, rows):
    """A decode step's expert layer (``ops/expert_stream.py``, PR 38) at the
    two sparse cells' shapes and the three widths a step has, at the blocks
    the module chooses: one Mosaic call, its rings and sums inside the VMEM
    limit it states, no copy of the experts beside them."""
    from dynamo_tpu.ops import expert_stream as es

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    w_gu, w_down = sds((held, h, 2 * im), jnp.bfloat16), sds((held, im, h), jnp.bfloat16)
    assert es.impl("tpu", jnp.bfloat16, rows, w_gu, w_down) == "stream/pallas"
    compiled = jax.jit(es.expert_stream).lower(
        sds((rows, h), jnp.bfloat16), sds((rows, held), jnp.float32), w_gu, w_down,
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "expert_stream_kernel" in text
    # the rows re-cut by K slab and the padded weights' columns: nothing a weight's size
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * h * 4


@pytest.mark.parametrize("kernel", ["ssd_step-128", "ssd_step-32", "stream-128", "stream-32",
                                    "grouped-2048", "attn-2048"])
def test_mosaic_compiles_nemotrons_kernels_for_a_v5e(one_chip, kernel):
    """Nemotron-3-Nano's two new kernels' worth of calls at the cell's shapes
    (PR 54): a Mamba-2 layer's decode step (64 heads, a float32 tile of ``[64,
    128]`` a head, a slab of 129 lane slots, the state aliased in place), and
    the UN-GATED expert layer 1,856 wide stored as 1,920 (``relu(x Wu)^2 Wd``:
    ``w_gu`` is ``[64, 2688, 1920]``) through the stream kernel at both decode
    widths and through the grouped kernel at the widest wave."""
    from dynamo_tpu.engine import model
    from dynamo_tpu.ops import expert_stream as es
    from dynamo_tpu.ops import ssm

    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    name, rows = kernel.split("-")
    rows = int(rows)
    if name == "attn":   # a wave of 32 query heads on 2 KV heads: 64 queries a block, not 128
        compiled = jax.jit(
            lambda *a: ra.pallas_ragged_attention(*a, sm_scale=128 ** -0.5)).lower(
            sds((rows, 32, 128), jnp.bfloat16), sds((12289, 32, 4, 128), jnp.bfloat16),
            sds((8,), jnp.int32), sds((8, 128), jnp.int32), sds((9,), jnp.int32),
            sds((1,), jnp.int32)).compile()
        assert "ragged_paged_attention_kernel" in compiled.as_text()
        return
    if name == "ssd_step":
        state = sds((129, 64, 64, 128))
        assert ssm.step_impl("tpu", state) == "pallas"
        compiled = jax.jit(ssm.ssd_step_pallas).lower(
            state, sds((rows,), jnp.int32), sds((rows, 64, 64)), sds((rows, 64)), sds((rows, 64)),
            sds((rows, 8, 128)), sds((rows, 8, 128)), sds((64,)), sds((rows,), jnp.bool_),
        ).compile()
        assert "ssd_step_kernel" in compiled.as_text()
        # in place: the slab is not copied to make the output (270 MB a layer a step)
        assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20
        return
    held, h, im = 64, 2688, 1920
    w_gu, w_down = sds((held, h, im), jnp.bfloat16), sds((held, im, h), jnp.bfloat16)
    if name == "stream":
        assert es.impl("tpu", jnp.bfloat16, rows, w_gu, w_down, gated=False) == "stream/pallas"
        assert es.impl("tpu", jnp.bfloat16, rows, sds((held, h, 1856), jnp.bfloat16),
                       sds((held, 1856, h), jnp.bfloat16), gated=False) == "all_rows"
        compiled = jax.jit(lambda *a: es.expert_stream(*a, gated=False)).lower(
            sds((rows, h), jnp.bfloat16), sds((rows, held)), w_gu, w_down).compile()
        assert "expert_stream_kernel" in compiled.as_text()
        return
    assert es.grouped_impl("tpu", jnp.bfloat16, rows * 6 / 128, w_gu, w_down, gated=False) == "stream"
    compiled = jax.jit(
        lambda *a: model._experts_grouped(*a, k=6, impl="stream", all_held=False, gated=False)
    ).lower(sds((rows, h), jnp.bfloat16), sds((rows, held)), sds((rows, held), jnp.bool_),
            w_gu, w_down).compile()
    assert "expert_stream_grouped_kernel" in compiled.as_text()


@pytest.mark.parametrize("page_size,width,grid", [
    (32, 256, (1, 16)),    # the three cells: what the sweep chose
    (32, 2, (1, 2)),       # never more pages than the table holds
    (8, 512, (1, 16)), (16, 256, (1, 16)),   # at most the 16 pages swept
    (64, 128, (1, 8)), (128, 64, (1, 4)), (256, 32, (1, 2)),
    (512, 16, (1, 1)), (1024, 8, (1, 1)),   # a page larger than the block
])
def test_the_decode_grids_kv_block_is_sized_in_tokens(page_size, width, grid):
    """At most 512 KV tokens a block at any page size, so that the
    block's VMEM buffers do not grow with ``--block-size``."""
    assert ra.decode_shape_grid(page_size, width) == grid


# -- routing: the program's shape selects the grid ---------------------------------


def _pallas_calls(jaxpr) -> list[tuple[str, tuple]]:
    """(name, grid) of every pallas_call in a jaxpr."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls += _pallas_calls(sub)
    return calls


def _delta(before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in ra.traced_calls().items()
            if n != before.get(k, 0)}


def test_on_a_tpu_the_decode_shape_gets_one_sequence_a_query_block(monkeypatch, caplog):
    """Traced with the backend reading "tpu": ``cu_q_lens=None`` gives the
    library kernel a query block per sequence, a ragged ``cu_q_lens`` of
    the same rows the small-call grid; both device ops carry the name
    the benchmark's readers look for, and the choice is said once."""
    monkeypatch.setattr(ra.jax, "default_backend", lambda: "tpu")
    ra._announce.cache_clear()
    # (4 heads on 4 of a page's 8 combined rows: a group of one whose token is
    # no octet of heads, which neither first-party kernel takes)
    q, kv, lens, tables = _case(32, 4, 4)
    num_seqs = jnp.asarray([32], jnp.int32)

    before = ra.traced_calls()
    with caplog.at_level("INFO", logger="dynamo_tpu.ops.ragged_attention"):
        decode = jax.make_jaxpr(lambda *a: ra.ragged_paged_attention(
            *a, None, num_seqs, sm_scale=SM_SCALE))(q, kv, lens, tables)
    assert _delta(before) == {("decode", "library"): 1}
    assert _pallas_calls(decode.jaxpr) == [("ragged_paged_attention_kernel", (1, 32))]
    assert any("decode shape, grid (1, 16)" in r.message for r in caplog.records)

    before = ra.traced_calls()
    ragged = jax.make_jaxpr(lambda *a: ra.ragged_paged_attention(
        *a, jnp.arange(33, dtype=jnp.int32), num_seqs, sm_scale=SM_SCALE))(
        q, kv, lens, tables)
    assert _delta(before) == {("ragged", "library"): 1}
    assert _pallas_calls(ragged.jaxpr) == [("ragged_paged_attention_kernel", (1, 4))]


@pytest.mark.parametrize("rows,width,grid", [
    (40, WIDTH, (8, 8)),      # verify rows, a small chunk
    (2048, WIDTH, (128, 8)),  # a prefill wave
    (40, 4, (8, 4)),          # a table narrower than the KV block
])
def test_a_ragged_call_gets_the_grid_the_cells_were_compiled_with(
        monkeypatch, rows, width, grid):
    """(queries, KV pages) a block that the library kernel is handed for a
    ragged ``cu_q_lens``: constants of this module, and the values the
    benchmark's programs were compiled with."""
    import jax.experimental.pallas.ops.tpu.ragged_paged_attention as library

    handed = []

    def kernel(q, *args, num_queries_per_block, num_kv_pages_per_block, **kw):
        handed.append((num_queries_per_block, num_kv_pages_per_block))
        return q

    monkeypatch.setattr(library, "ragged_paged_attention", kernel)
    ra.pallas_ragged_attention(
        jnp.zeros((rows, 4, HEAD_DIM), jnp.bfloat16),
        jnp.zeros((9, PAGE_SIZE, 8, HEAD_DIM), jnp.bfloat16),
        jnp.ones((2,), jnp.int32), jnp.zeros((2, width), jnp.int32),
        jnp.asarray([0, rows // 2, rows], jnp.int32), jnp.asarray([2], jnp.int32),
        sm_scale=SM_SCALE)
    assert handed == [grid]


def test_the_decode_grid_never_asks_for_more_pages_than_the_table_has(monkeypatch):
    """The library refuses, at trace time, a block of more pages than a
    sequence's table holds: a table of 2 gets 2, not the grid's 16."""
    monkeypatch.setattr(ra.jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((8, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((9, 32, 8, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: ra.ragged_paged_attention(
        *a, None, jnp.asarray([8], jnp.int32), sm_scale=1.0))(
        q, kv, jnp.ones((8,), jnp.int32), jnp.zeros((8, 2), jnp.int32))
    assert _pallas_calls(jaxpr.jaxpr) == [("ragged_paged_attention_kernel", (1, 8))]


def test_decode_tokens_states_the_decode_shape_and_prefill_does_not():
    """``decode_tokens`` traces every layer's attention as the decode
    shape; ``forward_tokens`` under a ragged ``cu_q_lens`` (a prefill
    chunk) as ragged. Read from the counter behind
    ``dynamo_engine_attention_calls_traced_total``; on the CPU both run
    the reference, and give what they gave before (test_engine_model)."""
    cfg, eng = cfgmod.tiny_model(), cfgmod.tiny_engine()
    params = init_params(jax.random.PRNGKey(0), cfg)
    B = eng.max_num_seqs
    tables = jnp.zeros((B, eng.max_blocks_per_seq), jnp.int32)

    before = ra.traced_calls()
    jax.eval_shape(
        lambda p, c: decode_tokens(
            p, c, jnp.zeros((B,), jnp.int32), tables, jnp.zeros((B,), jnp.int32),
            jnp.ones((B,), bool), cfg, eng),
        params, init_cache(cfg, eng))
    assert _delta(before) == {("decode", "reference"): cfg.num_layers}
    # What the dispatch annotation's ``attn`` carries (other tests of this
    # process may have traced other implementations too).
    assert "reference" in ra.traced_impl("decode").split("+")

    before = ra.traced_calls()
    prefill_chunk(params, init_cache(cfg, eng), [1, 2, 3], 0, [0], cfg, eng, 32)
    assert _delta(before) == {("ragged", "reference"): cfg.num_layers}


def test_the_traced_counter_is_on_metrics():
    from prometheus_client import CollectorRegistry, generate_latest

    from dynamo_tpu.runtime.status_server import _EngineCounters

    for _ in range(28):
        ra._count_traced("decode", "library")
    try:
        registry = CollectorRegistry()
        registry.register(_EngineCounters(lambda: {}, lambda: {}))
        text = generate_latest(registry).decode()
    finally:
        with ra._TRACED_LOCK:
            ra._TRACED["decode", "library"] -= 28
    line = next(l for l in text.splitlines() if l.startswith(
        'dynamo_engine_attention_calls_traced_total{impl="library"')
        and 'shape="decode"' in l)
    assert 'service="engine"' in line and float(line.split()[-1]) >= 28


# -- the first-party kernel of group-1 decode calls (PR 51, ops/mha_attention.py) ---


def _mha_case(heads: int, page_heads: int, page_size: int, lens, seed: int = 0):
    """A decode call of ``heads`` query heads over pages that keep
    ``page_heads`` KV heads, the spare heads' rows and every slot past a
    lane's ``kv_len`` holding large finite numbers: a kernel that reads
    either is far off."""
    rng = np.random.RandomState(seed)
    width = -(-max(lens) // page_size) + 1
    n_pages = len(lens) * width + 1
    kv = rng.randn(n_pages, page_size, 2 * page_heads, HEAD_DIM).astype(np.float32)
    kv[:, :, 2 * heads:] *= 50.0
    tables = rng.permutation(n_pages)[: len(lens) * width].reshape(len(lens), width)
    for s, n in enumerate(lens):
        for j in range(width):
            kv[tables[s, j], int(np.clip(n - j * page_size, 0, page_size)):] *= 100.0
    q = rng.randn(len(lens), heads, HEAD_DIM)
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(kv, jnp.bfloat16),
            jnp.asarray(lens, jnp.int32), jnp.asarray(tables, jnp.int32))


# a KV block of the kernel here: 128 tokens (4 pages of 32, 8 of 16)
@pytest.mark.parametrize("lens,live", [
    ((1, 200), 2),            # a lane of one token beside a longer one
    ((127, 1), 2),            # one under a block's edge
    ((128, 129), 2),          # at it and one over
    ((300, 40), 2),           # a partial last page, blocks of a lane behind another's
    ((257, 64, 500), 2),      # a lane past num_seqs: zeros, and no link of the chain
    ((90, 33), 0),            # no live lane at all
], ids=["one", "under", "at-over", "partial", "dead-lane", "none-live"])
@pytest.mark.parametrize("heads,page_heads,page_size", [(16, 16, 32), (30, 32, 32), (6, 8, 16)],
                         ids=["16of16", "30of32", "6of8-small-page"])
def test_the_group_one_kernel_gives_the_references_attention(
        heads, page_heads, page_size, lens, live):
    """``mha_decode_pallas`` under Pallas' TPU interpreter against
    ``ragged_paged_attention_ref`` (the spare heads' queries zeros, as the
    library kernel gets them) and the float64 softmax."""
    from dynamo_tpu.ops.mha_attention import mha_decode_pallas

    q, kv, kv_lens, tables = _mha_case(heads, page_heads, page_size, lens, seed=len(lens) + heads)
    num_seqs = jnp.asarray([live], jnp.int32)
    got = jax.block_until_ready(mha_decode_pallas(
        q, kv, kv_lens, tables, num_seqs, sm_scale=SM_SCALE,
        pages_per_block=128 // page_size, blocks_in_ring=2, interpret=True))
    assert got.shape == q.shape and got.dtype == q.dtype
    want = ra.ragged_paged_attention_ref(
        jnp.pad(q, ((0, 0), (0, page_heads - heads), (0, 0))), kv, kv_lens, tables, None,
        num_seqs, sm_scale=SM_SCALE)[:, :heads]
    _close(got, np.asarray(want, np.float64))
    exact = _dense_softmax(q[:live], kv[:, :, :2 * heads], kv_lens[:live], tables[:live])
    _close(got[:live], exact)
    assert not np.asarray(got[live:], np.float32).any()


@pytest.mark.parametrize("lanes,heads,page_heads,width,n_pages", [
    (8, 16, 16, 64, 676),       # ouro2p6b-reason-decode
    (48, 30, 32, 128, 2049),    # olmo-hybrid-7b-reason-decode, its two decode widths
    (16, 30, 32, 128, 2049),
])
def test_mosaic_compiles_the_group_one_decode_kernel_for_a_v5e(
        one_chip, lanes, heads, page_heads, width, n_pages):
    """The first-party kernel at the two cells' shapes and the module's
    constants; the page array is handed over by a bitcast, no copy."""
    from dynamo_tpu.ops import mha_attention as ma

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((lanes, heads, HEAD_DIM), jnp.bfloat16)
    pages = sds((n_pages, PAGE_SIZE, 2 * page_heads, HEAD_DIM), jnp.bfloat16)
    assert ma.fits("tpu", q, pages)
    text = jax.jit(
        lambda *a: ma.mha_decode_pallas(*a, sm_scale=SM_SCALE)
    ).lower(q, pages, sds((lanes,), jnp.int32), sds((lanes, width), jnp.int32),
            sds((1,), jnp.int32)).compile().as_text()
    assert MHA_KERNEL in text
    assert not [l for l in text.splitlines() if " copy(" in l and f"bf16[{n_pages}," in l]


MHA_KERNEL = "ragged_paged_attention_mha_decode_kernel"
GROUPED_KERNEL = "ragged_paged_attention_grouped_decode_kernel"
LIBRARY_KERNEL = "ragged_paged_attention_kernel"


def _rule_case(name: str):
    """(q heads, KV heads of the page, head width, kwargs of the entry, what
    ``num_kv_heads`` says)."""
    cu = jnp.arange(9, dtype=jnp.int32)
    return {
        "group-1": (16, 16, 128, {}, None),
        "group-1-spare-heads": (30, 32, 128, {}, 30),
        "group-7": (28, 4, 128, {}, None),               # qwen7b-decode-batch
        "group-6": (12, 2, 128, {}, None),               # qwen1p5b-chat-steady
        "group-8-paired": (32, 4, 128, {}, None),        # lfm2: 4 paired rows of 128
        "group-6-of-8": (48, 8, 128, {}, None),          # laguna's full layers
        "group-16": (32, 2, 128, {}, None),              # nemotron's attention blocks
        "group-2-of-16": (32, 16, 128, {}, None),
        "group-7-of-1": (7, 1, 128, {}, None),           # a page of 8 KB: not measured
        "group-2-spare-heads": (60, 32, 128, {}, 30),
        "window": (16, 16, 128, {"window": 64}, None),
        "window-group-9": (72, 8, 128, {"window": 512}, None),
        "kv_scales": (16, 16, 128, {"kv_scales": True}, None),
        "kv_scales-group-7": (28, 4, 128, {"kv_scales": True}, None),
        "ragged": (16, 16, 128, {"cu_q_lens": cu}, None),
        "ragged-group-7": (28, 4, 128, {"cu_q_lens": cu}, None),
        "ragged-spare-heads": (30, 32, 128, {"cu_q_lens": cu}, 30),
        "head-64": (16, 16, 64, {}, None),
        "head-64-group-4": (16, 4, 64, {}, None),
    }[name]


@pytest.mark.parametrize("backend,name,shape,impl,kernel", [
    ("tpu", "group-1", "decode", "pallas", MHA_KERNEL),
    ("tpu", "group-1-spare-heads", "decode", "pallas", MHA_KERNEL),
    ("tpu", "group-7", "decode", "pallas", GROUPED_KERNEL),
    ("tpu", "group-6", "decode", "pallas", GROUPED_KERNEL),
    ("tpu", "group-8-paired", "decode", "pallas", GROUPED_KERNEL),
    ("tpu", "group-6-of-8", "decode", "pallas", GROUPED_KERNEL),
    ("tpu", "group-16", "decode", "pallas", GROUPED_KERNEL),
    ("tpu", "group-2-of-16", "decode", "pallas", GROUPED_KERNEL),
    ("tpu", "group-7-of-1", "decode", "library", LIBRARY_KERNEL),
    ("tpu", "group-2-spare-heads", "decode", "library", LIBRARY_KERNEL),
    ("tpu", "window", "window-decode", "library", LIBRARY_KERNEL),
    ("tpu", "window-group-9", "window-decode", "library", LIBRARY_KERNEL),
    ("tpu", "kv_scales", "decode", "library", LIBRARY_KERNEL),
    ("tpu", "kv_scales-group-7", "decode", "library", LIBRARY_KERNEL),
    ("tpu", "ragged", "ragged", "library", LIBRARY_KERNEL),
    ("tpu", "ragged-group-7", "ragged", "library", LIBRARY_KERNEL),
    ("tpu", "ragged-spare-heads", "ragged", "library", LIBRARY_KERNEL),
    ("tpu", "head-64", "decode", "reference", None),
    ("tpu", "head-64-group-4", "decode", "reference", None),
    ("cpu", "group-1", "decode", "reference", None),
    ("cpu", "group-1-spare-heads", "decode", "reference", None),
    ("cpu", "group-7", "decode", "reference", None),
])
def test_a_decode_call_on_a_tpu_gets_the_first_party_kernel_its_geometry_fits(
        monkeypatch, backend, name, shape, impl, kernel):
    """The rule of ops/ragged_attention.py, from what a call can observe:
    decode shape, a TPU, 128-wide heads, bfloat16 pages, no window and no
    int8 scales -> ``impl="pallas"``: group 1 the kernel of
    ops/mha_attention.py, a group of 2 or more that of
    ops/grouped_attention.py; every other call the library kernel or the
    reference, counted as before."""
    monkeypatch.setattr(ra.jax, "default_backend", lambda: backend)
    heads, page_heads, d, kw, num_kv_heads = _rule_case(name)
    kw = dict(kw)
    cu = kw.pop("cu_q_lens", None)
    kv = jnp.zeros((9, PAGE_SIZE, 2 * page_heads, d), jnp.bfloat16)
    if kw.pop("kv_scales", False):
        kv, kw["kv_scales"] = kv.astype(jnp.int8), jnp.ones(kv.shape[:3], jnp.float32)
    q = jnp.zeros((8, heads, d), jnp.bfloat16)
    assert ra.decode_impl(backend, q, kv, cu, num_kv_heads=num_kv_heads, **kw) == impl
    before = ra.traced_calls()
    jaxpr = jax.make_jaxpr(lambda q, kv, lens, tables: ra.ragged_paged_attention(
        q, kv, lens, tables, cu, jnp.asarray([8], jnp.int32), sm_scale=1.0,
        num_kv_heads=num_kv_heads, **kw))(
        q, kv, jnp.ones((8,), jnp.int32), jnp.zeros((8, 4), jnp.int32))
    assert _delta(before) == {(shape, impl): 1}
    assert [n for n, _ in _pallas_calls(jaxpr.jaxpr)] == ([kernel] if kernel else [])
    assert jaxpr.out_avals[0].shape == q.shape


def test_the_log_says_which_first_party_kernel_a_call_took(monkeypatch, caplog):
    monkeypatch.setattr(ra.jax, "default_backend", lambda: "tpu")
    ra._announce.cache_clear()
    with caplog.at_level("INFO", logger="dynamo_tpu.ops.ragged_attention"):
        for heads, page_heads in ((28, 4), (16, 16)):
            jax.eval_shape(lambda q, kv, lens, tables: ra.ragged_paged_attention(
                q, kv, lens, tables, None, jnp.asarray([8], jnp.int32), sm_scale=1.0),
                jnp.zeros((8, heads, HEAD_DIM), jnp.bfloat16),
                jnp.zeros((9, PAGE_SIZE, 2 * page_heads, HEAD_DIM), jnp.bfloat16),
                jnp.ones((8,), jnp.int32), jnp.zeros((8, 256), jnp.int32))
    said = [r.message for r in caplog.records if "first-party" in r.message]
    assert len(said) == 2
    assert "a group of 7" in said[0] and "16 pages a KV block" in said[0]
    assert "group 1" in said[1]


@pytest.mark.parametrize("shape", ["block-decode", "block-ragged"])
def test_a_block_call_keeps_the_library_kernel(monkeypatch, shape):
    """``block_attention`` (SDAR's folded call of 128 heads) asks no
    ``decode_impl``: the library kernel at the decode grid, as before PR 55."""
    monkeypatch.setattr(ra.jax, "default_backend", lambda: "tpu")
    before = ra.traced_calls()
    jaxpr = jax.make_jaxpr(lambda q, kv, ends, tables: ra.block_attention(
        q, kv, ends, tables, jnp.asarray([8], jnp.int32), block_length=4, sm_scale=1.0,
        shape=shape))(
        jnp.zeros((32, 32, HEAD_DIM), jnp.bfloat16),
        jnp.zeros((9, PAGE_SIZE, 8, HEAD_DIM), jnp.bfloat16),
        jnp.ones((8,), jnp.int32), jnp.zeros((8, 4), jnp.int32))
    assert _delta(before) == {(shape, "library"): 1}
    assert _pallas_calls(jaxpr.jaxpr) == [(LIBRARY_KERNEL, (1, 8))]


# -- the first-party kernel of grouped decode calls (PR 55, ops/grouped_attention.py)


def _grouped_case(heads: int, n_kv: int, lens, seed: int = 0):
    """As :func:`_mha_case`: every slot past a lane's ``kv_len`` holds large
    finite numbers, the pages shuffled."""
    q, kv, kv_lens, tables = _mha_case(n_kv, n_kv, PAGE_SIZE, lens, seed=seed)
    q = np.random.RandomState(seed + 1).randn(len(lens), heads, HEAD_DIM)
    return jnp.asarray(q, jnp.bfloat16), kv, kv_lens, tables


# a KV block of the kernel here: its least, four quarters of whole tiles of 128
# word rows (4 pages of 32 tokens at 4 KV heads and at 8, 8 pages at 2)
@pytest.mark.parametrize("lens,live", [
    ((1, 200), 2),               # a lane of one token beside a longer one
    ((257, 64, 500), 2),         # contexts that end mid-page; a dead lane after the live ones
    ((300, 40, 129, 1, 512), 5), # a block's edge, a quarter's, blocks of a lane behind another's
    ((90, 33), 0),               # no live lane at all
], ids=["one", "dead-lane", "edges", "none-live"])
@pytest.mark.parametrize("heads,n_kv", [(28, 4), (12, 2), (32, 4), (48, 8), (32, 2)],
                         ids=["28of4", "12of2", "32of4", "48of8", "32of2"])
def test_the_grouped_kernel_gives_the_references_attention(heads, n_kv, lens, live):
    """``grouped_decode_pallas`` under Pallas' TPU interpreter against
    ``ragged_paged_attention_ref`` and the float64 softmax, at the cells'
    heads: a group that is no power of two (7, 6) is padded in VMEM."""
    from dynamo_tpu.ops.grouped_attention import grouped_decode_pallas, quarter_pages

    q, kv, kv_lens, tables = _grouped_case(heads, n_kv, lens, seed=len(lens) + heads)
    num_seqs = jnp.asarray([live], jnp.int32)
    got = jax.block_until_ready(grouped_decode_pallas(
        q, kv, kv_lens, tables, num_seqs, sm_scale=SM_SCALE,
        pages_per_block=4 * quarter_pages(PAGE_SIZE, n_kv), blocks_in_ring=2, interpret=True))
    assert got.shape == q.shape and got.dtype == q.dtype
    want = ra.ragged_paged_attention_ref(q, kv, kv_lens, tables, None, num_seqs,
                                         sm_scale=SM_SCALE)
    _close(got, np.asarray(want, np.float64))
    _close(got[:live], _dense_softmax(q[:live], kv, kv_lens[:live], tables[:live]))
    assert not np.asarray(got[live:], np.float32).any()


@pytest.mark.parametrize("lanes,heads,n_kv,width,n_pages", [
    (32, 28, 4, 256, 3073),      # qwen7b-decode-batch
    (8, 12, 2, 256, 11265),      # qwen1p5b-chat-steady, narrowest
    (32, 12, 2, 256, 11265),     # ... and widest
    (128, 32, 4, 128, 16385),    # lfm2-24b-hybrid-decode (paired rows), its two decode widths
    (32, 32, 4, 128, 16385),
    (48, 48, 8, 338, 16385),     # laguna-s21-longctx-agents' full layers, its two
    (16, 48, 8, 338, 16385),
    (128, 32, 2, 128, 12289),    # nemotron3-nano-ep2-decode, its two
    (32, 32, 2, 128, 12289),
])
def test_mosaic_compiles_the_grouped_decode_kernel_for_a_v5e(
        one_chip, lanes, heads, n_kv, width, n_pages):
    """The first-party kernel at the cells' shapes and the module's
    constants; the page array is handed over by a bitcast, no copy."""
    from dynamo_tpu.ops import grouped_attention as ga

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((lanes, heads, HEAD_DIM), jnp.bfloat16)
    pages = sds((n_pages, PAGE_SIZE, 2 * n_kv, HEAD_DIM), jnp.bfloat16)
    assert ga.fits("tpu", q, pages)
    text = jax.jit(
        lambda *a: ga.grouped_decode_pallas(*a, sm_scale=SM_SCALE)
    ).lower(q, pages, sds((lanes,), jnp.int32), sds((lanes, width), jnp.int32),
            sds((1,), jnp.int32)).compile().as_text()
    assert GROUPED_KERNEL in text
    assert not [l for l in text.splitlines() if " copy(" in l and f"bf16[{n_pages}," in l]


def test_spare_heads_of_a_page_get_zero_queries_where_the_library_kernel_reads_them():
    """``num_kv_heads=30`` over a page of 32 on the CPU (the reference, as
    the library kernel on a TPU's ragged calls): what 30 heads get is what
    they got with the model padding ``q`` itself, whatever the spare rows
    hold."""
    q, kv, lens, tables = _mha_case(30, 32, PAGE_SIZE, (70, 5, 33))
    num_seqs = jnp.asarray([3], jnp.int32)
    got = ra.ragged_paged_attention(q, kv, lens, tables, None, num_seqs, sm_scale=SM_SCALE,
                                    num_kv_heads=30)
    assert got.shape == q.shape
    _close(got, _dense_softmax(q, kv[:, :, :60], lens, tables))


def test_the_decode_bench_refuses_the_cpu(monkeypatch):
    """``tools/attn_decode_bench.py`` times kernels from a device trace; on
    the CPU there is none to time."""
    from tools import attn_decode_bench

    monkeypatch.setattr("sys.argv", ["attn_decode_bench", "--shapes", "olmo-48", "--quick"])
    with pytest.raises(SystemExit, match="no TPU"):
        attn_decode_bench.main()
    assert attn_decode_bench.geometry("olmo-48")[:4] == (48, 30, 30, 128)
    assert [t for t, _ in attn_decode_bench.variants("olmo-48", True)][:3] == [
        "serving", "library q1_p16", "mha_p8_r3"]


@pytest.mark.parametrize("shape,geometry,tags", [
    ("7b", (32, 28, 4, 256), ["serving", "library q1_p16", "gqa_p4_r3", "gqa_p8_r3", "gqa_p32_r3"]),
    ("lfm2-128", (128, 32, 4, 128), ["serving", "library q1_p16", "gqa_p4_r3", "gqa_p8_r3",
                                     "gqa_p32_r3"]),
    ("nemotron-128", (128, 32, 2, 128), ["serving", "library q1_p16", "gqa_p8_r3", "gqa_p16_r3"]),
    ("laguna-full-48", (48, 48, 8, 338), ["serving", "library q1_p16", "gqa_p4_r3", "gqa_p16_r3",
                                          "gqa_p32_r3"]),
])
def test_the_decode_bench_sweeps_the_grouped_kernel_beside_the_library(shape, geometry, tags):
    """A grouped shape's rows: the serving entry, the library kernel at the
    decode grid, the first-party kernel over ``--gqa-pages`` (the serving
    entry's own pair, 1 MB x 3, left out: one program, one executable)."""
    from tools import attn_decode_bench

    assert attn_decode_bench.geometry(shape)[:4] == geometry
    assert [t for t, _ in attn_decode_bench.variants(shape, True)] == tags
