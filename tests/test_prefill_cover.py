"""Prefill waves that fit their bucket (ISSUE 30).

The waves planner covers the waiting prompt tokens with the cheapest set
of compiled waves, by a table of measured ms per bucket (warm-up times
each program once) and the host's cost per dispatch. Here: the search
alone; the same tokens, block tables and prefix-cache commits as the
uncut plan on the tiny CPU model; warm-up compiling every bucket with an
empty table and leaving a whole one; the counters; the host's floor.
"""

import itertools

import numpy as np
import pytest

from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine.prefill_cover import cheapest_cover
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

pytestmark = [pytest.mark.unit]

CFG = tiny_model()

# ms of one wave per bucket, as ISSUE 30 reckoned them, with the host's floor.
T_7B = ((128, 15.0), (512, 60.0), (2048, 240.0), (8192, 1000.0))
T_1P5B = ((128, 5.0), (512, 11.0), (2048, 44.0), (8192, 200.0))
# A wave's time in proportion to its bucket, and one that favours no cut.
T_LINEAR = ((128, 1.0), (512, 4.0), (2048, 16.0), (8192, 64.0))
T_FLAT = ((128, 10.0), (512, 10.5), (2048, 11.0), (8192, 11.5))
# A ladder whose rungs share no large divisor.
T_ODD = ((96, 3.0), (160, 4.0), (1000, 30.0))


def _ms(buckets, table, floor):
    ms = dict(table)
    return sum(max(ms[b], floor) for b in buckets)


# -- (a) the search alone ----------------------------------------------------------


@pytest.mark.parametrize("table, floor", [
    (T_7B, 16.0), (T_7B, 0.0), (T_1P5B, 10.0), (T_LINEAR, 0.0), (T_LINEAR, 2.5),
    (T_FLAT, 0.0), (T_ODD, 0.0), (T_ODD, 3.5),
], ids=["7b", "7b-no-floor", "1p5b", "linear", "linear-floor", "flat", "odd",
        "odd-floor"])
def test_every_token_count_is_covered_and_never_dearer_than_one_wave(table, floor):
    buckets = [b for b, _ in table]
    for tokens in range(1, buckets[-1] + 1):
        got = cheapest_cover(tokens, table, floor)
        assert sum(got) >= tokens, (tokens, got)
        assert list(got) == sorted(got, reverse=True)
        one = next(b for b in buckets if b >= tokens)
        assert _ms(got, table, floor) <= _ms((one,), table, floor) + 1e-9, (tokens, got)
        if table is T_FLAT:
            assert got == (one,), (tokens, got)  # a cut never pays: whole


@pytest.mark.parametrize("table, floor", [(T_7B, 16.0), (T_1P5B, 10.0), (T_ODD, 3.5)],
                         ids=["7b", "1p5b", "odd"])
def test_the_search_is_exact(table, floor):
    """Against every multiset of a few waves, for token counts up to the
    third bucket: none is cheaper, and none as cheap has fewer waves."""
    buckets = [b for b, _ in table]
    sets = [c for n in range(1, 9)
            for c in itertools.combinations_with_replacement(buckets[:3], n)]
    for tokens in range(1, buckets[2] + 1, 7):
        got = cheapest_cover(tokens, table, floor)
        best = min((_ms(c, table, floor), len(c)) for c in sets if sum(c) >= tokens)
        assert (_ms(got, table, floor), len(got)) == pytest.approx(best), (tokens, got)


@pytest.mark.parametrize("table, floor, tokens, want", [
    (T_7B, 16.0, 640, (512, 128)),             # 76 ms, not 240
    (T_7B, 16.0, 768, (512, 128, 128)),        # 92, not 120 or 240
    (T_7B, 20.0, 384, (512,)),                 # 3 x 128 at the host's pace ties: whole
    (T_7B, 16.0, 1800, (512, 512, 512, 128, 128, 128)),  # 228 against 240: only a measured table can say
    (T_7B, 16.0, 8300, (2048, 2048, 2048, 2048, 128)),
    (T_1P5B, 10.0, 700, (512, 512)),           # 22, not 44
    (T_1P5B, 10.0, 277, (512,)),               # 11, not 3 x 10
    (T_1P5B, 0.0, 277, (512,)),                # 11, not 15
    (T_1P5B, 0.0, 130, (128, 128)),            # 10, not 11
    (T_1P5B, 10.0, 130, (512,)),               # the host's floor forbids it
], ids=lambda v: str(v) if isinstance(v, int) else None)
def test_worked_cases(table, floor, tokens, want):
    assert cheapest_cover(tokens, table, floor) == want


@pytest.mark.parametrize("tokens", [0, 1, 640, 8192, 20000])
def test_an_empty_table_covers_nothing(tokens):
    assert cheapest_cover(tokens, (), 5.0) == ()
    core = EngineCore(CFG, tiny_engine(), seed=0)
    assert core._prefill_cover(tokens) == ()


def test_one_rung_is_its_own_cover():
    """The pp engine's ladder may be cut to one rung: nothing to choose."""
    assert cheapest_cover(300, ((512, 9.0),), 2.0) == (512,)
    assert cheapest_cover(1100, ((512, 9.0),), 2.0) == (512, 512, 512)


# -- (b) the same tokens, block tables and prefix-cache commits -------------------

# Every bucket above the smallest is dear: all prompts ride 32-token waves.
CUTS = {32: 1.0, 64: 10.0, 128: 100.0}


def _req(prompt, rid, max_tokens=9):   # the prefill's token and one whole megastep
    return PreprocessedRequest(
        model="tiny", token_ids=list(prompt), request_id=rid,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


def _mm_req(rid, max_tokens=9):
    from dynamo_tpu.llm.multimodal import MM_PATCHES, image_bytes, patch_embed, pseudo_tokens

    img = "data:application/octet-stream;base64,YSBjYXQgb24gYSBtYXQ="
    text = list(range(5, 5 + 22))               # the span straddles token 32
    pre = _req(text + pseudo_tokens(img, CFG.vocab_size) + [9, 10, 11], rid, max_tokens)
    emb = patch_embed(image_bytes(img), CFG.hidden_size)
    pre.mm = {"images": [img], "positions": [[len(text), MM_PATCHES]],
              "embeds": emb.astype(np.float32).tobytes(),
              "embeds_shape": list(emb.shape)}
    return pre


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, CFG.vocab_size, size=n).tolist()


def _serve(core, requests, floor_ms=0.0):
    """Run ``requests`` (added together) to the end. Returns tokens per
    request, each prompt's block table when its prefill was over, and
    the prefix cache's commits in order."""
    core.host_floor_ms = lambda: floor_ms
    seqs = [core.add_request(r) for r in requests]
    tokens = {s.request_id: [] for s in seqs}
    tables = {}
    for _ in range(400):
        for seq, out in core.step():
            tokens[seq.request_id].extend(out.token_ids)
        for s in seqs:
            if s.request_id not in tables and s.prefill_done and s.block_ids:
                n = -(-s.prompt_len // core.engine.block_size)
                tables[s.request_id] = (tuple(s.block_ids[:n]),
                                        tuple(s.pinned_hashes[: s.prompt_len // core.engine.block_size]))
        if all(s.finish for s in seqs) and not core.has_work():
            break
    assert all(s.finish == "length" for s in seqs)
    return tokens, tables, core.allocator.snapshot()


SCENARIOS = {
    # a 100-token prompt in 32-token waves: cut three times
    "cut-thrice": lambda: ([], [_req(_prompt(1, 100), "a")]),
    # two prompts in one wave, the second cut off a block's edge (20 + 12)
    "two-in-a-wave": lambda: ([], [_req(_prompt(2, 20), "a"), _req(_prompt(3, 30), "b")]),
    # three blocks of the prompt come from the prefix cache, the rest is cut
    "prefix-hit": lambda: ([_req(_prompt(4, 40), "warm")],
                           [_req(_prompt(4, 40)[:24] + _prompt(5, 50), "a")]),
    # a cut inside an image's span of embeddings
    "multimodal": lambda: ([], [_mm_req("a")]),
    # longer than the largest bucket, beside a short one
    "over-the-ladder": lambda: ([], [_req(_prompt(6, 150), "a"), _req(_prompt(7, 9), "b")]),
}


@pytest.mark.parametrize("async_exec", [False, True], ids=["synchronous", "pipelined"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_cut_waves_serve_what_whole_waves_serve(scenario, async_exec):
    results = []
    for table in ({}, CUTS):
        core = EngineCore(CFG, tiny_engine(async_exec=async_exec), seed=0)
        before, requests = SCENARIOS[scenario]()
        if before:
            _serve(core, before)
        core.prefill_bucket_ms = dict(table)
        waves0 = dict(core.prefill_waves)
        results.append(_serve(core, requests))
        waves = {b: n - waves0.get(b, 0) for b, n in core.prefill_waves.items()}
        if table:
            # every wave rode the cheap bucket, and prompts were cut
            assert set(b for b, n in waves.items() if n) == {32}, waves
            assert core.exec_stats["prefill_cut_waves"] > 0
        else:
            assert core.exec_stats["prefill_cut_waves"] == 0
    whole, cut = results
    assert cut[0] == whole[0]          # greedy tokens
    assert cut[1] == whole[1]          # block tables and the blocks' hashes
    assert cut[2] == whole[2]          # what the prefix cache holds, in commit order


# -- (c) warm-up ---------------------------------------------------------------------


def test_warm_up_compiles_every_bucket_and_leaves_a_whole_table():
    """With the table empty every warm-up wave fills its bucket, so each
    bucket's program is compiled there and not on a request; the table
    appears only when all of it is over."""
    from dynamo_tpu.engine.warmup import warm_up

    # Shapes no other test's engine has: the jit caches of one function are
    # shared by every engine in the process, and test_warmup.py counts them.
    eng = tiny_engine(prefill_buckets=(24, 48, 96), megastep_k=3, decode_buckets=(5,),
                      max_num_seqs=5, prefill_batch=6)
    core = EngineCore(CFG, eng, seed=0)
    seen = []
    real = core._plan_prefill_wave

    def spy(seqs):
        seen.append(dict(core.prefill_bucket_ms))
        return real(seqs)

    core._plan_prefill_wave = spy
    cold = core._prefill._cache_size()
    phases = warm_up(core)
    for bucket in eng.prefill_buckets:
        assert f"prefill T={bucket}" in phases
    # a program per bucket, sampled and greedy in one, and the timed waves add none
    assert core._prefill._cache_size() - cold == len(eng.prefill_buckets)
    assert all(table == {} for table in seen), "a wave of warm-up was planned by a table"
    assert sorted(core.prefill_bucket_ms) == list(eng.prefill_buckets)
    assert all(ms > 0 for ms in core.prefill_bucket_ms.values())
    # each bucket once and once more to be timed; the decode phase
    # prefills 5 prompts of a block each (40 tokens) first
    assert core.prefill_waves == {24: 2, 48: 2 + 1, 96: 2}
    assert core.exec_stats["prefill_cut_waves"] == 0
    # the host's floor counts from the end of the compiles
    assert 0 < core.host_floor_ms() < 1e3
    assert core.scheduler_stats()["prefill_bucket_ms"] == core.prefill_bucket_ms
    # and serving afterwards compiles nothing, cut or whole
    core.prefill_bucket_ms = {24: 1.0, 48: 10.0, 96: 100.0}
    _serve(core, [_req(_prompt(8, 70), "a")])
    assert core._prefill._cache_size() - cold == len(eng.prefill_buckets)


def test_an_engine_that_skipped_warm_up_has_no_table():
    core = EngineCore(CFG, tiny_engine(), seed=0)
    _serve(core, [_req(_prompt(9, 70), "a")])
    assert core.prefill_bucket_ms == {} and core.prefill_waves == {128: 1}


# -- (d) the counters -----------------------------------------------------------------


def test_waves_by_bucket_add_up_and_only_the_planners_cuts_count():
    from chipbench.readers import prometheus
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters

    core = EngineCore(CFG, tiny_engine(), seed=0)
    # 150 tokens with no table: cut at the largest bucket (128), which is
    # the ladder's end and no choice of the planner's; then 22 in a 32 wave.
    _serve(core, [_req(_prompt(10, 150), "a")])
    assert core.prefill_waves == {128: 1, 32: 1}
    assert core.exec_stats["prefill_cut_waves"] == 0
    # 70 tokens by a table that prices 64 + 32 under 128: one cut wave.
    core.prefill_bucket_ms = {32: 1.0, 64: 1.5, 128: 100.0}
    _serve(core, [_req(_prompt(11, 70), "b")])
    assert core.prefill_waves == {128: 1, 64: 1, 32: 2}
    assert core.exec_stats["prefill_cut_waves"] == 1
    # every ragged dispatch of a waves engine is a prefill wave (each request
    # decodes one whole megastep, so no decode step runs single)
    assert sum(core.prefill_waves.values()) == core.exec_stats["single_step_dispatches"]

    registry = MetricsRegistry()
    registry.registry.register(
        _EngineCounters(core.step_phase_seconds, core.scheduler_stats))
    text = registry.render().decode()
    assert "# TYPE dynamo_engine_prefill_waves_total counter" in text
    assert "# TYPE dynamo_engine_prefill_bucket_ms gauge" in text
    by_bucket = {lab["bucket"]: v for name, lab, v in prometheus.parse(text)
                 if name == "dynamo_engine_prefill_waves_total"}
    assert by_bucket == {"32": 2.0, "64": 1.0, "128": 1.0}
    assert prometheus.total([text], "dynamo_engine_prefill_waves_total") == 4
    assert prometheus.total([text], "dynamo_engine_prefill_cut_waves_total") == 1
    assert prometheus.total(
        [text], "dynamo_engine_prefill_bucket_ms", {"bucket": "64"}) == 1.5


def test_the_dispatch_annotation_names_the_cover():
    core = EngineCore(CFG, tiny_engine(), seed=0)
    marks = []
    real = core.clock.mark

    def spy(phase, **attrs):
        if phase == "dispatch" and attrs.get("kind") == "prefill":
            marks.append((attrs["cover"], attrs["real"], attrs["padded"]))
        return real(phase, **attrs)

    core.clock.mark = spy
    _serve(core, [_req(_prompt(12, 70), "a")])
    core.prefill_bucket_ms = {32: 1.0, 64: 1.5, 128: 100.0}
    _serve(core, [_req(_prompt(13, 70), "b")])
    assert marks == [("", 70, 128), ("64+32", 64, 64), ("32", 6, 32)]


# -- (e) the host's floor -------------------------------------------------------------


@pytest.mark.parametrize("floor_ms, want", [
    (0.0, {32: 2}),       # 40 tokens: 32 + 32 at 1 ms each beat 64 at 3 ms
    (2.0, {64: 1}),       # the host needs 2 ms a dispatch: 4 ms against 3, whole
    (50.0, {64: 1}),      # a floor above every bucket: fewest waves
], ids=["no-floor", "floor-above-the-small-bucket", "floor-above-all"])
def test_a_host_floor_keeps_a_short_prompt_whole(floor_ms, want):
    core = EngineCore(CFG, tiny_engine(), seed=0)
    core.prefill_bucket_ms = {32: 1.0, 64: 3.0, 128: 9.0}
    _serve(core, [_req(_prompt(14, 40), "a")], floor_ms=floor_ms)
    assert core.prefill_waves == want
    assert core.exec_stats["prefill_cut_waves"] == (1 if floor_ms == 0.0 else 0)


def test_the_host_floor_is_host_seconds_over_dispatches_since_its_mark():
    core = EngineCore(CFG, tiny_engine(), seed=0)
    assert core.host_floor_ms() == 0.0
    seqs = [core.add_request(_req(_prompt(15, 20), "a"))]
    while not all(s.finish for s in seqs):
        core.step()
    # every host phase but ``dispatch``, where a program's first use compiles
    host = sum(s for (phase, blocks), s in core.step_phase_seconds().items()
               if blocks == "host" and phase != "dispatch")
    assert core.step_phase_seconds()[("dispatch", "host")] > 0
    n = core.exec_stats["dispatches"]
    assert n > 0 and core.host_floor_ms() == pytest.approx(1e3 * host / n, rel=0.05)
    # thirty seconds of compiling inside a jitted call (a program left to its
    # first use) are no part of what a dispatch costs from then on
    floor = core.host_floor_ms()
    core.clock._ns["dispatch"] += 30 * 10**9
    assert core.host_floor_ms() == pytest.approx(floor, rel=0.05)
    core.count_host_floor_from_here()
    assert core.host_floor_ms() == 0.0   # nothing dispatched since
