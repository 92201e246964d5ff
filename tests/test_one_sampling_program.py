"""One compiled program a shape, whatever its lanes sample (ISSUE 53).

Whether any lane of a batch draws used to be a static argument of every
serving program (two executables a shape, chosen by the host, both warmed
up); it is now a conditional on the device over the batch's own
``temperature`` vector (``engine/sampler.py:_sample``). Here: the sampler's
tokens against the two static bodies written out without a conditional; the
shape of what is traced (the arg-max alone in the greedy branch, nothing
hoisted in front of the conditional); a warmed-up engine that compiles
nothing for a greedy request, a sampled one or a mixed batch at every decode
width; the counter of which branch a dispatch takes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import device
from dynamo_tpu.engine import EngineCore, tiny_engine, tiny_model
from dynamo_tpu.engine.sampler import sample, sample_seeded
from dynamo_tpu.llm.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

pytestmark = [pytest.mark.unit]

B, V = 6, 97
LOGITS = jnp.asarray(np.random.RandomState(3).randn(B, V).astype(np.float32) * 2)
SEEDS = jnp.arange(11, 11 + B, dtype=jnp.int32)
COUNTERS = jnp.asarray([0, 5, 2, 9, 1, 7], jnp.int32)
TEMPERATURES = {
    "all-zero": [0.0] * B,
    "mixed": [0.0, 0.7, 0.0, 1.3, 0.2, 0.0],
    "all-positive": [0.5, 0.7, 1.0, 1.3, 0.2, 2.0],
}
NO_K, NO_P = jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32)


def _keys(seeds, counters):
    base = jax.random.PRNGKey(0)
    return jax.vmap(lambda s, c: jax.random.fold_in(jax.random.fold_in(base, s), c))(
        seeds, counters)


def _static_variant(logits, rng, temperature):
    """What the two executables of a shape computed, the host choosing: the
    arg-max where every lane is at temperature 0; else the draw, with the
    arg-max for a lane at 0. No conditional."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if all(t <= 0.0 for t in np.asarray(temperature)):
        return greedy
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    if rng.ndim == 2:
        sampled = jax.vmap(jax.random.categorical)(rng, scaled).astype(jnp.int32)
    else:
        sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


ENTRIES = {
    "seeded": (lambda t, **kw: sample_seeded(LOGITS, SEEDS, COUNTERS, t, NO_K, NO_P, **kw),
               lambda: _keys(SEEDS, COUNTERS)),
    "lane-keys": (lambda t, **kw: sample(LOGITS, _keys(SEEDS, COUNTERS), t, NO_K, NO_P, **kw),
                  lambda: _keys(SEEDS, COUNTERS)),
    "one-key": (lambda t, **kw: sample(LOGITS, jax.random.PRNGKey(5), t, NO_K, NO_P, **kw),
                lambda: jax.random.PRNGKey(5)),
}


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("kind", list(TEMPERATURES))
def test_the_tokens_are_the_static_variants(kind, entry):
    """All-zero: the arg-max; mixed and all-positive: the draw of each
    lane's own key, the arg-max for a lane at 0. To the bit, traced or not,
    and the masked variant (no lane asks for top-k / top-p) agrees."""
    temperature = jnp.asarray(TEMPERATURES[kind], jnp.float32)
    call, rng = ENTRIES[entry]
    want = np.asarray(_static_variant(LOGITS, rng(), temperature))
    if kind == "all-zero":
        np.testing.assert_array_equal(want, np.asarray(jnp.argmax(LOGITS, axis=-1)))
    got = call(temperature, need_mask=False)
    np.testing.assert_array_equal(np.asarray(got), want)
    jitted = jax.jit(lambda t: call(t, need_mask=False))(temperature)
    np.testing.assert_array_equal(np.asarray(jitted), want)
    np.testing.assert_array_equal(np.asarray(call(temperature, need_mask=True)), want)


def _count(jaxpr, primitive: str) -> int:
    return sum((eqn.primitive.name == primitive)
               + sum(_count(sub, primitive) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("need_mask", [False, True], ids=["plain", "masked"])
def test_what_is_traced(need_mask):
    """Without a mask: ONE conditional and nothing that reads ``[B, V]`` in
    front of it; its greedy branch is the arg-max alone (no key folded, no
    bits drawn), its drawn branch today's sampled body (one draw, and the
    arg-max for the lanes at 0). The masked variant keeps its body: no
    conditional."""
    temperature = jnp.asarray(TEMPERATURES["mixed"], jnp.float32)
    jaxpr = jax.make_jaxpr(lambda l, t: sample_seeded(
        l, SEEDS, COUNTERS, t, NO_K, NO_P, need_mask=need_mask))(LOGITS, temperature).jaxpr
    conds = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "cond"]
    if need_mask:
        assert not _count(jaxpr, "cond") and _count(jaxpr, "argmax") == 3
        return
    (cond,) = conds
    assert _count(jaxpr, "cond") == 1
    wide = [eqn.primitive.name for eqn in jaxpr.eqns if eqn is not cond
            and any(getattr(v.aval, "shape", ()) == (B, V) for v in eqn.outvars)]
    assert not wide and not any(eqn.primitive.name == "argmax" for eqn in jaxpr.eqns)
    drawn, greedy = (branch.jaxpr for branch in cond.params["branches"])   # index 0: false
    assert [eqn.primitive.name for eqn in greedy.eqns] == ["argmax"]
    assert _count(drawn, "argmax") == 2 and _count(drawn, "random_bits") == 1
    assert _count(drawn, "random_fold_in") == 2


# -- a warmed-up engine ------------------------------------------------------------

SERVING = ("_prefill_and_sample", "_megastep_body", "pad_feedback", "gather_feedback")


def _req(i: int, n_prompt: int, max_tokens: int, **sampling) -> PreprocessedRequest:
    return PreprocessedRequest(
        model="tiny", token_ids=list(range(1 + i, 1 + i + n_prompt)), request_id=f"r{i}",
        sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True))


def _serve(core, reqs):
    seqs = [core.add_request(r) for r in reqs]
    for _ in range(2000):
        core.step()
        if all(s.finish for s in seqs) and not core.has_work():
            break
    assert all(s.finish == "length" for s in seqs)
    return seqs


@pytest.fixture(scope="module")
def warmed():
    """A tiny engine after warm-up, with every compile event from then on.
    Shapes no other test's engine has (tests/test_warmup.py)."""
    from dynamo_tpu.engine.warmup import warm_up

    core = EngineCore(
        tiny_model(),
        tiny_engine(megastep_k=5, decode_buckets=(2, 6), max_num_seqs=6, prefill_batch=3,
                    prefill_buckets=(40, 80)),
        seed=0)
    log = device.compile_log()
    heard, before = [], log.sink
    phases = warm_up(core)
    log.sink = lambda kind, name, seconds: heard.append((kind, name))
    yield core, phases, heard
    log.sink = before


def _executables(core) -> tuple[int, int]:
    return core._prefill._cache_size(), core._decode._cache_size()


def test_warm_up_leaves_one_executable_a_shape(warmed):
    core, phases, _ = warmed
    eng = core.engine
    rows = [p for p in phases if p != "prefill waves timed"]
    assert rows == ["prefill T=40", "prefill T=80", "decode B=2 k=5", "decode B=6 k=5"]
    # half of what a pass a sampling kind left: one a bucket, one a width
    assert _executables(core) == (len(eng.prefill_buckets), len(eng.decode_buckets))
    # a warm-up outside a worker kept its own clock and closed it: the next
    # worker this process starts opens one of its own
    from dynamo_tpu.tracing import startclock
    assert startclock.current() is None


@pytest.mark.parametrize("temperatures", [
    (0.0,), (0.8,), (0.0, 0.8), (0.0,) * 3, (0.8,) * 4, (0.0, 0.8, 0.0, 1.0, 0.0),
    (0.8, 0.0, 0.0, 0.0, 0.0, 0.0),
], ids=["greedy-w2", "sampled-w2", "mixed-w2", "greedy-w6", "sampled-w6", "mixed-w6",
        "mixed-w6-full"])
def test_serving_after_warm_up_compiles_nothing(warmed, temperatures):
    """A greedy request, a sampled one and a mixed batch, at both decode
    widths (1-2 lanes: 2; 3-6: 6) and through both prefill buckets: no
    serving program is traced, lowered or compiled again, and the engine's
    executables stay one a (program, shape)."""
    core, _, heard = warmed
    held = _executables(core)
    del heard[:]
    greedy, drawn = core.exec_stats["dispatches_greedy"], core.exec_stats["dispatches_drawn"]
    n = len(temperatures)
    seqs = _serve(core, [_req(i, 9 if n < 4 else 14, 1 + 2 * 5, temperature=t, seed=i)
                         for i, t in enumerate(temperatures)])
    assert all(len(s.out_tokens) == 11 for s in seqs)
    assert _executables(core) == held
    assert [name for _, name in heard if name in SERVING] == []
    # every dispatch took the branch its batch's temperatures say
    took_greedy = core.exec_stats["dispatches_greedy"] - greedy
    took_drawn = core.exec_stats["dispatches_drawn"] - drawn
    assert took_greedy + took_drawn >= 3
    if not any(temperatures):
        assert took_drawn == 0
    elif all(temperatures):
        assert took_greedy == 0
    else:
        assert took_drawn > 0


def test_a_lane_at_temperature_zero_has_its_greedy_stream_in_any_batch(warmed):
    """The arg-max branch and the drawn branch give a greedy lane the same
    tokens: alone (every dispatch greedy) and beside lanes that draw."""
    core, _, _ = warmed
    (alone,) = _serve(core, [_req(40, 9, 11, temperature=0.0)])
    beside = _serve(core, [_req(40, 9, 11, temperature=0.0),
                           _req(41, 9, 11, temperature=0.9, seed=3),
                           _req(42, 9, 11, temperature=1.1, seed=4)])
    assert beside[0].out_tokens == alone.out_tokens
    assert beside[1].out_tokens != alone.out_tokens


def test_the_compile_events_are_heard(warmed):
    """What the tests above listen with does hear a serving program compile:
    the log-probability variant, left to its first use."""
    from dynamo_tpu.llm.protocols.common import OutputOptions

    core, _, heard = warmed
    held = _executables(core)
    del heard[:]
    req = _req(50, 9, 6, temperature=0.0)
    req.output = OutputOptions(logprobs=1)
    _serve(core, [req])
    assert {"_prefill_and_sample", "_megastep_body"} <= {name for _, name in heard}
    assert _executables(core) == (held[0] + 1, held[1] + 1)


# -- the counter ------------------------------------------------------------------


def test_the_counter_reads_the_branch_a_dispatch_takes():
    from chipbench.readers import prometheus
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters

    core = EngineCore(tiny_model(), tiny_engine(), seed=0)
    count = lambda: (core.exec_stats["dispatches_greedy"],  # noqa: E731
                     core.exec_stats["dispatches_drawn"])
    assert count() == (0, 0)
    _serve(core, [_req(i, 9, 9, temperature=0.0) for i in range(3)])
    greedy, drawn = count()
    assert greedy >= 2 and drawn == 0                 # an all-zero batch: a wave, megasteps
    _serve(core, [_req(3, 9, 9, temperature=0.0), _req(4, 9, 9, temperature=0.7, seed=1)])
    assert count()[0] == greedy and count()[1] >= 2   # a mixed one
    # a greedy lane's top-k asks for no mask; a drawing lane's does
    temp, top_k, top_p = np.asarray([0.0, 0.7], np.float32), np.asarray([5, 0]), np.ones(2)
    assert core._count_sampling(temp, top_k, top_p) is False
    assert core._count_sampling(temp, top_k[::-1], top_p) is True
    assert core._count_sampling(temp, top_k * 0, np.asarray([0.5, 1.0])) is False
    assert core._count_sampling(temp, top_k * 0, np.asarray([1.0, 0.5])) is True
    registry = MetricsRegistry()
    registry.registry.register(_EngineCounters(core.step_phase_seconds, core.scheduler_stats))
    text = registry.render().decode()
    name = "dynamo_engine_dispatches_by_sampling_total"
    greedy, drawn = count()
    assert prometheus.total([text], name, {"kind": "greedy"}) == greedy
    assert prometheus.total([text], name, {"kind": "drawn"}) == drawn
    assert core.scheduler_stats()["dispatches_drawn"] == drawn


# -- the tool that times the greedy branch (tools/sampling_branch_bench.py) ----------


@pytest.mark.parametrize("temperature,kind", [(0.0, "greedy"), (0.7, "drawn")])
def test_the_bench_drives_the_branch_it_names(temperature, kind):
    """On the CPU the times mean nothing; the dispatches it times are all of
    the kind its temperature says, and as many as asked for."""
    from tools.sampling_branch_bench import PROMPT, _timed

    core = EngineCore(
        tiny_model(),
        tiny_engine(megastep_k=4, decode_buckets=(4,), max_num_seqs=4, prefill_batch=4,
                    prefill_buckets=(PROMPT * 4,), max_model_len=192, num_kv_blocks=128),
        seed=0)
    row = _timed(core, lanes=4, dispatches=3, temperature=temperature, seed=1)
    other = "drawn" if kind == "greedy" else "greedy"
    assert row["megasteps"] == 3 and row["k"] == 4
    assert row["by_sampling"] == {kind: 3, other: 0}
    assert row["wall_ms_a_megastep"] > 0 and not core.has_work()
