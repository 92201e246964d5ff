"""SDAR's block megastep as a program, and the pieces beside it (the second
half of ``tests/test_sdar.py``, split off in PR 45 by that file's own
sections so that six workers balance: ROADMAP D17). The head and the
sampler run only where a place can still be hidden, and the megastep is
bit-equal to its plain form; ``unmask_block``, the fold and the dropless
layer's shares; what the block step does not carry is refused by name; a
checkpoint under the base family's names loads.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import architectures
from chipbench.architectures import sdar_moe as arch
from chipbench.configs import engine_overrides
from chipbench.reference import sdar_moe as reference
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine import model as model_mod
from dynamo_tpu.engine.config import (
    UnsupportedModelOption,
    tiny_engine,
    tiny_lfm2,
    tiny_moe,
    tiny_sdar,
)
from dynamo_tpu.engine import programs
from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.engine.options import _resolve_block_megastep
from dynamo_tpu.engine.programs import _megastep_blocks
from dynamo_tpu.engine.model import block_hidden, block_logits, init_cache, init_params
from dynamo_tpu.engine.sampler import (
    LOGPROBS_K,
    hidden_at_most,
    sample_seeded,
    unmask_block,
)
from dynamo_tpu.llm.protocols.common import (
    OutputOptions,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.ops.ragged_attention import block_attention, ragged_paged_attention_ref
from tests.test_sdar import (
    CFG,
    FILE,
    PARENT_GREEDY,
    PARENT_SEEDED,
    PROMPT,
    TIGHT,
    _req,
    file_with,
    held_to_reference,
    make_core,
    run_to_completion,
)


# -- the head and the sampler only where a place can still be hidden -----------------

def _plain_megastep_blocks(params, cache, lanes, block_tables, known, *, n_steps, need_mask,
                           want_logprobs, cfg, engine):
    """The block megastep in its plain form, kept here as what
    ``core._megastep_blocks`` is held to: ONE scanned body for every pass,
    the clean pass too, with the head and the sampler on every row of it."""
    B, steps = cfg.block_length, cfg.denoising_steps
    n_blocks, S, K = n_steps // (steps + 1), lanes.shape[0], LOGPROBS_K
    f32 = lambda col: jax.lax.bitcast_convert_type(lanes[:, col], jnp.float32)  # noqa: E731
    position, active = lanes[:, programs._L_POSITION], lanes[:, programs._L_ACTIVE] != 0
    seeds = jnp.repeat(lanes[:, programs._L_SEED], B)
    temperature, top_k, top_p = (
        jnp.repeat(f32(programs._L_TEMPERATURE), B), jnp.repeat(lanes[:, programs._L_TOP_K], B),
        jnp.repeat(f32(programs._L_TOP_P), B))
    watch, min_left = lanes[:, programs._L_WATCH:], lanes[:, programs._L_MIN_LEFT]
    place = jnp.arange(B, dtype=jnp.int32)

    def one_pass(carry, p):
        toks, hidden, step_of, lp, cache, pos, act, counts = carry
        x, cache = block_hidden(params, cache, jnp.where(hidden, cfg.mask_token_id, toks),
                                block_tables, pos, act, cfg, engine)
        logits = block_logits(params, x, None, cfg)
        counters = ((pos[:, None] + place[None, :]) * steps + p).reshape(-1)
        x0 = sample_seeded(logits, seeds, counters, temperature, top_k, top_p,
                           need_mask=need_mask)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, x0[:, None], axis=-1)[:, 0] - lse
        reveal, by_threshold = unmask_block(
            jnp.exp(chosen).reshape(S, B), hidden, p,
            steps=steps, threshold=cfg.confidence_threshold)
        reveal = reveal & act[:, None] & (p < steps)
        toks = jnp.where(reveal, x0.reshape(S, B), toks)
        step_of = jnp.where(reveal, p, step_of)
        hidden = hidden & ~reveal
        n = jnp.sum(reveal, axis=1)
        counts = counts + jnp.stack([
            jnp.sum(jnp.where(by_threshold, n, 0)),
            jnp.sum(jnp.where(by_threshold, 0, n))]).astype(jnp.int32)
        if want_logprobs:
            top_lps, top_ids = jax.lax.top_k(logits, K)
            new = (chosen.reshape(S, B), top_ids.astype(jnp.int32).reshape(S, B, K),
                   (top_lps - lse[:, None]).reshape(S, B, K))
            lp = tuple(jnp.where(reveal if a.ndim == 2 else reveal[..., None], a, old)
                       for a, old in zip(new, lp))
        return (toks, hidden, step_of, lp, cache, pos, act, counts), None

    def one_block(carry, b):
        cache, pos, alive, budget, floor, counts = carry
        act = active & alive
        opens = (b == 0) & (known >= 0)
        toks = jnp.where(opens, known, 0)
        hidden = ~opens
        lp = (jnp.zeros((S, B), jnp.float32), jnp.zeros((S, B, K), jnp.int32),
              jnp.zeros((S, B, K), jnp.float32)) if want_logprobs else None
        (toks, _, step_of, lp, cache, _, _, counts), _ = jax.lax.scan(
            one_pass,
            (toks, hidden, jnp.full((S, B), -1, jnp.int32), lp, cache, pos, act, counts),
            jnp.arange(steps + 1))
        ordinal = jnp.cumsum(hidden, axis=1) * hidden
        hit = (toks[:, :, None] == watch[:, None, :]).any(axis=2) & hidden & (
            ordinal >= floor[:, None])
        made = jnp.sum(hidden, axis=1)
        budget, floor = budget - made, floor - made
        alive = alive & ~hit.any(axis=1) & (budget > 0)
        pos = pos + B * act.astype(jnp.int32)
        return (cache, pos, alive, budget, floor, counts), (toks, step_of, lp, act)

    (cache, _, _, _, _, counts), (tokens, step_of, lps, ran) = jax.lax.scan(
        one_block,
        (cache, position, jnp.ones_like(active), lanes[:, programs._L_BUDGET], min_left,
         jnp.zeros(2, jnp.int32)),
        jnp.arange(n_blocks))
    aux = jnp.concatenate([step_of.reshape(-1), ran.astype(jnp.int32).reshape(-1), counts])
    return tokens, lps, cache, None, aux


def _megastep_inputs(cfg, engine, temperature, masked, seed=0):
    """Eight lanes at three blocks a dispatch: a plain lane, three whose first
    block a prompt's tail opens with 1-3 known places, one that is not live,
    one whose budget ends inside the second block and one that stops on a
    watched id (both dead for the blocks after), one more plain; a cache of
    noise, so that a block's rows read a past."""
    S, B, pages = 8, cfg.block_length, 6
    rs = np.random.RandomState(seed)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    cache = tuple(jnp.asarray(rs.randn(*c.shape), c.dtype) for c in init_cache(cfg, engine))
    tables = jnp.asarray(np.arange(S * pages).reshape(S, pages), jnp.int32)
    watch = np.full((S, programs.MEGASTEP_WATCH_W), -1, np.int32)
    watch[6, :] = np.arange(40, 40 + programs.MEGASTEP_WATCH_W * 40, 40)   # some id will come
    lanes = programs.pack_lanes(
        tokens=np.zeros(S, np.int32), feed_idx=None,
        positions=np.asarray([8, 16, 12, 8, 8, 20, 8, 24], np.int32),
        active=np.asarray([1, 1, 1, 1, 0, 1, 1, 1], np.int32),
        seeds=np.arange(11, 11 + S, dtype=np.int32), counters=np.zeros(S, np.int32),
        temperature=np.full(S, temperature, np.float32),
        top_k=np.full(S, 20 if masked else 0, np.int32),
        top_p=np.full(S, 0.9 if masked else 1.0, np.float32), watch=watch,
        budgets=np.asarray([99, 99, 99, 99, 99, 6, 99, 99], np.int32),
        min_left=np.zeros(S, np.int32))
    known = np.full((S, B), -1, np.int32)
    for lane, tail in ((1, 1), (2, 2), (3, 3)):
        known[lane, :tail] = rs.randint(1, 380, size=tail)
    return params, cache, jnp.asarray(lanes), tables, jnp.asarray(known)


def _folded(known, pending=None):
    """``core._megastep_blocks``'s ``known``: the places a lane's first block opens
    with beside the pending block the host holds for it (none: -1)."""
    none = jnp.full_like(known, -1)
    return jnp.stack([known, none if pending is None else pending], axis=1)


NO_FEED = jnp.zeros(1024, jnp.int32)


def _positions_of(lanes, tables, pages, page, blocks_of):
    """A mask over ``[pages, page]`` of the cache: the places of lane ``i``'s blocks
    ``blocks_of[i]`` (counted from the lane's position), through its table row."""
    B = CFG.block_length
    mask = np.zeros((pages, page), bool)
    for i, blocks in enumerate(blocks_of):
        for b in blocks:
            for pos in range(int(lanes[i, programs._L_POSITION]) + b * B,
                             int(lanes[i, programs._L_POSITION]) + (b + 1) * B):
                mask[int(tables[i, pos // page]), pos % page] = True
    return mask


@pytest.mark.parametrize("steps,temperature,threshold,want_lp", [
    (steps, temperature, threshold, True)
    for steps in (1, 2, 4) for temperature in (0.0, 0.7) for threshold in (0.9, 0.004)
] + [(2, 0.7, 0.9, False), (4, 0.0, 0.004, False)])
def test_the_megastep_is_bit_equal_to_its_plain_form(steps, temperature, threshold, want_lp):
    """The plain form (a clean pass of its own after every block's denoising
    passes, the head and the sampler on every row of every pass) stays the
    DEFINITION. Held to it over two dispatches, three blocks then one: the
    tokens, the step that revealed each place, the lanes that ran and the two
    reveal counts to the bit; the log-probabilities and the cache to 1e-5,
    because a row's products now run in a batch of ``2 S B`` rows beside its
    lane's pending block where the plain form's ran among ``S B`` (the CPU's
    readings are 0 to a few 1e-7). The cache is the plain form's everywhere
    but in each lane's LAST block run, whose clean rows have yet to ride a
    pass: after the first dispatch that block holds the K/V of a pass with
    places still masked; the second dispatch brings it final, fed from the
    first one's output on the device (lane 7: handed down by the host), and
    leaves its own block pending in turn. A lane the device saw end (5: its
    budget, 6: a watched id) keeps its last block as it was: no pass is spent
    on it. A threshold of 0.004 reveals MORE than the quota (a pass then finds
    fewer hidden places than its slots); seeded lanes ask for top-k / top-p
    where the threshold fires, so both samplers are held."""
    cfg = tiny_sdar(denoising_steps=steps, confidence_threshold=threshold)
    engine = tiny_engine(block_size=8)
    masked = temperature > 0 and threshold < 0.5
    params, cache, lanes, tables, known = _megastep_inputs(cfg, engine, temperature, masked)
    S, B = known.shape
    # ONE program: a greedy case and a drawing one differ by the lanes' temperatures
    static = dict(need_mask=masked, want_logprobs=want_lp, cfg=cfg, engine=engine)
    folded = jax.jit(lambda *a, n: _megastep_blocks(*a, n_steps=n, **static),
                     static_argnames="n")
    plain = jax.jit(lambda *a, n: _plain_megastep_blocks(*a, n_steps=n, **static),
                    static_argnames="n")
    n1 = 3 * (steps + 1)
    got = folded(params, cache, lanes, tables, NO_FEED, _folded(known), n=n1)
    want = plain(params, cache, lanes, tables, known, n=n1)
    aux = np.asarray(want[4])
    ran = aux[3 * S * B: -2].reshape(3, S)
    assert ran[:, 4].sum() == 0 and ran[:, 0].all()            # the idle lane; a plain one
    assert ran[:, 5].tolist() == [1, 1, 0]                       # the budget ended in block 2
    assert (aux[-2] > 0) == (threshold < 0.5) and aux[-2] + aux[-1] > 0
    if threshold < 0.5 and steps > 1:     # some first pass revealed MORE than its quota
        first = (aux[: 3 * S * B].reshape(3, S, B) == 0).sum(axis=2)
        assert first.max() > hidden_at_most(B, steps)[0] - hidden_at_most(B, steps)[1]

    def same(got, want, pending):
        """Tokens and aux to the bit, log-probabilities and every page but the
        garbage page (where dead lanes' rows collide) and the ``pending`` places."""
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(want[4]))
        if want_lp:
            for a, b in zip(got[1], want[1]):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        else:
            assert got[1] is None and want[1] is None
        for a, b in zip(got[2], want[2]):
            np.testing.assert_allclose(np.asarray(a)[~pending], np.asarray(b)[~pending], atol=1e-5)

    lanes_np, page, pages = np.asarray(lanes), engine.block_size, cache[0].shape[0]
    last = [[int(ran[:, i].sum()) - 1] if ran[:, i].any() else [] for i in range(S)]
    pending = _positions_of(lanes_np, tables, pages, page, last)
    pending[-1] = True
    same(got, want, pending)
    if threshold > 0.5:       # a plain lane's last block is NOT final yet: its last pass saw masks
        mine = _positions_of(lanes_np, tables, pages, page, [[2]] + [[]] * (S - 1))
        assert float(np.abs(np.asarray(got[2][0])[mine] - np.asarray(want[2][0])[mine]).max()) > 1e-3
    # the sparse layers' counts are summed over every pass: ``steps`` a block
    assert got[3].shape == (5,) and int(got[3][1]) == 3 * steps * cfg.num_layers

    # the second dispatch, one block: lanes 0-3 fed from the first one's output on the
    # device, lane 7 handed its block by the host; 4-6 are over
    alive = np.asarray([1, 1, 1, 1, 0, 0, 0, 1], np.int32)
    again = lanes_np.copy()
    again[:, programs._L_POSITION] += 3 * B
    again[:, programs._L_ACTIVE] = alive
    plain_lanes = jnp.asarray(again)
    again[:, programs._L_FEED] = np.where(alive == 1, (2 * S + np.arange(S)) * B, -1)
    again[7, programs._L_FEED] = -1
    from_host = np.full((S, B), -1, np.int32)
    from_host[7] = np.asarray(got[0])[2, 7]
    nothing = jnp.full((S, B), -1, jnp.int32)
    feed = jnp.pad(got[0].reshape(-1), (0, NO_FEED.shape[0] - got[0].size))
    got2 = folded(params, got[2], jnp.asarray(again), tables, feed,
                  _folded(nothing, jnp.asarray(from_host)), n=steps + 1)
    want2 = plain(params, want[2], plain_lanes, tables, nothing, n=steps + 1)
    assert np.asarray(want2[4])[S * B: -2].tolist() == alive.tolist()
    pending = _positions_of(lanes_np, tables, pages, page, [[3] if alive[i] else last[i] for i in range(S)])
    pending[-1] = True
    same(got2, want2, pending)


@pytest.mark.parametrize("B,steps,want", [
    (4, 2, (4, 2, 0)), (4, 4, (4, 3, 2, 1, 0)), (4, 1, (4, 0)), (4, 3, (4, 2, 1, 0)),
    (8, 3, (8, 5, 2, 0)), (8, 8, (8, 7, 6, 5, 4, 3, 2, 1, 0))])
def test_the_bound_on_hidden_places_is_unmask_blocks_quota(B, steps, want):
    """``hidden_at_most`` against what ``unmask_block`` leaves hidden where
    the threshold never fires: step by step the same counts, and a lane that
    opened with known places stays under them."""
    assert hidden_at_most(B, steps) == want
    rs = np.random.RandomState(B * 10 + steps)
    hidden = jnp.asarray(np.stack([np.ones(B, bool), np.arange(B) >= 1, np.arange(B) >= B - 1]))
    for p in range(steps):
        assert int(hidden[0].sum()) == want[p]
        assert all(int(n) <= want[p] for n in hidden.sum(axis=1))
        reveal, _ = unmask_block(jnp.asarray(rs.rand(3, B), jnp.float32), hidden, jnp.int32(p),
                                 steps=steps, threshold=2.0)
        again, _ = unmask_block(jnp.asarray(rs.rand(3, B), jnp.float32), hidden, p,
                                steps=steps, threshold=2.0)     # a step known at trace time
        assert reveal.sum(axis=1).tolist() == again.sum(axis=1).tolist()
        hidden = hidden & ~reveal
    assert not bool(hidden.any())


def _head_products(jaxpr, vocab: int) -> list[int]:
    """The row count of each product whose result is ``vocab`` wide, sub-programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape[-1] == vocab:
            found.append(int(np.prod(eqn.outvars[0].aval.shape[:-1])))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _head_products(sub, vocab)
    return found


def _switches(jaxpr, branches: int, vocab: int) -> list:
    """The conditionals of ``branches`` branches that hold a product with the
    vocabulary: the passes' switch, and not the sampler's own conditional
    (does any lane draw), which lies inside a pass's branch after its head."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "cond" and len(eqn.params["branches"]) == branches
                and any(_head_products(b.jaxpr, vocab) for b in eqn.params["branches"])):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _switches(sub, branches, vocab)
    return found


def _count(jaxpr, primitive: str) -> int:
    """Equations of that primitive, sub-programs included."""
    return sum((eqn.primitive.name == primitive)
               + sum(_count(sub, primitive) for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_pass_has_a_head_of_its_hidden_places_and_the_clean_pass_none(steps):
    """The one product with the vocabulary a pass has lies in the branch the
    pass's number picks: ``S x H_p`` rows of the CURRENT half in pass ``p``
    and none outside the switch; the clean rows (the pending half of a
    block's first pass) go through no head, and there is no clean pass."""
    cfg = tiny_sdar(denoising_steps=steps)
    engine = tiny_engine(block_size=8)
    *args, known = _megastep_inputs(cfg, engine, 0.7, False)
    S = args[2].shape[0]
    jaxpr = jax.make_jaxpr(lambda *a: _megastep_blocks(
        *a, n_steps=2 * (steps + 1), need_mask=False, want_logprobs=True,
        cfg=cfg, engine=engine))(*args, NO_FEED, _folded(known)).jaxpr
    want = [[S * H] for H in hidden_at_most(cfg.block_length, steps)[:steps]]
    if steps == 1:     # one pass a block: no switch, the head as it lies
        assert not _switches(jaxpr, 2, cfg.vocab_size)
        assert _head_products(jaxpr, cfg.vocab_size) == want[0]
        return
    (switch,) = _switches(jaxpr, steps, cfg.vocab_size)
    by_pass = [_head_products(branch.jaxpr, cfg.vocab_size) for branch in switch.params["branches"]]
    assert by_pass == want
    assert sorted(_head_products(jaxpr, cfg.vocab_size)) == sorted(sum(by_pass, []))


@pytest.mark.parametrize("steps", [2, 4])
def test_the_block_megastep_holds_one_stack(steps):
    """Every pass of every block is the ONE scanned body: a layer's two
    grouped products (gate/up, down) appear ``num_layers`` times in the
    whole program, at the one shape ``[S current blocks | S pending blocks]``,
    whatever the blocks a dispatch and the steps a block; and nothing in the
    compiled text is a rematerialised copy (on the v5e, written-out passes
    cost a head computed once a copy and 15 s of set-up: PERF.md, PR 43)."""
    cfg = tiny_sdar(denoising_steps=steps)
    engine = tiny_engine(block_size=8, num_kv_blocks=640)
    S, B = 64, cfg.block_length                  # 2 S B = 512 rows: a wave's grouped product
    shapes = (
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)),
        jax.eval_shape(lambda: init_cache(cfg, engine)),
        jax.ShapeDtypeStruct((S, programs.LANE_COLS), jnp.int32),
        jax.ShapeDtypeStruct((S, 10), jnp.int32),
        jax.ShapeDtypeStruct((2 * S * B,), jnp.int32),
        jax.ShapeDtypeStruct((S, 2, B), jnp.int32))
    program = jax.jit(lambda *a: _megastep_blocks(
        *a, n_steps=2 * (steps + 1), need_mask=False, want_logprobs=False,
        cfg=cfg, engine=engine))
    jaxpr = jax.make_jaxpr(program)(*shapes).jaxpr
    assert _count(jaxpr, "ragged_dot_general") == 2 * cfg.num_layers
    assert _count(jaxpr, "scan") == 2              # the blocks, and a block's passes
    text = program.lower(*shapes).compile().as_text()
    assert ".remat" not in text


@pytest.mark.parametrize("sampling,want", [
    (dict(temperature=0.0), PARENT_GREEDY),
    (dict(temperature=0.8, top_k=20, seed=7), PARENT_SEEDED)], ids=["greedy", "seeded"])
def test_served_tokens_are_the_parent_commits(sampling, want):
    core = make_core()
    seq = core.add_request(PreprocessedRequest(
        model="m", token_ids=PROMPT[:30], request_id="t", sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=21, ignore_eos=True), output=OutputOptions()))
    assert run_to_completion(core, [seq])[0]["t"] == want


def test_the_rows_that_went_through_the_head_are_counted():
    from chipbench.readers import prometheus
    from dynamo_tpu.runtime.metrics import MetricsRegistry
    from dynamo_tpu.runtime.status_server import _EngineCounters

    for steps in (2, 4):
        core = make_core(file_with(steps))
        seq = core.add_request(_req(PROMPT[:34], "c", 25, ignore_eos=True))
        run_to_completion(core, [seq])
        st = core.exec_stats
        B = core.cfg.block_length
        # every block's clean rows rode the next block's first pass, but the last one's
        assert st["block_pending_dropped"] == 1
        assert st["denoise_forwards"] == steps * (st["commit_forwards"] + 1) > 0
        assert st["block_rows"] == (st["denoise_forwards"] + st["commit_forwards"]) * B
        assert st["head_rows"] * steps == st["denoise_forwards"] * sum(hidden_at_most(B, steps))
        assert st["head_rows"] * 2 == st["block_rows"] + B      # 6 of 12 rows; 10 of 20
    registry = MetricsRegistry()
    registry.registry.register(_EngineCounters(core.step_phase_seconds, core.scheduler_stats))
    text = registry.render().decode()
    assert prometheus.total([text], "dynamo_engine_block_head_rows_total") == st["head_rows"]
    assert prometheus.total([text], "dynamo_engine_block_rows_total") == st["block_rows"]
    # the two counters of the fold, and the passes a live lane ran: every pass a denoising one
    assert prometheus.total([text], "dynamo_engine_block_clean_folded_total") == \
        st["block_clean_folded"] == st["blocks_committed"] - 1 > 0
    assert prometheus.total([text], "dynamo_engine_block_pending_dropped_total") == 1
    assert prometheus.total([text], "dynamo_engine_denoise_forwards_total") == st["denoise_forwards"]
    assert prometheus.total([text], "dynamo_engine_denoise_forwards_total", {"pass": "commit"}) is None


# -- the pieces -------------------------------------------------------------------

def test_unmask_block_reveals_by_threshold_or_by_quota():
    conf = jnp.asarray([[0.2, 0.95, 0.93, 0.1],     # two over: by threshold (quota 1)
                        [0.3, 0.3, 0.2, 0.91],      # one over, quota 1: that one
                        [0.3, 0.5, 0.5, 0.1],       # none over: the surest, ties to the lower
                        [0.99, 0.2, 0.1, 0.3]])     # the surest is not hidden
    hidden = jnp.asarray([[True] * 4, [True] * 4, [True] * 4, [False, True, True, True]])
    reveal, by_threshold = unmask_block(conf, hidden, jnp.int32(0), steps=4, threshold=0.9)
    assert reveal.tolist() == [[False, True, True, False], [False, False, False, True],
                               [False, True, False, False], [False, False, False, True]]
    assert by_threshold.tolist() == [True, True, False, False]
    # 3 steps of 4 places: quotas 2, 1, 1
    for step, quota in ((0, 2), (1, 1), (2, 1)):
        reveal, _ = unmask_block(conf[2:3], hidden[2:3], jnp.int32(step), steps=3, threshold=2.0)
        assert int(reveal.sum()) == quota


def test_the_fold_is_the_block_mask():
    """block_attention's one decode-shaped call against the plain reference
    call a ROW, each row told its block's end: the same numbers."""
    rs = np.random.RandomState(3)
    B, n_q, n_kv, d, page = 4, 4, 2, 16, 8
    kv_pages = jnp.asarray(rs.randn(6, page, 2 * n_kv, d), jnp.float32)
    q = jnp.asarray(rs.randn(3 * B, n_q, d), jnp.float32)
    ends = jnp.asarray([8, 12, 4], jnp.int32)        # three blocks of two sequences
    tables = jnp.asarray([[0, 1, 5], [0, 1, 5], [2, 3, 5]], jnp.int32)
    got = block_attention(q, kv_pages, ends, tables, jnp.asarray([3], jnp.int32),
                          block_length=B, sm_scale=0.25, shape="block-ragged")
    rows = ragged_paged_attention_ref(
        q, kv_pages, jnp.repeat(ends, B), jnp.repeat(tables, B, axis=0), None,
        jnp.asarray([3 * B], jnp.int32), sm_scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rows), atol=1e-5)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shares_of_the_dropless_layer_add_up_under_either_scoring(scoring):
    """Four chips' shares of 8 experts (each computed with the share's own
    parameters) add up to the uncut layer, and that to the plain sum over the
    chosen experts: the softmax shares too."""
    whole = tiny_sdar(router_scoring=scoring, block_length=0, denoising_steps=0,
                      confidence_threshold=1.0, mask_token_id=0)
    params = init_params(jax.random.PRNGKey(5), whole)
    lp = model_mod.layer_params(params, 1, whole)
    y = jnp.asarray(np.random.RandomState(1).randn(21, 64), jnp.float32)
    im = whole.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        if scoring == "softmax":
            w = reference.routing_weights(y, lp["w_router"], top_k=2)
        else:
            from chipbench.reference import lfm2_moe

            w = lfm2_moe.routing_weights(y, lp["w_router"], jnp.zeros(8), top_k=2, scale=1.0,
                                         norm_eps=whole.router_norm_eps)
        assert int((w > 0).sum()) == 21 * 2
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
        want = sum(w[:, e, None] * reference.mlp_block(
            y, lp["w_gu"][e][:, :im], lp["w_gu"][e][:, im:], lp["w_down"][e]) for e in range(8))
        uncut = model_mod._shared_sparse_mlp(y, lp, whole)
        assert float(jnp.abs(uncut - want).max()) < TIGHT
        total = 0
        for rank in range(4):
            cfg = dataclasses.replace(whole, experts_held=(rank, 4))
            lp_r = model_mod.layer_params(init_params(jax.random.PRNGKey(5), cfg), 1, cfg)
            assert lp_r["w_gu"].shape[0] == 2
            part = model_mod._shared_sparse_mlp(y, lp_r, cfg)
            assert float(jnp.abs(part).max()) > 1e-4      # the share adds something
            total = total + part
        assert float(jnp.abs(total - want).max()) < TIGHT


def test_mixtral_keeps_its_path_and_its_numbers():
    """The capacity-bounded layer is chosen by the layout the tree has, as
    before: a softmax-scored model WITHOUT moe_intermediate_size goes through
    _moe_mlp, token for token what its own reference gives."""
    from chipbench.reference import mixtral as mixtral_reference  # noqa: F401

    cfg = tiny_moe()
    params = init_params(jax.random.PRNGKey(2), cfg)
    lp = model_mod.layer_params(params, 0, cfg)
    assert "w_gate" in lp and "w_gu" not in lp
    y = jnp.asarray(np.random.RandomState(2).randn(5, 64), jnp.float32)
    np.testing.assert_array_equal(np.asarray(model_mod._mlp(y, lp, cfg, 1)),
                                  np.asarray(model_mod._moe_mlp(y, lp, cfg, None)))
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        dataclasses.replace(cfg, router_scoring="sigmoid")


# -- refused by name ----------------------------------------------------------------

@pytest.mark.parametrize("option,engine", [
    ("spec_decode", {"spec_decode": "ngram"}),
    ("scheduling", {"scheduling": "chunked"}),
    ("kv_dtype", {"kv_dtype": "int8"}),
])
def test_an_option_the_block_step_does_not_carry_is_refused_at_start_up(option, engine):
    with pytest.raises(UnsupportedModelOption) as e:
        EngineCore(CFG, tiny_engine(**engine), seed=0)
    assert e.value.option == option and "tiny-sdar" in str(e.value)


def test_meshes_quantised_weights_and_misfit_sizes_are_refused():
    from dynamo_tpu.backends.jax.main import build_engine

    with pytest.raises(UnsupportedModelOption, match="tp"):
        EngineCore(CFG, tiny_engine(), seed=0, mesh=object())
    with pytest.raises(UnsupportedModelOption, match="pp"):
        EngineCore(CFG, tiny_engine(), seed=0, pp_mesh=object())
    with pytest.raises(NotImplementedError, match="tiny-sdar"):
        build_engine("tiny-sdar", {"num_kv_blocks": 16, "block_size": 8}, quant="int8")
    with pytest.raises(ValueError, match="whole blocks"):
        EngineCore(CFG, tiny_engine(block_size=6, prefill_buckets=(24,)), seed=0)
    with pytest.raises(ValueError, match="denoising_steps"):
        tiny_sdar(denoising_steps=5)
    with pytest.raises(ValueError, match="only a model that generates by blocks"):
        dataclasses.replace(tiny_lfm2(), denoising_steps=2)
    with pytest.raises(ValueError, match="block_length"):
        dataclasses.replace(CFG, block_length=0)
    with pytest.raises(NotImplementedError):
        dataclasses.replace(tiny_lfm2(), block_length=4, denoising_steps=2)


@pytest.mark.parametrize("option,sampling", [
    ("frequency_penalty", {"frequency_penalty": 0.5}),
    ("presence_penalty", {"presence_penalty": 0.5}),
    ("repetition_penalty", {"repetition_penalty": 1.2}),
    ("n", {"n": 2}),
])
def test_a_request_for_left_to_right_sampling_is_refused_by_name(option, sampling):
    core = make_core()
    with pytest.raises(ValueError, match=option):
        core.add_request(PreprocessedRequest(
            model="m", token_ids=PROMPT[:8], request_id="r",
            sampling=SamplingOptions(temperature=0.0, **sampling),
            stop=StopConditions(max_tokens=4), output=OutputOptions()))
    assert not core.has_work()


def test_resolved_schedule_and_what_the_worker_reports():
    eng = _resolve_block_megastep(tiny_sdar(denoising_steps=4), tiny_engine(block_size=8))
    assert eng.megastep == 5                                   # one block of 5 passes
    eng = _resolve_block_megastep(CFG, tiny_engine(block_size=8))
    assert CFG.denoising_steps == 2 and eng.megastep == 6      # two blocks of 3 passes
    core = make_core()
    stats = core.scheduler_stats()
    assert stats["block_length"] == 4 and stats["denoising_steps"] == 2
    assert stats["megastep_k"] == 6


def test_counters_of_a_run():
    from dynamo_tpu.ops.ragged_attention import traced_calls

    core = make_core()
    seq = core.add_request(_req(PROMPT[:34], "c", 25, ignore_eos=True))
    run_to_completion(core, [seq])
    st = core.exec_stats
    # 34 = 8 whole blocks + a tail of 2; 25 tokens: 2 + 5 whole blocks + 3 of 4
    assert st["blocks_committed"] == 7
    assert st["places_revealed_quota"] + st["places_revealed_threshold"] >= 26
    assert st["denoise_forwards"] == 2 * 7 and st["commit_forwards"] == st["block_clean_folded"] == 6
    assert st["block_pending_dropped"] == 1
    assert st["committed_tokens"] == 25 and st["block_places_discarded"] >= 1
    traced = traced_calls()
    assert traced.get(("block-decode", "reference"), 0) > 0
    assert traced.get(("block-ragged", "reference"), 0) > 0
    assert not core.running and core.allocator.free_blocks > 0


# -- a checkpoint under the base family's names ---------------------------------------

def test_loads_a_checkpoint_under_the_base_familys_names(tmp_path):
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.loader import load_hf_llama

    rng = np.random.RandomState(0)
    h, d, E, im, v = 64, 16, 8, 32, 384
    mat = lambda o, i: (rng.randn(o, i) * i ** -0.5).astype(np.float32)  # noqa: E731
    sd = {"model.embed_tokens.weight": mat(v, h), "model.norm.weight": np.ones(h, np.float32),
          "lm_head.weight": mat(v, h)}
    for l in range(2):
        p = f"model.layers.{l}."
        sd[p + "input_layernorm.weight"] = (1 + 0.1 * rng.randn(h)).astype(np.float32)
        sd[p + "post_attention_layernorm.weight"] = (1 + 0.1 * rng.randn(h)).astype(np.float32)
        for name, out in (("q_proj", 4 * d), ("k_proj", 2 * d), ("v_proj", 2 * d)):
            sd[p + f"self_attn.{name}.weight"] = mat(out, h)
        sd[p + "self_attn.o_proj.weight"] = mat(h, 4 * d)
        sd[p + "self_attn.q_norm.weight"] = (1 + 0.1 * rng.randn(d)).astype(np.float32)
        sd[p + "self_attn.k_norm.weight"] = (1 + 0.1 * rng.randn(d)).astype(np.float32)
        sd[p + "mlp.gate.weight"] = mat(E, h)
        for e in range(E):
            sd[p + f"mlp.experts.{e}.gate_proj.weight"] = mat(im, h)
            sd[p + f"mlp.experts.{e}.up_proj.weight"] = mat(im, h)
            sd[p + f"mlp.experts.{e}.down_proj.weight"] = mat(h, im) / 4
    save_file(sd, str(tmp_path / "model.safetensors"))
    hf = {k: val for k, val in FILE.items()
          if k not in ("name", "torch_dtype", "serve", "source", "deployment", "reduced",
                       "assumed", "probe")}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg, loaded = load_hf_llama(tmp_path, dtype=jnp.float32)
    assert cfg == dataclasses.replace(CFG, name="sdar_moe", dtype="bfloat16")
    assert [a.shape for a in loaded["moe"]["w_gu"]] == [(E, h, 2 * im)] * 2
    np.testing.assert_array_equal(loaded["moe"]["w_gu"][1][5, :, im:],
                                  sd["model.layers.1.mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(loaded["layers"]["k_layernorm"][1],
                                  sd["model.layers.1.self_attn.k_norm.weight"])
    np.testing.assert_array_equal(loaded["layers"]["wqkv"][0][:, 4 * d: 6 * d],
                                  sd["model.layers.0.self_attn.k_proj.weight"].T)
    # the loaded tree serves, and the reference reads it as it reads a drawn one
    served = EngineCore(dataclasses.replace(cfg, dtype="float32"),
                        EngineConfig(**engine_overrides(FILE)),
                        params=jax.tree.map(jnp.asarray, loaded))
    verdict, _ = held_to_reference(served, FILE, {"prompt_ids": PROMPT[:30], "max_tokens": 9,
                                                  "top": 5})
    assert verdict["ok"] and verdict["max_abs_diff"] < TIGHT


def test_architectures_knows_the_module_and_its_optional_member():
    assert "sdar_moe" in architectures.known()
    assert architectures.of(FILE) is arch and callable(arch.score_probe)
