"""The device programs, and the format of what they take.

Pure functions of arrays: a megastep's scan bodies, the prefill programs, the
pipeline's chain, and the lane format (``pack_lanes`` on the host,
``unpack_lanes`` on the device). They know nothing of a sequence, the
allocator or a lock; :func:`compile_programs` binds the configurations and
the meshes and hands the engine its jitted callables (engine/core.py reads
them as attributes at every dispatch).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.model import (
    block_hidden,
    block_logits,
    decode_tokens,
    forward_ring_prefill,
    forward_tokens,
    verify_tokens,
)
from dynamo_tpu.engine.sampler import (
    LOGPROBS_K,
    device_ngram_draft,
    gather_feedback,
    hidden_at_most,
    pad_feedback,
    resolve_verify,
    ring_append,
    sample_seeded,
    stop_flags,
    stop_flags_prefix,
    token_logprobs,
    unmask_block,
)


# Static width of the per-lane on-device stop-watch array ([B, W], -1
# padded): EOS ids + stop_token_ids. Lanes with more watch ids than fit
# simply truncate — the device then under-stops (extra masked no-op
# iterations, exactly the pre-stop-flag behavior) but never over-stops;
# the host stop-scan stays the authority either way.
MEGASTEP_WATCH_W = 8

# A megastep's per-lane inputs cross to the device as ONE int32 array
# ``[B, LANE_COLS]``, a column a quantity, floats by their bits, the watch
# list last: a transfer costs the runtime's Python once (~0.45 ms on the
# serving host) where twelve arrays cost it twelve times, on the leg
# between a landing and the next enqueue (PERF.md section 6, PR 40).
(_L_TOKEN, _L_FEED, _L_POSITION, _L_ACTIVE, _L_SEED, _L_COUNTER, _L_TEMPERATURE,
 _L_TOP_K, _L_TOP_P, _L_BUDGET, _L_MIN_LEFT, _L_WATCH) = range(12)
LANE_COLS = _L_WATCH + MEGASTEP_WATCH_W


def pack_lanes(
    tokens, feed_idx, positions, active, seeds, counters, temperature,
    top_k, top_p, watch, budgets, min_left,
) -> np.ndarray:
    """int32 ``[B, LANE_COLS]`` of a megastep's twelve per-lane host
    arrays (``feed_idx`` None: no lane is fed), which
    :func:`unpack_lanes` takes apart again on the device, bit for bit."""
    lanes = np.empty((tokens.shape[0], LANE_COLS), np.int32)
    lanes[:, _L_TOKEN] = tokens
    lanes[:, _L_FEED] = -1 if feed_idx is None else feed_idx
    lanes[:, _L_POSITION] = positions
    lanes[:, _L_ACTIVE] = active
    lanes[:, _L_SEED] = seeds
    lanes[:, _L_COUNTER] = counters
    lanes[:, _L_TOP_K] = top_k
    lanes[:, _L_BUDGET] = budgets
    lanes[:, _L_MIN_LEFT] = min_left
    lanes[:, _L_WATCH:] = watch
    bits = lanes.view(np.float32)   # the same memory: a float's bits as they are
    bits[:, _L_TEMPERATURE] = temperature
    bits[:, _L_TOP_P] = top_p
    return lanes


def unpack_lanes(lanes: jax.Array, feed: jax.Array):
    """A megastep's per-lane inputs from their packed array, in the order
    the step programs name them: ``tokens`` (a lane whose feed column is
    >= 0 reads ``feed`` there: the step in flight's sampled tokens,
    sampler.gather_feedback), ``positions``, ``active``, ``seeds``,
    ``counters``, ``temperature``, ``top_k``, ``top_p``, ``watch``,
    ``budgets``, ``min_left``. Slices and bitcasts of a ``[128, 19]``
    array, once a dispatch."""
    def f32(col):
        return jax.lax.bitcast_convert_type(lanes[:, col], jnp.float32)

    tokens = gather_feedback(feed, lanes[:, _L_TOKEN], lanes[:, _L_FEED])
    return (
        tokens, lanes[:, _L_POSITION], lanes[:, _L_ACTIVE] != 0,
        lanes[:, _L_SEED], lanes[:, _L_COUNTER], f32(_L_TEMPERATURE),
        lanes[:, _L_TOP_K], f32(_L_TOP_P), lanes[:, _L_WATCH:],
        lanes[:, _L_BUDGET], lanes[:, _L_MIN_LEFT],
    )


def _expert_stats_list(cfg) -> list | None:
    """Where a program's sparse layers leave their counts at trace time
    (model._shared_sparse_mlp); None for a model that keeps none."""
    return [] if cfg.shared_sparse else None


def _expert_stats_sum(stats: list | None):
    """int32 [5] over a step's sparse layers: held experts touched, layer
    steps, (token, expert) pairs on held experts, pairs routed, rows the
    expert products ran on."""
    return sum(stats[1:], stats[0]) if stats else None


def _megastep_body(
    params, cache, lanes, block_tables, feed, known=None,
    *, n_steps, need_mask, want_logprobs=False,
    cfg, engine, mesh=None,
):
    """The decode MEGASTEP: ``n_steps`` fused decode+sample iterations in
    ONE device dispatch — the single scanned-decode implementation (the
    legacy waves decode chain and the chunked scheduler's decode-only
    steps both run this body). Each inner iteration writes the current
    token's K/V, attends through the same ragged program every other
    step shape uses (decode_tokens is thin assembly over forward_tokens),
    samples the next token with per-position ``(seed, counter + i)``
    keys — which feeds the next iteration on-device, no host round trip
    — and updates per-lane stop flags: a lane that samples a watched
    stop id (EOS / stop_token_ids, past its min-tokens floor) or
    exhausts its generation budget runs its remaining iterations as
    masked no-ops (K/V writes routed to the garbage block, position
    frozen, output padded with its last live token).

    Returns all sampled tokens [n_steps, B] (+ logprob arrays with
    ``want_logprobs``); the host stop-scan stays the AUTHORITY over what
    is emitted — stops only the host can see (stop strings, truncated
    watch lists) roll back via the ``num_computed_tokens`` cursor, whose
    un-advanced tail is never attended and is rewritten by the next
    dispatch.

    The per-lane inputs arrive packed (:func:`pack_lanes`) beside the
    block tables and the feedback source (and, for a block-diffusion model
    alone, ``known``: :func:`_megastep_blocks`)."""
    if cfg.block_length:
        return _megastep_blocks(
            params, cache, lanes, block_tables, feed, known, n_steps=n_steps,
            need_mask=need_mask, want_logprobs=want_logprobs,
            cfg=cfg, engine=engine, mesh=mesh)
    (tokens, positions, active, seeds, counters, temperature, top_k, top_p,
     watch, budgets, min_left) = unpack_lanes(lanes, feed)

    def body(carry, i):
        toks, cache, alive, pos = carry
        act = active & alive
        stats = _expert_stats_list(cfg)
        logits, cache = decode_tokens(
            params, cache, toks, block_tables, pos, act, cfg, engine, mesh,
            expert_stats=stats,
        )
        with jax.named_scope("sample"):
            nxt = sample_seeded(
                logits, seeds, counters + i, temperature, top_k, top_p,
                need_mask=need_mask,
            )
            # Dead lanes pad the output with their last live token — a
            # deterministic, pinnable value (the host stop-scan resolves
            # the repeated stop id to the same stop position).
            out_tok = jnp.where(act, nxt, toks)
            lp = token_logprobs(logits, out_tok) if want_logprobs else None
            alive = alive & ~stop_flags(nxt, watch, budgets, min_left, i)
            pos = pos + act.astype(jnp.int32)
        return (out_tok, cache, alive, pos), (out_tok, lp, _expert_stats_sum(stats))

    (_, cache, _, _), (sampled, lps, stats) = jax.lax.scan(
        body,
        (tokens, cache, jnp.ones_like(active), positions),
        jnp.arange(n_steps),
    )
    if stats is not None:
        stats = jnp.sum(stats, axis=0)
    return _replicate_out(sampled, mesh), _replicate_out(lps, mesh), cache, stats


def _megastep_blocks(
    params, cache, lanes, block_tables, feed, known,
    *, n_steps, need_mask, want_logprobs, cfg, engine, mesh=None,
):
    """:func:`_megastep_body` of a block-diffusion model
    (``cfg.block_length = B > 0``): a scanned iteration is ONE forward
    (model.block_hidden), and a dispatch runs ``n_steps / (steps + 1)`` whole
    blocks a lane, ``steps = cfg.denoising_steps`` denoising passes each. A
    block starts as ``cfg.mask_token_id`` at its hidden places (``known[:,
    0]`` ``[S, B]``: a prompt's tail opens a lane's FIRST block of the
    dispatch as known places, -1 where hidden; every later block is all
    hidden). A denoising pass samples each hidden place from its OWN row with
    the request's sampler (key ``(seed, position x steps + step)``: a place's
    draw is the same whatever neighbours, preemption or blocks a dispatch it
    meets), takes the sample's probability under the raw row as its
    confidence, and reveals by ``sampler.unmask_block``; the last step
    reveals what is left. Hidden places are a MASK OF PLACES, never ``id ==
    mask_token_id``: a prompt token with that id is a known token.

    **Where a block's K/V become final: in the NEXT block's first pass.** The
    K/V a block leaves are those of its revealed tokens, each seeing the
    block both ways over the causal past: its CLEAN rows. They are no pass
    of their own (that pass streamed every touched expert and the lane's
    past for nothing else): pass 0 of the lane's next block runs them beside
    that block's places (model.block_hidden's ``pending``), in this dispatch
    or the next, and that pass reads the same weights anyway. Every pass has
    the one static shape ``[S current blocks | S pending blocks]``; in the
    passes after a block's first the pending half is dead. So a lane always
    has at most one block whose clean rows have yet to run, its PENDING
    block: the dispatch's last block of a live lane comes back pending, and
    the next dispatch's first pass is handed it: from the output of the
    dispatch in flight where the host has not seen it yet (``feed``, flat
    and padded, the lane's ``_L_FEED`` column naming the first place of its
    last block there, as a next-token lane's names its token), else from the
    host (``known[:, 1]``, -1 where the lane has none: fresh from a wave).
    A lane the device sees end (a stop, its budget) is dead for the blocks
    after, and its last block's clean rows are never run: no one will read
    them. The host's cursor moves over a block when its clean rows have run
    and not before (``EngineCore._plan_blocks``).

    **Which pass runs the head on which rows.** Only a hidden place's logits,
    draw and confidence are ever read, and after ``p`` steps a live lane
    holds at most ``H_p`` hidden places (``sampler.hidden_at_most``: 4, 2 at
    ``B`` 4 and 2 steps). So pass ``p`` hands the head and the sampler ``S x
    H_p`` rows of the CURRENT half: each lane's hidden places in ascending
    order (spare slots, where a prompt's tail or the threshold left fewer,
    point at the lane's place 0 and are read by no one; a dead lane reveals
    nothing, so any ``H_p`` of its places do), and spreads the draws back
    over ``[S, B]``. Pass 0 (``H_0 = B``) takes every row as it lies. The
    pending half has no head, no draw, no confidence. The passes stay ONE
    scanned body, the stack once a program, and ``lax.switch`` on the pass's
    number picks the head of that pass's shape: written out, the passes cost
    the v5e a head computed once a copy a pass (XLA rematerialised the
    logits) and 15 s of set-up (PERF.md section 6, PR 43).

    A lane goes dead for the dispatch's later blocks once its budget of
    places to generate is spent or a revealed place holds a watched id (past
    the min-tokens floor); the host's stop scan stays the authority over
    what is kept. Returns ``(tokens [n_blocks, S, B], logprob arrays or
    None, cache, expert counts, aux)`` with ``aux`` one flat int32 array: the
    step that revealed each place ``[n_blocks, S, B]`` (-1: known), the lanes
    alive at each block's start ``[n_blocks, S]``, the places revealed by
    threshold / by quota ``[2]``."""
    B, steps = cfg.block_length, cfg.denoising_steps
    n_blocks = n_steps // (steps + 1)
    S = lanes.shape[0]
    f32 = lambda col: jax.lax.bitcast_convert_type(lanes[:, col], jnp.float32)  # noqa: E731
    position, active = lanes[:, _L_POSITION], lanes[:, _L_ACTIVE] != 0
    lane_sampling = (lanes[:, _L_SEED], f32(_L_TEMPERATURE), lanes[:, _L_TOP_K], f32(_L_TOP_P))
    watch, min_left = lanes[:, _L_WATCH:], lanes[:, _L_MIN_LEFT]
    lane, place = jnp.arange(S, dtype=jnp.int32), jnp.arange(B, dtype=jnp.int32)
    K = LOGPROBS_K
    # each lane's pending block as the dispatch starts: the dispatch in
    # flight's output where the lane names a place of it, else the host's
    fed = lanes[:, _L_FEED, None]
    pending = gather_feedback(feed, known[:, 1], jnp.where(fed >= 0, fed + place[None, :], -1))
    known = known[:, 0]

    def blank_lp():   # chosen, top ids, top log-probabilities of a block's places
        return (jnp.zeros((S, B), jnp.float32), jnp.zeros((S, B, K), jnp.int32),
                jnp.zeros((S, B, K), jnp.float32)) if want_logprobs else None

    def head(H, x, hidden, pos, p):
        """A branch of ``one_pass``'s switch: pass ``p``'s head and draws on
        ``H`` rows a lane, spread back over ``[S, B]``: (the draws, their
        confidences, the log-probability arrays or None)."""
        with jax.named_scope("unmask"):
            if H < B:
                # slot j of a lane: its j-th hidden place (place 0 where it has fewer)
                nth = jnp.cumsum(hidden, axis=1) - 1
                at = hidden[:, None, :] & (nth[:, None, :] == place[None, :H, None])
                slots = jnp.argmax(at, axis=2).astype(jnp.int32)               # [S, H]
                rows = (lane[:, None] * B + slots).reshape(-1)
                slot_of = jnp.clip(nth, 0, H - 1)

                def spread(a):     # [S x H, ...] by slot -> [S, B, ...] by place
                    a = a.reshape(S, H, *a.shape[1:])
                    return jnp.take_along_axis(
                        a, slot_of.reshape(S, B, *(1,) * (a.ndim - 2)), axis=1)
            else:
                slots, rows = jnp.broadcast_to(place, (S, B)), None
                spread = lambda a: a.reshape(S, B, *a.shape[1:])  # noqa: E731
        logits = block_logits(params, x, rows, cfg)                            # [S x H, V]
        with jax.named_scope("unmask"):
            counters = ((pos[:, None] + slots) * steps + p).reshape(-1)
            seeds, temperature, top_k, top_p = (jnp.repeat(a, H) for a in lane_sampling)
            x0 = sample_seeded(
                logits, seeds, counters, temperature, top_k, top_p,
                need_mask=need_mask)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            chosen = jnp.take_along_axis(logits, x0[:, None], axis=-1)[:, 0] - lse
            lp = None
            if want_logprobs:
                top_lps, top_ids = jax.lax.top_k(logits, K)
                lp = (spread(chosen), spread(top_ids.astype(jnp.int32)),
                      spread(top_lps - lse[:, None]))
            return spread(x0), spread(jnp.exp(chosen)), lp

    heads = [partial(head, H) for H in hidden_at_most(B, steps)[:steps]]

    def one_block(carry, b):
        cache, pos, alive, budget, floor, counts, clean = carry
        act = active & alive

        def one_pass(carry, p):
            toks, hidden, step_of, lp, cache, counts = carry
            stats = _expert_stats_list(cfg)
            x, cache = block_hidden(
                params, cache, jnp.where(hidden, cfg.mask_token_id, toks),
                block_tables, pos, act, cfg, engine, mesh, expert_stats=stats,
                pending=jnp.where(p == 0, clean, -1))
            x0, conf, new = jax.lax.switch(p, heads, x, hidden, pos, p)
            with jax.named_scope("unmask"):
                reveal, by_threshold = unmask_block(
                    conf, hidden, p, steps=steps, threshold=cfg.confidence_threshold)
                reveal = reveal & act[:, None]
                toks = jnp.where(reveal, x0, toks)
                step_of = jnp.where(reveal, p, step_of)
                hidden = hidden & ~reveal
                n = jnp.sum(reveal, axis=1)
                counts = counts + jnp.stack([
                    jnp.sum(jnp.where(by_threshold, n, 0)),
                    jnp.sum(jnp.where(by_threshold, 0, n))]).astype(jnp.int32)
                if want_logprobs:
                    lp = tuple(jnp.where(reveal if a.ndim == 2 else reveal[..., None], a, old)
                               for a, old in zip(new, lp))
            return (toks, hidden, step_of, lp, cache, counts), _expert_stats_sum(stats)

        # the dispatch's first block may open with known places (a prompt's tail)
        opens = (b == 0) & (known >= 0)
        toks = jnp.where(opens, known, 0)
        hidden = ~opens
        (toks, _, step_of, lp, cache, counts), stats = jax.lax.scan(
            one_pass,
            (toks, hidden, jnp.full((S, B), -1, jnp.int32), blank_lp(), cache, counts),
            jnp.arange(steps))
        with jax.named_scope("unmask"):
            # the places this block generated, in order: 1, 2, ... at its hidden places
            ordinal = jnp.cumsum(hidden, axis=1) * hidden
            hit = (toks[:, :, None] == watch[:, None, :]).any(axis=2) & hidden & (
                ordinal >= floor[:, None])
            made = jnp.sum(hidden, axis=1)
            budget, floor = budget - made, floor - made
            alive = alive & ~hit.any(axis=1) & (budget > 0)
            pos = pos + B * act.astype(jnp.int32)
        if stats is not None:
            stats = jnp.sum(stats, axis=0)
        # this block is the lane's pending one now; a lane that did not run it has none
        return ((cache, pos, alive, budget, floor, counts, jnp.where(act[:, None], toks, -1)),
                (toks, step_of, lp, act, stats))

    (cache, _, _, _, _, counts, _), (tokens, step_of, lps, ran, stats) = jax.lax.scan(
        one_block,
        (cache, position, jnp.ones_like(active), lanes[:, _L_BUDGET], min_left,
         jnp.zeros(2, jnp.int32), pending),
        jnp.arange(n_blocks))
    if stats is not None:
        stats = jnp.sum(stats, axis=0)
    aux = jnp.concatenate([
        step_of.reshape(-1), ran.astype(jnp.int32).reshape(-1), counts])
    return (_replicate_out(tokens, mesh), _replicate_out(lps, mesh), cache, stats,
            _replicate_out(aux, mesh))


def _megastep_fused_body(
    params, cache,
    # -- iteration 0: the ragged program (exactly _dispatch_ragged's shape)
    tokens, positions, write_pages, write_offs, kv_lens, block_tables,
    cu_q_lens, num_seqs, gather,
    seeds_r, counters_r, temp_r, top_k_r, top_p_r,
    mm_embeds, mm_mask,
    # -- per-lane continuation state ([S] unless noted)
    draft, draft_len,        # [S, R-1] drafted tokens, live length
    cont_active,             # bool — lane continues as a decode row
    base_pos,                # write position of the first scan write at acc=0
    seeds, temp, top_k, top_p,
    watch, budgets, min_left,
    *, n_steps, need_mask, want_logprobs=False,
    want_mm=False, cfg, engine, mesh=None,
):
    """The UNIVERSAL megastep (ISSUE 12): ONE device dispatch fuses an
    arbitrary ragged first iteration — prefill chunks, decode rows, and
    speculative verify rows, the exact program :meth:`_dispatch_ragged`
    runs — with ``n_steps - 1`` scanned decode+sample iterations over
    the same lanes.

    Iteration 0 samples the [S, R] verify-width slots with per-position
    ``(seed, counter + j)`` keys, then each lane resolves ON DEVICE
    (:func:`sampler.resolve_verify`): a verify row accepts the longest
    drafted prefix the target agrees with and continues from the
    correction/bonus token at position ``base + accepted`` — a rejected
    draft rolls back INSIDE the dispatch (its K/V writes sit past the
    lane's position cursor, never attended, overwritten in place by the
    continuation) instead of forcing a host round trip. A prefill chunk
    that completes its prompt continues as a decode row from its
    first sampled token; mid-prompt chunks run the remaining iterations
    as masked no-ops (``cont_active`` False). The per-lane stop state
    (watch ids, budget, min-tokens floor) carries the data-dependent
    iteration-0 emission count, so a verify row that emits
    ``accepted + 1`` tokens burns exactly that much budget.

    Returns sampled [n_steps, S, R] (iteration 0 fills the verify width,
    later iterations broadcast their single token across R) plus
    matching logprob arrays; the HOST stop-scan stays the authority,
    exactly as in :func:`_megastep_body`."""
    logits, cache = forward_tokens(
        params, cache, tokens, positions, write_pages, write_offs,
        kv_lens, block_tables, cu_q_lens, num_seqs, gather,
        cfg, engine, mesh,
        mm_embeds=mm_embeds if want_mm else None,
        mm_mask=mm_mask if want_mm else None,
    )
    with jax.named_scope("sample"):
        t0 = sample_seeded(
            logits, seeds_r, counters_r, temp_r, top_k_r, top_p_r,
            need_mask=need_mask,
        )
        lp0 = token_logprobs(logits, t0) if want_logprobs else None
        S = draft.shape[0]
        R = t0.shape[0] // S
        t0s = t0.reshape(S, R)
        acc, cur = resolve_verify(t0s, draft, draft_len)
        alive0 = cont_active & ~stop_flags_prefix(
            t0s, acc, watch, budgets, min_left
        )
        gen0 = jnp.where(cont_active, acc + 1, 0)   # tokens iteration 0 produced
        pos0 = base_pos + acc                       # next write position
        counters0 = counters_r.reshape(S, R)[:, 0]  # per-lane generated base

    def body(carry, _):
        tok, cache, alive, pos, gen = carry
        act = alive
        logits, cache = decode_tokens(
            params, cache, tok, block_tables, pos, act, cfg, engine, mesh,
        )
        with jax.named_scope("sample"):
            nxt = sample_seeded(
                logits, seeds, counters0 + gen, temp, top_k, top_p,
                need_mask=need_mask,
            )
            out_tok = jnp.where(act, nxt, tok)
            lp = token_logprobs(logits, out_tok) if want_logprobs else None
            g = gen + act.astype(jnp.int32)
            stop = ((nxt[:, None] == watch).any(axis=1) & (g >= min_left)) | (
                g >= budgets
            )
            alive = alive & ~stop
            pos = pos + act.astype(jnp.int32)
        return (out_tok, cache, alive, pos, g), (out_tok, lp)

    (_, cache, _, _, _), (rest, rest_lp) = jax.lax.scan(
        body, (cur, cache, alive0, pos0, gen0), None, length=n_steps - 1
    )
    sampled = jnp.concatenate(
        [t0s[None], jnp.broadcast_to(rest[:, :, None], (n_steps - 1, S, R))],
        axis=0,
    )
    lps = None
    if want_logprobs:
        def widen(a0, ar):
            # a0: [S*R(,K)] iteration-0 slots; ar: [n_steps-1, S(,K)]
            a0 = a0.reshape((1, S, R) + a0.shape[1:])
            ar = jnp.broadcast_to(
                ar[:, :, None], (n_steps - 1, S, R) + ar.shape[2:]
            )
            return jnp.concatenate([a0, ar], axis=0)

        lps = tuple(widen(a0, ar) for a0, ar in zip(lp0, rest_lp))
    return _replicate_out(sampled, mesh), _replicate_out(lps, mesh), cache


def _megastep_draft_body(
    params, cache,
    # -- iteration 0: the ragged program (exactly _dispatch_fused's shape)
    tokens, positions, write_pages, write_offs, kv_lens, block_tables,
    cu_q_lens, num_seqs, gather,
    seeds_r, counters_r, temp_r, top_k_r, top_p_r,
    mm_embeds, mm_mask,
    # -- per-lane continuation state ([S] unless noted)
    draft, draft_len,        # [S, R-1] host-drafted tokens, live length
    cont_active,             # bool — lane continues past iteration 0
    base_pos,                # write position of the first post-0 write at acc=0
    seeds, temp, top_k, top_p,
    watch, budgets, min_left,
    # -- on-device drafting state (ISSUE 18)
    hist, hist_len,          # [S, H] right-aligned history ring, [S] lengths
    dd,                      # [S] bool — lanes that draft on device
    win, nmin, nmax, kmax,   # [S] per-lane resolved drafter knobs
    *, n_steps, need_mask, want_logprobs=False,
    want_mm=False, ngram_max_static, cfg, engine, mesh=None,
):
    """The ON-DEVICE-DRAFTING megastep (ISSUE 18): the universal
    megastep's ragged first iteration, fused with ``n_steps - 1``
    verify-SHAPED scanned iterations. Between iterations each
    device-drafting lane suffix-matches its history ring
    (:func:`sampler.device_ngram_draft` — the bit-exact scanned-body
    replay of ``spec/ngram.py``), and the next iteration verifies
    pending + fresh draft as one width-R row
    (:func:`model.verify_tokens`), resolves accept/reject on device, and
    appends the emitted tokens back into the ring
    (:func:`sampler.ring_append`) — draft→verify→accept LOOPS inside one
    dispatch, so accepted depth compounds to ``1 + (n_steps-1) * R``
    tokens per dispatch while the host pays one fixed dispatch overhead.

    Non-drafting lanes (prefill chunks, plain decode rows, host-drafted
    verify rows riding the same batch) draft nothing each round
    (``draft_len == 0``), so their rounds degenerate to exactly the
    fused body's one-token scan semantics — same counters, same budget
    arithmetic (:func:`sampler.stop_flags_prefix` with the running
    per-lane ``gen`` base), same under-stop-never-over-stop contract.
    The host stop-scan stays the authority: a host-side stop truncates
    the emission via the ``num_computed_tokens`` cursor, and the ring is
    repacked from host history at the next plan, which is the whole
    ring-rollback story.

    Returns sampled [n_steps, S, R] plus a [3, n_steps, S] int32 aux
    (per-round emitted counts / draft lengths / accepted counts — round
    0 carries the iteration-0 resolution) the commit replays, plus
    matching logprob arrays."""
    logits, cache = forward_tokens(
        params, cache, tokens, positions, write_pages, write_offs,
        kv_lens, block_tables, cu_q_lens, num_seqs, gather,
        cfg, engine, mesh,
        mm_embeds=mm_embeds if want_mm else None,
        mm_mask=mm_mask if want_mm else None,
    )
    t0 = sample_seeded(
        logits, seeds_r, counters_r, temp_r, top_k_r, top_p_r,
        need_mask=need_mask,
    )
    lp0 = token_logprobs(logits, t0) if want_logprobs else None
    S = draft.shape[0]
    R = t0.shape[0] // S
    t0s = t0.reshape(S, R)
    acc, cur = resolve_verify(t0s, draft, draft_len)
    alive0 = cont_active & ~stop_flags_prefix(
        t0s, acc, watch, budgets, min_left
    )
    gen0 = jnp.where(cont_active, acc + 1, 0)   # tokens iteration 0 produced
    pos0 = base_pos + acc                       # next write position
    counters0 = counters_r.reshape(S, R)[:, 0]  # per-lane generated base
    # Iteration-0 emission enters the ring (drafting lanes only; the
    # ring of a non-dd lane is dead weight carried as zeros).
    hist, hist_len = ring_append(hist, hist_len, t0s, jnp.where(dd, gen0, 0))
    jR = jnp.arange(R, dtype=jnp.int32)
    rep = lambda a: jnp.repeat(a, R, axis=0)  # noqa: E731 — [S] -> [S*R]

    def body(carry, _):
        tok, cache, alive, pos, gen, hist, hlen = carry
        act = alive
        # Redraft from the ring: budget-clamped exactly like the host
        # (`_draft_for`): at most remaining-budget - 1 so the mandatory
        # correction/bonus token always fits.
        kc = jnp.where(dd & act, jnp.minimum(kmax, budgets - gen - 1), 0)
        dtoks, dlen = device_ngram_draft(
            hist, hlen, win, nmin, nmax, kc,
            ngram_max_static=ngram_max_static, slots=R - 1,
        )
        slot = jnp.concatenate(
            [tok[:, None], jnp.where(dtoks >= 0, dtoks, 0)], axis=1
        )
        logits, cache = verify_tokens(
            params, cache, slot, block_tables, pos, dlen, act, cfg,
            engine, mesh,
        )
        cnt = ((counters0 + gen)[:, None] + jR[None, :]).reshape(-1)
        nxt = sample_seeded(
            logits, rep(seeds), cnt, rep(temp), rep(top_k), rep(top_p),
            need_mask=need_mask,
        )
        ns = nxt.reshape(S, R)
        accj, nxt_tok = resolve_verify(ns, dtoks, dlen)
        e = jnp.where(act, accj + 1, 0)
        out = jnp.where(act[:, None], ns, tok[:, None])
        lp = token_logprobs(logits, out.reshape(-1)) if want_logprobs else None
        stop = stop_flags_prefix(
            ns, accj, watch, budgets, min_left, gen_base=gen
        )
        alive = alive & ~stop
        pos = pos + e
        gen = gen + e
        hist, hlen = ring_append(hist, hlen, ns, jnp.where(dd, e, 0))
        tok = jnp.where(act, nxt_tok, tok)
        return (tok, cache, alive, pos, gen, hist, hlen), (out, e, dlen, accj, lp)

    (_, cache, _, _, _, _, _), (rest, es, dls, accs, rest_lp) = jax.lax.scan(
        body, (cur, cache, alive0, pos0, gen0, hist, hist_len), None,
        length=n_steps - 1,
    )
    sampled = jnp.concatenate([t0s[None], rest], axis=0)  # [n_steps, S, R]
    aux = jnp.stack([
        jnp.concatenate([gen0[None], es], axis=0),
        jnp.concatenate([draft_len[None], dls], axis=0),
        jnp.concatenate([acc[None], accs], axis=0),
    ]).astype(jnp.int32)                                  # [3, n_steps, S]
    lps = None
    if want_logprobs:
        def widen(a0, ar):
            # a0: [S*R(,K)] iteration-0 slots; ar: [n_steps-1, S*R(,K)]
            a0 = a0.reshape((1, S, R) + a0.shape[1:])
            ar = ar.reshape((n_steps - 1, S, R) + ar.shape[2:])
            return jnp.concatenate([a0, ar], axis=0)

        lps = tuple(widen(a0, ar) for a0, ar in zip(lp0, rest_lp))
    return (
        _replicate_out(sampled, mesh),
        _replicate_out(aux, mesh),
        _replicate_out(lps, mesh),
        cache,
    )


def _replicate_out(x, mesh):
    """Pin small host-bound outputs (sampled tokens, logprobs) to a
    replicated layout: under dp the batch inputs are dp-sharded and GSPMD
    would propagate that to the outputs, which a multi-host leader could
    not fetch (each host would hold only its lanes). The all-gather this
    inserts is a few KB."""
    if x is None or mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, rep), x
    )


def _ring_prefill_and_sample(
    params, cache, tokens, write_pages, write_offs, last_row,
    seeds, counters, temperature, top_k, top_p,
    *, need_mask, want_logprobs=False, cfg, engine, sp_mesh,
):
    """One dense sequence-parallel prefill (ring attention over sp) +
    fused first-token sampling for a single long prompt."""
    logits, cache = forward_ring_prefill(
        params, cache, tokens, write_pages, write_offs, last_row,
        cfg, engine, sp_mesh,
    )
    with jax.named_scope("sample"):
        toks = sample_seeded(
            logits, seeds, counters, temperature, top_k, top_p,
            need_mask=need_mask,
        )
        lps = token_logprobs(logits, toks) if want_logprobs else None
    return toks, lps, cache


def _prefill_and_sample(
    params, cache, tokens, positions, write_pages, write_offs,
    kv_lens, block_tables, cu_q_lens, num_seqs, last_rows,
    seeds, counters, temperature, top_k, top_p, mm_embeds, mm_mask,
    *, need_mask, want_logprobs=False, want_mm=False,
    cfg, engine, mesh=None,
):
    """One ragged prefill wave + fused first-token sampling: every row of
    the [S, vocab] last-token logits is sampled on-device; the host keeps
    only rows whose prompt completed this wave. ``want_mm`` (a separate
    compiled variant) splices multimodal embedding rows over placeholder
    positions (llm/multimodal.py)."""
    stats = _expert_stats_list(cfg)
    logits, cache = forward_tokens(
        params, cache, tokens, positions, write_pages, write_offs,
        kv_lens, block_tables, cu_q_lens, num_seqs, last_rows,
        cfg, engine, mesh,
        mm_embeds=mm_embeds if want_mm else None,
        mm_mask=mm_mask if want_mm else None,
        expert_stats=stats,
    )
    with jax.named_scope("sample"):
        toks = sample_seeded(
            logits, seeds, counters, temperature, top_k, top_p,
            need_mask=need_mask,
        )
        lps = token_logprobs(logits, toks) if want_logprobs else None
    return (_replicate_out(toks, mesh), _replicate_out(lps, mesh), cache,
            _expert_stats_sum(stats))


def _pp_prefill_and_sample(
    params, cache, mb_tokens, mb_positions, mb_pages, mb_offs,
    mb_kv_lens, block_tables, mb_cu, num_seqs, mb_last_local, mb_last_mask,
    seeds, counters, temperature, top_k, top_p,
    *, need_mask, want_logprobs=False,
    cfg, engine, pp_mesh, n_micro,
):
    """Prefill wave under pipeline parallelism: the GPipe shard_map
    program (parallel/pipeline.py) + the same fused first-token sampling
    as :func:`_prefill_and_sample`."""
    from dynamo_tpu.parallel.pipeline import pp_forward_impl

    logits, cache = pp_forward_impl(
        params, cache, mb_tokens, mb_positions, mb_pages, mb_offs,
        mb_kv_lens, block_tables, mb_cu, num_seqs, mb_last_local,
        mb_last_mask, cfg=cfg, engine=engine, mesh=pp_mesh, n_micro=n_micro,
    )
    with jax.named_scope("sample"):
        toks = sample_seeded(
            logits, seeds, counters, temperature, top_k, top_p,
            need_mask=need_mask,
        )
        lps = token_logprobs(logits, toks) if want_logprobs else None
    return (
        _replicate_out(toks, pp_mesh), _replicate_out(lps, pp_mesh), cache
    )


def _pp_decode_chain(
    params, cache, lanes, block_tables, feed,
    *, n_steps, need_mask, want_logprobs=False,
    cfg, engine, pp_mesh, n_micro,
):
    """Wavefront pipeline-parallel decode: ``B`` lanes split into ``M``
    groups that march through the ``pp`` stages staggered one round
    apart, so in steady state EVERY stage works EVERY round (utilization
    ``n_steps*M / (n_steps*M + pp - 1)`` — the fill/drain bubble is paid
    once per chain, not once per token). The autoregressive feedback
    rides the ring: group ``g``'s next token is sampled when it drains
    stage ``pp-1`` at round ``g + t*M + pp - 1`` and re-enters stage 0 at
    round ``g + (t+1)*M`` — legal exactly when ``M >= pp`` (enforced by
    EngineCore). Same output contract as :func:`_megastep_body`: returns
    sampled ``[n_steps, B]`` (+ logprobs) and the cache, with the same
    on-device stop flags — a lane that samples a watched stop id (or
    exhausts its budget) at its drain round goes dead, and its remaining
    wavefront visits run masked no-ops (K/V writes routed to the garbage
    block, output padded with its last live token). The wavefront makes
    that legal: group ``g``'s step-``t`` drain (round ``g + t*M + pp-1``)
    strictly precedes EVERY stage's processing of its step ``t+1`` (first
    at round ``g + (t+1)*M``) whenever ``M >= pp``, so the updated alive
    mask is consistently visible pipe-wide before the dead lane would
    compute again. One deliberate divergence from ``_megastep_body``:
    dead-lane positions keep advancing (``pos0 + t`` stays in-table —
    _plan_decode pre-grows k tokens of block headroom per lane) because
    freezing them would need a second carried cursor; the writes are
    garbage-routed either way, so the emitted stream is identical. The
    host stop-scan stays the AUTHORITY (host-only stops / truncated
    watch lists roll back via the cursor, exactly as on one chip).

    No GPU schedule looks like this — it exists because under jit the
    whole chain is ONE XLA program and ppermute edges are ICI
    neighbor-hops, so "pipeline" degenerates into a ring rotation with
    modular-arithmetic bookkeeping (the reference delegates PP to its
    engines per-microbatch with host-driven queues instead)."""
    from dynamo_tpu.parallel.pipeline import pp_decode_round

    (tokens, positions, active, seeds, counters, temperature, top_k, top_p,
     watch, budgets, min_left) = unpack_lanes(lanes, feed)
    pp = int(pp_mesh.shape["pp"])
    M = n_micro
    B = tokens.shape[0]
    Bm = B // M
    tok_g = tokens.reshape(M, Bm)
    tab_g = block_tables.reshape(M, Bm, -1)
    pos_g = positions.reshape(M, Bm)
    act_g = active.reshape(M, Bm)
    seeds_g = seeds.reshape(M, Bm)
    cnt_g = counters.reshape(M, Bm)
    temp_g = temperature.reshape(M, Bm)
    k_g = top_k.reshape(M, Bm)
    p_g = top_p.reshape(M, Bm)
    watch_g = watch.reshape(M, Bm, -1)
    bud_g = budgets.reshape(M, Bm)
    ml_g = min_left.reshape(M, Bm)

    R = n_steps * M + pp - 1
    buf0 = jnp.zeros((pp, Bm, cfg.hidden_size), cfg.jax_dtype)
    out0 = jnp.zeros((n_steps, M, Bm), jnp.int32)
    alive0 = jnp.ones((M, Bm), bool)
    if want_logprobs:
        lp0 = (
            jnp.zeros((n_steps, M, Bm), jnp.float32),
            jnp.zeros((n_steps, M, Bm, LOGPROBS_K), jnp.int32),
            jnp.zeros((n_steps, M, Bm, LOGPROBS_K), jnp.float32),
        )
    else:
        lp0 = None

    def body(carry, r):
        store, buf, cache, alive, out, lps = carry
        buf, cache, logits = pp_decode_round(
            params, cache, buf, r, store, tab_g, pos_g, act_g & alive,
            cfg=cfg, engine=engine, mesh=pp_mesh, n_micro=M, n_steps=n_steps,
        )
        # Work item draining the last stage this round.
        e = r - (pp - 1)
        ev = e >= 0  # e < n_steps*M holds by construction of R
        ec = jnp.maximum(e, 0)
        ge = ec % M
        te = ec // M
        nxt = sample_seeded(
            logits, seeds_g[ge], cnt_g[ge] + te, temp_g[ge], k_g[ge], p_g[ge],
            need_mask=need_mask,
        )
        # Dead lanes pad with their last live token (same pinnable value
        # as _megastep_body — the host stop-scan resolves the repeated
        # stop id to the same stop position).
        live = act_g[ge] & alive[ge]
        new_tok = jnp.where(ev & live, nxt, store[ge])
        store = store.at[ge].set(new_tok)
        out = out.at[te, ge].set(jnp.where(ev, new_tok, out[te, ge]))
        stop = stop_flags(nxt, watch_g[ge], bud_g[ge], ml_g[ge], te)
        alive = alive.at[ge].set(
            jnp.where(ev, alive[ge] & ~stop, alive[ge])
        )
        if lps is not None:
            chosen, ids, vals = token_logprobs(logits, new_tok)
            lps = (
                lps[0].at[te, ge].set(jnp.where(ev, chosen, lps[0][te, ge])),
                lps[1].at[te, ge].set(jnp.where(ev, ids, lps[1][te, ge])),
                lps[2].at[te, ge].set(jnp.where(ev, vals, lps[2][te, ge])),
            )
        return (store, buf, cache, alive, out, lps), None

    (store, buf, cache, alive, out, lps), _ = jax.lax.scan(
        body, (tok_g, buf0, cache, alive0, out0, lp0), jnp.arange(R)
    )
    sampled = out.reshape(n_steps, B)
    if lps is not None:
        lps = tuple(
            a.reshape((n_steps, B) + a.shape[3:]) for a in lps
        )
    return (
        _replicate_out(sampled, pp_mesh), _replicate_out(lps, pp_mesh), cache
    )


def _program(fn, **bound):
    """``fn`` with ``bound`` given, under ``fn``'s name. JAX names a
    compiled program after ``__name__`` — in compile logs and events, IR
    dumps and profiler traces — and a ``functools.partial`` has none, so
    every serving program would read ``<unknown>``. A closure over
    ``(*args, **kw)`` and no partial, because ``jax.jit`` refuses a static
    name the signature cannot take, and a caller may name one a program
    no longer has (chipbench/rehearse_v5e.py)."""
    def program(*args, **kw):
        return fn(*args, **bound, **kw)

    program.__name__ = program.__qualname__ = fn.__name__
    return program


_ONE = ("need_mask", "want_logprobs")
_ONE_MM = (*_ONE, "want_mm")
_CHAIN = ("n_steps", *_ONE)
_CHAIN_MM = (*_CHAIN, "want_mm")


def compile_programs(model_cfg, engine_cfg, mesh, sp_mesh, pp_mesh, pp_micro) -> tuple:
    """The engine's jitted programs, in the order ``EngineCore.__init__``
    binds them to the attributes the dispatchers read: ``_prefill``,
    ``_ring``, ``_decode``, ``_fused``, ``_drafted``, ``_prefill_pp``,
    ``_decode_pp``, ``_feed``, ``_feed_pad`` (a ring's and a pipeline's
    are None where there is no such mesh). Every serving program takes the
    cache second and is given it (``donate_argnums``); nothing compiles
    here: a program is lowered at its first call."""
    both = dict(cfg=model_cfg, engine=engine_cfg)
    on_mesh = dict(both, mesh=mesh)
    staged = dict(both, pp_mesh=pp_mesh, n_micro=pp_micro)
    ring, pipe = sp_mesh is not None, pp_mesh is not None
    # program (None: not built), what is bound, its static names
    table = (
        (_prefill_and_sample, on_mesh, _ONE_MM),
        (_ring_prefill_and_sample if ring else None, dict(both, sp_mesh=sp_mesh), _ONE),
        (_megastep_body, on_mesh, _CHAIN),
        # The UNIVERSAL megastep (ISSUE 12): ragged first iteration
        # (prefill chunks + decode rows + verify rows) fused with
        # n_steps-1 scanned decode iterations in one dispatch; verify
        # accept/reject resolves on device.
        (_megastep_fused_body, on_mesh, _CHAIN_MM),
        # On-device drafting megastep (ISSUE 18): same ragged first
        # iteration, but the n_steps-1 scanned iterations are
        # verify-SHAPED — each round suffix-matches the per-lane history
        # ring, verifies the fresh draft R-wide, resolves accept/reject,
        # and redrafts, so draft→verify→accept loops inside one dispatch.
        (_megastep_draft_body,
         dict(on_mesh, ngram_max_static=engine_cfg.spec_ngram_max), _CHAIN_MM),
        (_pp_prefill_and_sample if pipe else None, staged, _ONE),
        (_pp_decode_chain if pipe else None, staged, _CHAIN),
    )
    # Device-resident token feedback: the next step's token buffer
    # gathers just-sampled ids straight from the previous dispatch's
    # device output (sampler.gather_feedback) — no D2H→H2D round trip
    # on the decode critical path. The source is first padded to ONE
    # flat width (_feed_pad: a program per output shape), so the
    # gather compiles per token-buffer width and not per (previous
    # width, next width) pair: serving crosses widths that warm-up's
    # phases, one width at a time, never pair up. A megastep gathers
    # inside its own program (unpack_lanes), from the same source.
    return (
        *(fn and jax.jit(_program(fn, **bound), static_argnames=static, donate_argnums=(1,))
          for fn, bound, static in table),
        jax.jit(gather_feedback),
        jax.jit(pad_feedback, static_argnames=("width",)),
    )
