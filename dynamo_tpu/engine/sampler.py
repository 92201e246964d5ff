"""Jittable batched token sampling: greedy / temperature / top-k / top-p.

Per-request sampling params arrive as arrays (one lane per sequence), so a
single compiled program serves any mix of greedy and sampled requests —
no per-request recompiles, no host round trip per token. Whether ANY lane
draws is read on the device too, from the batch's own ``temperature``
vector: one ``lax.cond`` whose true branch (every lane at temperature 0,
the common served case) is the arg-max alone, with no keys folded and no
draw over ``[B, V]``, and whose false branch is the seeded draw. It is NOT
a static argument: a static one compiles, and warm-up then has to run,
every serving program once a kind.

Full-vocab sorts are the classic decode-step killer (O(V log V) over 128k
vocab per token), so masking works on a ``k_cap``-sized `lax.top_k` slice:
top-k is exact for k <= k_cap and the nucleus is computed within those
top-k_cap candidates (the standard serving approximation — vLLM caps the
same way). Batches with no top-k/top-p lane among those that draw skip the
partial sort entirely (``need_mask=False``, the programs warm-up compiles;
``need_mask=True`` is the one static choice left, a second compiled
variant chosen by the host per batch and compiled on first use).

Capability parity: the sampling options the reference extracts in its
preprocessor (`lib/llm/src/protocols/common`) and hands to vLLM; here the
sampler is part of the first-party engine.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_TOP_CAP = 64

# Top-k alternatives returned when a request asks for logprobs. Static so
# the logprob program compiles once; per-request k <= this is sliced on
# the host. 20 covers the OpenAI maxima (completions k<=5, chat
# top_logprobs<=20) so no request is silently truncated.
LOGPROBS_K = 20


def gather_feedback(
    prev_tokens: jax.Array,   # previous dispatch's sampled tokens, any shape
    host_tokens: jax.Array,   # [T] int32 — host-assembled token buffer
    src_idx: jax.Array,       # [T] int32 — flat index into prev_tokens, or -1
) -> jax.Array:               # [T] int32
    """Device-resident token feedback (async pipelined execution): slots
    of the next step's token buffer whose value is a just-sampled token
    read it straight from the previous dispatch's device output — the
    sampled id never round-trips D2H→H2D on the critical path. Slots
    with ``src_idx < 0`` keep the host value (prefill chunks, draft
    tokens, already-committed pendings). One tiny program per (prev
    size, T) pair — the engine hands it every source at one size
    (:func:`pad_feedback`), so per T; enqueued on the device stream, so
    it never blocks the host."""
    flat = prev_tokens.reshape(-1)
    fed = flat[jnp.clip(src_idx, 0, flat.shape[0] - 1)]
    return jnp.where(src_idx >= 0, fed, host_tokens)


def pad_feedback(prev_tokens: jax.Array, *, width: int) -> jax.Array:
    """A dispatch's sampled tokens, any shape, flat and zero-padded to
    ``width``: flat indices into the output stay what they were, and
    every consumer of the feedback sees one source shape."""
    flat = prev_tokens.reshape(-1)
    return jnp.pad(flat, (0, width - flat.shape[0]))


def sample_seeded(
    logits: jax.Array,        # [B, V] float32
    seeds: jax.Array,         # [B] int32 — per-lane request seeds
    counters: jax.Array,      # [B] int32 — per-lane position counters
    temperature: jax.Array,   # [B] float32; 0 => greedy
    top_k: jax.Array,         # [B] int32
    top_p: jax.Array,         # [B] float32
    *,
    need_mask: bool = True,
) -> jax.Array:               # [B] int32
    """THE seeded-sampling entry every compiled program uses — prefill
    waves, decode megasteps, pp wavefronts, ring prefill, verify rows.
    Each lane's PRNG key is ``fold_in(fold_in(key0, seed), counter)``, so
    a seeded request reproduces bit-for-bit regardless of batch
    neighbors, scheduler, chain length, or pipelining: any path that
    samples position ``counter`` of request ``seed`` draws the same
    token. Scanned callers pass ``counters + i`` per inner iteration —
    which is why megastep output at k=8 matches k=1 exactly. The keys
    are folded where they are drawn from: a batch with every lane at
    temperature 0 folds none (:func:`_sample`)."""
    def keys() -> jax.Array:
        base = jax.random.PRNGKey(0)
        return jax.vmap(
            lambda s, c: jax.random.fold_in(jax.random.fold_in(base, s), c)
        )(seeds, counters)

    return _sample(logits, keys, temperature, top_k, top_p, need_mask=need_mask)


def stop_flags(
    sampled: jax.Array,    # [B] int32 — tokens just sampled at inner step i
    watch: jax.Array,      # [B, W] int32 — per-lane stop ids, -1 padded
    budgets: jax.Array,    # [B] int32 — remaining max-tokens generation budget
    min_left: jax.Array,   # [B] int32 — tokens until min_tokens is satisfied
    i: jax.Array,          # scalar int32 — 0-based inner iteration
) -> jax.Array:            # [B] bool — True where the lane stops HERE
    """On-device per-lane stop detection for the decode megastep: a lane
    that samples a watched id (EOS / stop_token_ids, once past its
    min-tokens floor) or exhausts its generation budget goes dead, and
    its remaining inner iterations run as masked no-ops (no K/V write,
    frozen position). The HOST stop-scan stays the authority — the
    device watch set may be a subset (host-only stop strings, truncated
    watch lists), so flags here may under-stop but never over-stop."""
    gen = i + 1  # tokens this chain has produced for the lane, inclusive
    watch_hit = (sampled[:, None] == watch).any(axis=1) & (gen >= min_left)
    budget_hit = gen >= budgets
    return watch_hit | budget_hit


def resolve_verify(
    sampled: jax.Array,    # [S, R] int32 — target choices per verify slot
    draft: jax.Array,      # [S, R-1] int32 — drafted tokens, -1 padded
    draft_len: jax.Array,  # [S] int32 — live draft length (0 = plain row)
) -> tuple[jax.Array, jax.Array]:  # (accepted [S], next_token [S])
    """On-device accept/reject for FUSED verify rows (the universal
    megastep): ``accepted`` is the longest drafted prefix the target
    agrees with — slot j of ``sampled`` is the target's own
    ``(seed, counter + j)``-keyed choice after the row's j-th token, so
    comparing it against ``draft[j]`` replays exactly the host-side
    accept loop — and ``next_token`` is the target's correction (or
    bonus) choice at slot ``accepted``, the token the lane continues
    decoding from inside the same dispatch. Rows that drafted nothing
    (decode rows, prefill chunks, draft-less verify rows) resolve to
    ``accepted == 0`` and their slot-0 sample, which is the plain
    single-step contract."""
    R = sampled.shape[1]
    if R == 1:
        zero = jnp.zeros(sampled.shape[0], jnp.int32)
        return zero, sampled[:, 0]
    j = jnp.arange(R - 1, dtype=jnp.int32)[None, :]
    match = (sampled[:, :-1] == draft) & (j < draft_len[:, None])
    acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    nxt = jnp.take_along_axis(sampled, acc[:, None], axis=1)[:, 0]
    return acc, nxt


def stop_flags_prefix(
    sampled: jax.Array,    # [S, R] int32 — iteration-0 sampled slots
    accepted: jax.Array,   # [S] int32 — emitted slots are 0..accepted
    watch: jax.Array,      # [S, W] int32 — per-lane stop ids, -1 padded
    budgets: jax.Array,    # [S] int32 — remaining max-tokens budget
    min_left: jax.Array,   # [S] int32 — tokens until min_tokens passes
    gen_base: jax.Array | None = None,  # [S] int32 — tokens already
                           # emitted by this dispatch before these slots
) -> jax.Array:            # [S] bool — True where the lane stops HERE
    """Stop detection over a fused iteration whose emission count is
    data-dependent (a verify row emits accepted + 1 tokens): slot j —
    dispatch-generation ``gen_base + j + 1`` — stops the lane if it is
    actually emitted (j <= accepted) and samples a watched id past the
    min-tokens floor, or lands on the budget edge. ``gen_base`` defaults
    to 0 (the megastep's first iteration); device-draft rounds pass the
    running per-lane emission count so budget/min-tokens arithmetic
    stays exact across multiple verify-shaped rounds in one dispatch.
    Same under-stop-never-over-stop contract as :func:`stop_flags`; the
    host stop-scan stays the authority."""
    R = sampled.shape[1]
    gen = jnp.arange(1, R + 1, dtype=jnp.int32)[None, :]
    if gen_base is not None:
        gen = gen + gen_base[:, None]
    emitted = (jnp.arange(R, dtype=jnp.int32)[None, :]) <= accepted[:, None]
    watch_hit = (sampled[:, :, None] == watch[:, None, :]).any(axis=2)
    hit = (watch_hit & (gen >= min_left[:, None])) | (gen >= budgets[:, None])
    return (hit & emitted).any(axis=1)


def ring_append(
    hist: jax.Array,      # [S, H] int32 — right-aligned history ring, -1 padded
    hist_len: jax.Array,  # [S] int32 — valid tokens (right-aligned)
    emitted: jax.Array,   # [S, E] int32 — row-packed fresh tokens
    count: jax.Array,     # [S] int32 in [0, E] — valid prefix of `emitted`
) -> tuple[jax.Array, jax.Array]:  # (hist' [S, H], hist_len' [S])
    """Shift ``count`` fresh tokens into each lane's history ring. The
    ring is right-aligned (newest token at column H-1), so the append is
    a per-lane gather over ``concat([hist, emitted])`` at offset
    ``count`` — count == 0 is the identity, which is how dead lanes and
    non-drafting rows ride the same program. Slots of ``emitted`` past
    ``count`` are never gathered (the read window ends at column
    H - 1 + count), so junk samples from rejected draft slots cannot
    leak into the history."""
    H = hist.shape[1]
    buf = jnp.concatenate([hist, emitted.astype(hist.dtype)], axis=1)
    idx = jnp.arange(H, dtype=jnp.int32)[None, :] + count[:, None]
    return (
        jnp.take_along_axis(buf, idx, axis=1),
        jnp.minimum(hist_len + count, H),
    )


def device_ngram_draft(
    hist: jax.Array,       # [S, H] int32 — right-aligned history ring, -1 padded
    hist_len: jax.Array,   # [S] int32 — valid tokens (min(true_len, H))
    window: jax.Array,     # [S] int32 — per-lane lookback bound (<= H)
    ngram_min: jax.Array,  # [S] int32
    ngram_max: jax.Array,  # [S] int32 (<= ngram_max_static)
    k_cap: jax.Array,      # [S] int32 — draft budget this round (<= slots;
                           # <= 0 disables the lane)
    *,
    ngram_max_static: int,  # engine-wide suffix-length bound (unrolled loop)
    slots: int,             # draft slot width of the verify row (spec_R - 1)
) -> tuple[jax.Array, jax.Array]:  # (draft [S, slots] -1 padded, draft_len [S])
    """Kernel-free on-device prompt-lookup drafter — the scanned-body
    replay of :func:`dynamo_tpu.spec.ngram.propose_ngram`.

    The ring holds each lane's last H = engine_window + engine_ngram_max
    tokens right-aligned, which is exactly the tail the host drafter is
    handed (`_draft_for` truncates to window + ngram_max), so ring
    coordinates and host-context coordinates describe the same candidate
    set. The match replays the host semantics bit-for-bit:

    - longest suffix first: the n loop is unrolled from
      ``ngram_max_static`` down to 1, lanes select via
      ``ngram_min <= n <= min(ngram_max, hist_len - 1)`` and the FIRST
      (largest) matching n wins;
    - most recent occurrence: among candidate starts the LARGEST ring
      index wins (``max`` over the match mask);
    - window bound: candidate starts below ``H - min(hist_len, window)``
      are masked (the ring analogue of ``lo = max(0, L - window)``);
    - the follow-on run is truncated at the ring end (== sequence end)
      and at ``k_cap``, matching the host's ``context[s+n : s+n+k]``.

    A lane with no match (or ``k_cap <= 0``, or too little history)
    drafts nothing — draft_len 0, slots -1 — which downstream resolves
    as a plain decode row. Pure jnp slice-compares over [S, H]: no
    kernel, O(S * H * ngram_max_static) VPU work per round."""
    S, H = hist.shape
    r_lo = H - jnp.minimum(hist_len, window)  # [S] first in-window start
    found = jnp.zeros(S, bool)
    best_r = jnp.zeros(S, jnp.int32)
    best_n = jnp.zeros(S, jnp.int32)
    for n in range(ngram_max_static, 0, -1):
        if n >= H:
            continue
        width = H - n  # candidate starts r in [0, H-n-1]
        m = jnp.ones((S, width), bool)
        for t in range(n):
            m = m & (hist[:, t:width + t] == hist[:, H - n + t][:, None])
        cand = jnp.arange(width, dtype=jnp.int32)[None, :]
        rn = jnp.max(jnp.where(m & (cand >= r_lo[:, None]), cand, -1), axis=1)
        sel = (ngram_min <= n) & (n <= jnp.minimum(ngram_max, hist_len - 1))
        upd = (~found) & sel & (rn >= 0)
        best_r = jnp.where(upd, rn, best_r)
        best_n = jnp.where(upd, jnp.int32(n), best_n)
        found = found | upd
    avail = H - (best_r + best_n)  # follow-run room to the ring end (>= 1)
    d = jnp.maximum(jnp.where(found, jnp.minimum(k_cap, avail), 0), 0)
    j = jnp.arange(slots, dtype=jnp.int32)[None, :]
    src = jnp.clip(best_r[:, None] + best_n[:, None] + j, 0, H - 1)
    draft = jnp.take_along_axis(hist, src, axis=1)
    draft = jnp.where(j < d[:, None], draft, jnp.int32(-1))
    return draft, d


def token_logprobs(
    logits: jax.Array,   # [B, V] float32 (raw, pre-temperature)
    tokens: jax.Array,   # [B] int32 — the sampled/chosen tokens
    k: int = LOGPROBS_K,
):
    """Chosen-token logprob plus top-k alternatives under the model's
    raw distribution (temperature-independent, the convention OpenAI
    clients expect for analysis; reference threads engine logprobs the
    same way, lib/llm/src/perf/logprobs.rs). Returns
    (chosen [B], top_ids [B, k] i32, top_lps [B, k] f32)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    lp = logits - lse
    chosen = jnp.take_along_axis(lp, tokens[:, None], axis=-1)[:, 0]
    top_lps, top_ids = jax.lax.top_k(lp, k)
    return chosen, top_ids.astype(jnp.int32), top_lps


def step_quota(B: int, steps: int, step):
    """The places of a block of ``B`` a denoising step reveals at the least:
    ``B // steps``, one more in the first ``B % steps`` steps (``step``
    0-based, a Python int or a traced scalar)."""
    return B // steps + (step < B % steps)


def hidden_at_most(B: int, steps: int) -> tuple[int, ...]:
    """``H_p`` for ``p = 0 .. steps``: the most places of a block of ``B``
    that can still be hidden when pass ``p`` starts, ``B`` less the quotas
    of the steps before it (:func:`unmask_block` reveals a step's quota, or
    every hidden place where they are fewer): 4, 2, 0 at ``B`` 4 and 2
    steps; 4, 3, 2, 1, 0 at 4. The last, 0, is what the last step leaves:
    a block's clean rows, which no head reads. A pass needs the head and the
    sampler on no more rows a lane than that (``programs._megastep_blocks``)."""
    out = [B]
    for step in range(steps):
        out.append(out[-1] - step_quota(B, steps, step))
    return tuple(out)


def unmask_block(
    conf: jax.Array,        # [S, B] float32: confidence of each place's sample
    hidden: jax.Array,      # [S, B] bool: places not yet revealed
    step: jax.Array,        # scalar int32: 0-based denoising step
    *,
    steps: int,
    threshold: float,
) -> tuple[jax.Array, jax.Array]:   # (reveal [S, B] bool, by_threshold [S] bool)
    """Which hidden places of each lane's block a denoising step reveals
    (a block-diffusion model's confidence-ordered unmasking,
    ``low_confidence_dynamic``): every hidden place whose confidence is
    OVER ``threshold``; or, where those are fewer than the step's quota,
    the quota's most confident hidden places, ties to the lower place.
    The quota is ``B // steps``, one more in the first ``B % steps``
    steps, so that ``steps`` steps reveal a block whatever the threshold
    does; a block with fewer hidden places than the quota (known places
    of a prompt's tail) reveals them all. ``by_threshold`` says which of
    the two rules a lane's reveal came by."""
    B = conf.shape[1]
    quota = step_quota(B, steps, step)
    over = hidden & (conf > threshold)
    c = jnp.where(hidden, conf, -jnp.inf)
    place = jnp.arange(B, dtype=jnp.int32)
    # a place's rank among its lane's hidden places: those surer, or as
    # sure and lower
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (place[None, None, :] < place[None, :, None]))
    rank = jnp.sum(ahead & hidden[:, None, :], axis=2)
    by_threshold = jnp.sum(over, axis=1) >= quota
    reveal = jnp.where(by_threshold[:, None], over, hidden & (rank < quota))
    return reveal, by_threshold


def sample(
    logits: jax.Array,        # [B, V] float32
    rng: jax.Array,           # single key, or per-lane keys [B, 2]
    temperature: jax.Array,   # [B] float32; 0 => greedy
    top_k: jax.Array,         # [B] int32; <= 0 => disabled
    top_p: jax.Array,         # [B] float32; >= 1 => disabled
    *,
    need_mask: bool = True,   # static: False skips top-k/top-p entirely
    k_cap: int = DEFAULT_TOP_CAP,
) -> jax.Array:               # [B] int32
    return _sample(logits, lambda: rng, temperature, top_k, top_p,
                   need_mask=need_mask, k_cap=k_cap)


def _sample(logits, keys, temperature, top_k, top_p, *, need_mask, k_cap=DEFAULT_TOP_CAP):
    """:func:`sample` with its key(s) as a function, called where they are
    drawn from. Without a mask the body is ONE conditional on whether any
    lane draws, a scalar read from the batch itself and so a real branch on
    the device (inside a megastep's scan too). Each branch reads the logits
    as often as it needs and no more: the arg-max is NOT hoisted in front,
    which would cost the drawn branch a second pass over ``[B, V]``."""
    def greedy() -> jax.Array:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn() -> jax.Array:
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        if need_mask:
            cap = min(k_cap, logits.shape[1])
            sampled = _draw_masked(keys(), scaled, top_k, top_p, cap)
        else:
            sampled = _draw(keys(), scaled)
        return jnp.where(temperature <= 0.0, greedy(), sampled)

    if need_mask:
        return drawn()
    # Whole-batch greedy (the common served case at temperature=0) skips
    # the keys and the gumbel draw over [B, V] entirely; a lane at
    # temperature 0 in a batch that draws gets its arg-max too.
    return jax.lax.cond(jnp.all(temperature <= 0.0), greedy, drawn)


def _draw(rng: jax.Array, values: jax.Array) -> jax.Array:
    if rng.ndim == 2:
        # Per-lane keys: each request draws from its own seeded
        # stream, reproducible regardless of batch neighbors.
        return jax.vmap(jax.random.categorical)(rng, values).astype(jnp.int32)
    return jax.random.categorical(rng, values, axis=-1).astype(jnp.int32)


def _draw_masked(rng, scaled, top_k, top_p, cap: int) -> jax.Array:
    """A draw a lane from ``scaled`` under its top-k / top-p, within the
    ``cap`` largest candidates (the module docstring)."""
    vals, idx = jax.lax.top_k(scaled, cap)  # [B, cap] descending
    ranks = jnp.arange(cap, dtype=jnp.int32)[None, :]
    k = jnp.where(top_k > 0, jnp.minimum(top_k, cap), cap)[:, None]
    keep_k = ranks < k

    probs = jax.nn.softmax(vals, axis=-1)
    cum_prev = jnp.cumsum(probs, axis=-1) - probs
    # Keep ranks whose preceding cumulative mass is < top_p (rank 0 always).
    keep_p = cum_prev < jnp.where(top_p >= 1.0, 2.0, top_p)[:, None]

    masked = jnp.where(keep_k & keep_p, vals, -jnp.inf)
    choice = _draw(rng, masked)  # index into the capped candidate set
    sampled_masked = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    # Pure-temperature lanes in a masked batch keep full-vocab sampling
    # (categorical is sort-free); only lanes that asked for top-k/top-p
    # get the capped candidate set.
    sampled_full = _draw(rng, scaled)
    lane_masked = (top_k > 0) | (top_p < 1.0)
    return jnp.where(lane_masked, sampled_masked, sampled_full)
