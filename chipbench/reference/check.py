"""Compare the worker's engine with the plain reference, in log-probabilities.

The parent asks the worker's side thread (``ref.request``, see
``chipbench/worker_entry.py``) while no traffic is offered. The worker
sends a greedy probe of token ids, with log-probabilities, through its
own engine core (:func:`run_probe`): the engine prefills the prompt and
decodes through its paged cache, and reports for every generated
position the chosen token's log-probability and the top alternatives,
computed from its raw logits. It then runs the full forward pass of its
architecture's plain reference (``chipbench/architectures``, by the
configuration's ``model_type``) over prompt + generated tokens on its own
weights (:func:`score_request`), and the parent holds the two together
with :func:`compare`. Sent a second time, the probe's prompt is a
prefix-cache hit, so that path is compared as well. How long the probe is,
is the configuration's to say (``run.probe_of``); how the reference scores
it is the architecture's, where its module brings a ``score_probe`` of its
own (a step that yields other than the next token of a sequence), and
:func:`score_probe` here otherwise. The comparison and its tolerance are
neither's. The HTTP path is not
part of this check: every measured response is checked on it
(``run.check_record``).

Log-probabilities and not sampled tokens, because with random weights
the largest logit changes on rounding; where the engine's greedy choice
is not the reference's, the two top logits must be within tolerance.
"""

from __future__ import annotations

from chipbench import architectures
from chipbench.configs import model_fields

# Tolerance on a log-probability, and its reason. The engine keeps bf16
# activations (8 significand bits: 2^-8 relative per rounding) through
# every layer's residual stream and feeds bf16 operands to the MXU with
# f32 accumulation; the reference is f32 throughout on the same weight
# values. With these random weights logits are O(1) (std ~1), and the
# rounding of ~6 bf16 casts per layer over 28 layers accumulates to a few
# 2^-8 x sqrt(170) ~ 0.05 at worst over the positions and tokens
# compared. Measured on the v5e (PERF.md, Findings): max |diff| 0.02-0.06
# for both configurations. 0.15 is ~3x that, and far below the ~0.5-1.0
# that fp8/int8 activations or a dropped bias or rope term produce (the
# tiny-size test injects such faults and must fail).
LOGPROB_ATOL = 0.15


def reference_logprobs(cfg: dict, params, ids: list[int], rows: list[int], **options):
    """log-softmax [len(rows), vocab] (numpy, float32) of the plain
    reference of the configuration's architecture on the engine's own
    weights ``params`` at positions ``rows`` of the sequence ``ids``.
    ``options`` are that reference's own (how finely it cuts its weights)."""
    import jax
    import numpy as np

    logits = architectures.of(cfg).reference_logits(
        params, model_fields(cfg), ids, rows, **options)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1), np.float32)


def run_probe(core, prompt_ids: list[int], max_tokens: int, top: int, tag: str,
              extra: bool = False) -> dict:
    """One greedy request with log-probabilities through the worker's own
    engine core, stepped the way its warm-up steps it: the same programs,
    scheduler and paged cache a served request runs on. Only called while
    the worker serves nothing (the parent sends no traffic meanwhile).
    ``extra``: carry what else the program said of each token, for an
    architecture that scores the probe itself."""
    from dynamo_tpu.llm.protocols.common import (
        OutputOptions,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    seq = core.add_request(PreprocessedRequest(
        model="chipbench-probe", token_ids=list(prompt_ids),
        request_id=f"chipbench-probe-{tag}",
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        output=OutputOptions(logprobs=top),
    ))
    tokens: list[int] = []
    entries: list[dict] = []
    while seq.finish is None:
        for s, out in core.step():
            if s is seq:
                tokens += list(out.token_ids)
                entries += list(out.logprobs or [])
    probe = {
        "tokens": tokens,
        "top_ids": [[t for t, _ in e["top"]] for e in entries],
        "top_lps": [[lp for _, lp in e["top"]] for e in entries],
        "cached_tokens": int(seq.num_cached_tokens),
    }
    if extra:
        # what else the program said of each token (the denoising step that
        # chose it, say): a CLAIM, which the architecture's score_probe checks
        # against what its own forward would have done; it never only replays it
        probe["extra"] = [{k: v for k, v in e.items() if k != "top"} for e in entries]
    return probe


def score_probe(cfg: dict, params, prompt: list[int], probe: dict, **options) -> dict:
    """The reference's side of one probe as :func:`run_probe` gave it: its
    log-probability of every id the engine listed, its own arg-max and
    that arg-max's log-probability, per generated position. For a model
    whose step yields the NEXT token: one causal forward over prompt +
    generated tokens. An architecture whose tokens are chosen otherwise
    brings a ``score_probe`` of its own with this signature and this return
    (``chipbench/architectures``, which says what it owes), built on
    :func:`reference_logprobs`."""
    import numpy as np

    ids = prompt + probe["tokens"]
    # Position p's logits predict token p+1: generated token j (at
    # index len(prompt) + j) is predicted from row len(prompt) + j - 1.
    rows = list(range(len(prompt) - 1, len(ids) - 1))
    lp = reference_logprobs(cfg, params, ids, rows, **options)
    best = lp.argmax(-1)
    return {
        "top_lps": [[float(lp[r, t]) for t in tops]
                    for r, tops in enumerate(probe["top_ids"])],
        "argmax": [int(b) for b in best],
        "argmax_lp": [float(lp[r, b]) for r, b in enumerate(best)],
        "finite": bool(np.isfinite(lp).all()),
    }


def _on_one_stack_chunk(fn, *args):
    """Call ``fn(*args)`` with its whole Python call stack in one piece of
    memory, so that ``correct_check_s`` does not depend on how deep the
    caller happens to sit. CPython (3.11 and later) keeps frames in 16 KiB
    chunks, mapped when a call does not fit and unmapped when it returns;
    where a chunk's end falls just under a hot loop of JAX's tracing, every
    call of that loop pays an mmap, a page fault and a munmap. Which loop
    is hit depends on every frame's size from the thread's first frame
    down: PR 26 added two frames above the reference and the check took 3 s
    longer (PERF.md, Findings). A frame that reserves more than half a MiB
    of evaluation stack gets a chunk of 1 MiB of its own, which holds every
    frame below it. The program's warm-up does the same
    (``dynamo_tpu.engine.warmup``); copied, because the yardstick takes no
    code from the program."""
    return fn(*args)


_on_one_stack_chunk.__code__ = _on_one_stack_chunk.__code__.replace(
    co_stacksize=1 << 16  # slots of 8 bytes: 512 KiB, so a 1 MiB chunk
)


def score_request(core, cfg: dict, body: dict) -> dict:
    """Runs in the worker. Sends the probe twice through the engine (the
    second send finds the prompt in the prefix cache), then runs the
    reference over prompt + generated tokens and returns both sides:
    ``served`` as :func:`run_probe` gives it, and ``scored`` as
    :func:`score_probe` does."""
    return _on_one_stack_chunk(_score_request, core, cfg, body)


def _score_request(core, cfg: dict, body: dict) -> dict:
    prompt = list(body["prompt_ids"])
    own = getattr(architectures.of(cfg), "score_probe", None)
    served = [run_probe(core, prompt, body["max_tokens"], body["top"], tag, extra=bool(own))
              for tag in ("first", "repeat")]
    asked = ("tokens", "top_ids") + (("extra",) if own else ())
    scored = []
    for probe in served:
        if scored and all(probe[k] == served[0][k] for k in asked):
            scored.append(scored[0])   # the same sequence and the same ids asked
        else:
            scored.append((own or score_probe)(cfg, core.params, prompt, probe))
    return {"served": served, "scored": {"sequences": scored},
            "megastep_k": int(core.engine.megastep)}


def compare(served: list[dict], scored: dict, atol: float = LOGPROB_ATOL) -> dict:
    """``served[i]`` = {"top_ids", "top_lps", "tokens"} of probe i as the
    engine reported it; ``scored`` = :func:`score_request`'s answer.
    Returns {"ok", "max_abs_diff", "argmax_mismatches", "compared"}."""
    worst, compared, mismatches, ok = 0.0, 0, 0, True
    for s, r in zip(served, scored["sequences"], strict=True):
        ok &= r["finite"]
        for j, (ids, lps) in enumerate(zip(s["top_ids"], s["top_lps"], strict=True)):
            for got, want in zip(lps, r["top_lps"][j], strict=True):
                worst = max(worst, abs(got - want))
                compared += 1
            if s["tokens"][j] != r["argmax"][j]:
                # A near-tie may fall either way; anything else is a fault.
                mismatches += 1
                chosen = lps[ids.index(s["tokens"][j])] if s["tokens"][j] in ids else None
                if chosen is None or abs(chosen - r["argmax_lp"][j]) > atol:
                    ok = False
    ok = ok and compared > 0 and worst <= atol
    return {"ok": bool(ok), "max_abs_diff": worst,
            "argmax_mismatches": mismatches, "compared": compared}
