"""Compare the worker's engine with the plain reference, in log-probabilities.

The parent asks the worker's side thread (``ref.request``, see
``chipbench/worker_entry.py``) while no traffic is offered. The worker
sends a greedy probe of token ids, with log-probabilities, through its
own engine core (:func:`run_probe`): the engine prefills the prompt and
decodes through its paged cache, and reports for every generated
position the chosen token's log-probability and the top alternatives,
computed from its raw logits. It then runs the reference's full forward
pass over prompt + generated tokens on its own weights
(:func:`score_request`), and the parent holds the two together with
:func:`compare`. Sent a second time, the probe's prompt is a prefix-cache
hit, so that path is compared as well. The HTTP path is not part of this
check: every measured response is checked on it (``run.check_record``).

Log-probabilities and not sampled tokens, because with random weights
the largest logit changes on rounding; where the engine's greedy choice
is not the reference's, the two top logits must be within tolerance.
"""

from __future__ import annotations


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


def _dequant(w):
    """Engine weight leaf (plain, or int8 {"w", "scale"}) as float32."""
    import jax.numpy as jnp

    if isinstance(w, dict):
        return w["w"].astype(jnp.float32) * w["scale"].astype(jnp.float32)
    return w.astype(jnp.float32)


def published_layout(params, l: int, mf: dict, mlp_blocks: int = 8):
    """Layer ``l`` of the engine's parameter tree (fused, maybe int8,
    tp=1 column order ``[q | k | v]`` and ``[gate | up]``) as the float32
    unfused pieces the reference takes: (attention weights, mlp_norm,
    iterator of MLP column blocks). Each piece is de-quantised when it is
    asked for and dropped when the reference has used it."""
    import jax
    import jax.numpy as jnp

    lp = jax.tree.map(lambda a: a[l], params["layers"])
    q_size = mf["num_heads"] * mf["head_dim"]
    kv_size = mf["num_kv_heads"] * mf["head_dim"]
    wqkv = _dequant(lp["wqkv"])
    bqkv = (lp["bqkv"].astype(jnp.float32) if "bqkv" in lp
            else jnp.zeros((q_size + 2 * kv_size,), jnp.float32))
    attn = {
        "attn_norm": lp["attn_norm"].astype(jnp.float32),
        "wq": wqkv[:, :q_size], "wk": wqkv[:, q_size:q_size + kv_size],
        "wv": wqkv[:, q_size + kv_size:],
        "bq": bqkv[:q_size], "bk": bqkv[q_size:q_size + kv_size],
        "bv": bqkv[q_size + kv_size:],
        "wo": _dequant(lp["wo"]),
    }
    inter = mf["intermediate_size"]
    edges = [inter * i // mlp_blocks for i in range(mlp_blocks + 1)]
    cols = lambda w, a, b: _dequant(jax.tree.map(lambda x: x[..., a:b], w))  # noqa: E731

    def blocks():
        for a, b in zip(edges, edges[1:]):
            down = lp["w_down"]
            if isinstance(down, dict):   # scale is per output channel: all rows share it
                w_down = down["w"][a:b].astype(jnp.float32) * down["scale"]
            else:
                w_down = down[a:b].astype(jnp.float32)
            yield cols(lp["wgu"], a, b), cols(lp["wgu"], inter + a, inter + b), w_down

    return attn, lp["mlp_norm"].astype(jnp.float32), blocks()


def reference_logprobs(params, mf: dict, ids: list[int], rows: list[int],
                       vocab_chunks: int = 16):
    """log-softmax [len(rows), vocab] (numpy, float32) of the reference
    on the engine's own weights ``params`` at positions ``rows`` of the
    sequence ``ids``. ``mf`` are the ModelConfig fields."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import qwen2

    if int(params.get("fuse_tp", 1)) != 1:
        raise ValueError("the reference reads the tp=1 fused layout only")
    v = mf["vocab_size"]
    edges = [v * i // vocab_chunks for i in range(vocab_chunks + 1)]

    def lm_chunks():
        for a, b in zip(edges, edges[1:]):
            if mf.get("tie_embeddings"):
                yield params["embed"][a:b].astype(jnp.float32).T
            else:
                yield _dequant(jax.tree.map(lambda x: x[..., a:b], params["lm_head"]))

    logits = qwen2.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32), lm_chunks(),
        n_heads=mf["num_heads"], n_kv=mf["num_kv_heads"],
        head_dim=mf["head_dim"], theta=mf["rope_theta"],
        eps=mf["rms_norm_eps"], rows=rows,
    )
    return np.asarray(jax.nn.log_softmax(logits, axis=-1), np.float32)


def run_probe(core, prompt_ids: list[int], max_tokens: int, top: int, tag: str) -> dict:
    """One greedy request with log-probabilities through the worker's own
    engine core, stepped the way its warm-up steps it: the same programs,
    scheduler and paged cache a served request runs on. Only called while
    the worker serves nothing (the parent sends no traffic meanwhile)."""
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
    return {
        "tokens": tokens,
        "top_ids": [[t for t, _ in e["top"]] for e in entries],
        "top_lps": [[lp for _, lp in e["top"]] for e in entries],
        "cached_tokens": int(seq.num_cached_tokens),
    }


def score_request(core, cfg: dict, body: dict) -> dict:
    """Runs in the worker. Sends the probe twice through the engine (the
    second send finds the prompt in the prefix cache), then runs the
    reference over prompt + generated tokens and returns both sides:
    ``served`` as :func:`run_probe` gives it, and ``scored`` with the
    reference's log-probability of every id the engine listed, its own
    arg-max and that arg-max's log-probability, per generated position."""
    import numpy as np

    from chipbench.configs import model_fields

    mf = model_fields(cfg)
    prompt = list(body["prompt_ids"])
    served = [run_probe(core, prompt, body["max_tokens"], body["top"], tag)
              for tag in ("first", "repeat")]
    scored = []
    for probe in served:
        if scored and probe["tokens"] == served[0]["tokens"] and (
                probe["top_ids"] == served[0]["top_ids"]):
            scored.append(scored[0])   # the same sequence and the same ids asked
            continue
        ids = prompt + probe["tokens"]
        # Position p's logits predict token p+1: generated token j (at
        # index len(prompt) + j) is predicted from row len(prompt) + j - 1.
        rows = list(range(len(prompt) - 1, len(ids) - 1))
        lp = reference_logprobs(core.params, mf, ids, rows)
        best = lp.argmax(-1)
        scored.append({
            "top_lps": [[float(lp[r, t]) for t in tops]
                        for r, tops in enumerate(probe["top_ids"])],
            "argmax": [int(b) for b in best],
            "argmax_lp": [float(lp[r, b]) for r, b in enumerate(best)],
            "finite": bool(np.isfinite(lp).all()),
        })
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
