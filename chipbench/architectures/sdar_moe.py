"""``model_type`` "sdar_moe": JetLM's SDAR-30B-A3B-Chat (published
``config.json``; a Qwen3-MoE body trained to generate by diffusion over
blocks). Grouped-query attention with an RMSNorm over each head of q and k
before rope (the base family's QK-norm), ``num_experts`` experts of width
``moe_intermediate_size`` scored by a softmax over all of them,
``num_experts_per_tok`` chosen, weights normalised over the chosen
(``norm_topk_prob``); no shared expert, no dense layer. A query at position
``p`` sees key ``j`` iff ``j // B <= p // B``. Its plain reference is
``chipbench/reference/sdar_moe.py``.

A step of this model yields other than the next token of a sequence, so the
module brings :func:`score_probe` (``chipbench/architectures/__init__.py``,
``OPTIONAL``). The keys of the program's log-probability entry it trusts,
beside ``top``: ``block`` (the token's position ``// B``), ``place`` (its
position ``% B``), ``step`` (the 0-based denoising step that revealed it),
and, on the last kept entry of a block that a stop cut, ``cut``: ``[[place,
step, token], ...]`` of the block's places after the cut, which the device
revealed and no client was sent (a later step's input held them).

Counts, from shapes alone: a pass of the program (one forward over ``B``
rows a lane: a denoising pass or the clean one) reads the head, no
embedding, and every expert (:func:`decode_weight_bytes`; ``tests/
chipbench/test_chipbench_sdar.py`` pins it to the leaves the pass reads):
at 128 lanes x 4 rows the expert layer runs the grouped product over the
4,096 chosen pairs (``model._experts_grouped``), which reads an expert where
a row chose it, and rows that route apart (``model._qk_norm_gain`` says what
it took with random weights) reach all 128.
:func:`forward_flops_per_token` is the operations ONE
LANE's pass needs: ``B`` rows, each through its ``k`` chosen experts; the
readers divide the program's time by the passes a dispatch fuses and multiply
by the live lanes.
"""

from __future__ import annotations

import math

from chipbench import peaks
from chipbench.architectures import UNKNOWN, Observed, qwen2

# published key -> ModelConfig field; the last five are the configuration's
# ``assumed`` (the catalog's copy of config.json gives none of them)
KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "torch_dtype": "dtype",
    "block_length": "block_length",
    "denoising_steps": "denoising_steps",
    "confidence_threshold": "confidence_threshold",
    "mask_token_id": "mask_token_id",
}

# published keys this file reads no equation from must hold the one value
# the equations assume
_ASSUMED = {"attention_bias": False, "decoder_sparse_step": 1, "hidden_act": "silu",
            "mlp_only_layers": [], "rope_scaling": None, "use_sliding_window": False,
            "norm_topk_prob": True}


def derived(cfg: dict) -> dict:
    """The router's scoring and QK-norm are the model type's."""
    for key, want in _ASSUMED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"sdar_moe: {key}={cfg[key]!r} is not modelled (only {want!r})")
    return {"router_scoring": "softmax", "qk_norm": True, "rope_theta": float(cfg["rope_theta"])}


# -- the engine's parameter tree as the reference's float32 pieces ---------

def published_layout(params, l: int, mf: dict):
    """Layer ``l`` of the engine's tree (``layers``: the two norms, ``wqkv``,
    ``wo`` and the head norms of every layer; ``moe``: router and experts
    ``w_gu [E, h, 2 im]`` / ``w_down``) as ``(attention weights, mlp_norm,
    w_router, experts)`` for ``reference.sdar_moe.forward``."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731 — served unquantised
    lp = qwen2.layer(params, l)
    q_size = mf["num_heads"] * mf["head_dim"]
    kv_size = mf["num_kv_heads"] * mf["head_dim"]
    wqkv = f32(lp["wqkv"])
    w_attn = {"attn_norm": f32(lp["attn_norm"]), "wq": wqkv[:, :q_size],
              "wk": wqkv[:, q_size:q_size + kv_size], "wv": wqkv[:, q_size + kv_size:],
              "wo": f32(lp["wo"]), "q_norm": f32(lp["q_layernorm"]),
              "k_norm": f32(lp["k_layernorm"])}
    m = {k: v[l] for k, v in params["moe"].items()}
    im = mf["moe_intermediate_size"]

    def experts():
        for e in range(mf["num_experts"]):
            yield (e, f32(m["w_gu"][e, :, :im]), f32(m["w_gu"][e, :, im:]), f32(m["w_down"][e]))

    return w_attn, f32(lp["mlp_norm"]), f32(m["w_router"]), experts()


def _forward(params, mf: dict, ids, rows, vocab_chunks: int = 16, faults=()):
    """``reference.sdar_moe.forward`` on the engine's own weights: logits
    ``[N, R, vocab]`` of ``ids [N, T]`` at ``rows [N, R]``."""
    import jax.numpy as jnp

    from chipbench.reference import sdar_moe

    qwen2.require_tp1(params)
    return sdar_moe.forward(
        ids, params["embed"],
        (published_layout(params, l, mf) for l in range(mf["num_layers"])),
        params["final_norm"].astype(jnp.float32),
        qwen2.lm_head_chunks(params, mf, vocab_chunks),
        n_heads=mf["num_heads"], n_kv=mf["num_kv_heads"], head_dim=mf["head_dim"],
        theta=mf["rope_theta"], eps=mf["rms_norm_eps"], top_k=mf["num_experts_per_tok"],
        block=mf["block_length"], rows=rows, faults=faults,
    )


def reference_logits(params, mf: dict, ids: list[int], rows: list[int],
                     vocab_chunks: int = 16, faults: tuple[str, ...] = ()):
    """Logits [len(rows), vocab] (float32) of the plain block-masked forward
    on the engine's own weights ``params`` at positions ``rows`` of ``ids``
    (which may hold mask tokens). ``faults`` as ``reference/sdar_moe.py``
    reads them."""
    return _forward(params, mf, [list(ids)], [list(rows)], vocab_chunks, faults)[0]


# -- the reference's side of a probe: each token from its own row ------------

def _quota(B: int, steps: int, step: int) -> int:
    return B // steps + (step < B % steps)


def _claims(mf: dict, steps: int, prompt, tokens, extra):
    """The claimed schedule as ``{position: (token, step)}`` of every place
    the device revealed (the kept tokens and a cut block's discarded ones),
    or None where the claim is not one a program of this kind can have run:
    a token off its position's block or place, a step outside ``0 .. steps -
    1``, a cut block whose other places are not told, or a step that
    revealed fewer hidden places than its quota."""
    B, P = mf["block_length"], len(prompt)
    if len(extra) != len(tokens):
        return None
    at: dict[int, tuple[int, int]] = {}
    for j, (tok, e) in enumerate(zip(tokens, extra)):
        pos = P + j
        if (e.get("block"), e.get("place")) != (pos // B, pos % B):
            return None
        at[pos] = (int(tok), e.get("step", -1))
        for place, step, cut_tok in e.get("cut", ()):
            if not pos % B < place < B:
                return None
            at[pos // B * B + place] = (int(cut_tok), step)
    if not at or set(at) != set(range(P, (max(at) // B + 1) * B)):
        return None     # a block with a place neither kept nor told
    for first in range(P // B * B, max(at) + 1, B):
        mine = [at[p][1] for p in range(first, first + B) if p in at]
        if any(not 0 <= s < steps for s in mine):
            return None
        for step in range(steps):
            hidden = sum(s >= step for s in mine)
            if sum(s == step for s in mine) < min(_quota(B, steps, step), hidden):
                return None
    return at


def score_probe(cfg: dict, params, prompt: list[int], probe: dict,
                faults: tuple[str, ...] = (), **options) -> dict:
    """The optional member of an architecture module: the reference's side
    of one GREEDY probe, each generated token scored from its OWN row of the
    input of the denoising step that is claimed to have revealed it
    (``probe["extra"]``: the keys the module's docstring names). Every step
    of every block goes through ONE batched forward.

    The claim is checked, not only replayed. An illegal schedule is not
    ``finite`` (:func:`_claims`). And over each step's input the reference
    keeps the schedule itself: the confidence of a hidden place is its row's
    largest probability; it would reveal every hidden place over the
    threshold or, if those are fewer than the step's quota, the quota's
    surest (ties to the lower place). Where the claimed step revealed a
    place the reference would not have, the token's ``argmax`` is no token
    (-1) and its ``argmax_lp`` its own log-probability RAISED by what the
    place lacked (the surest place the reference would have revealed in its
    stead, or the threshold), so that ``compare`` holds the two to its
    near-tie rule; likewise, raised by what it had over the threshold, for a
    place the reference would have revealed a step earlier with no other
    place claimed in its stead. ``faults``: "order" makes the reference keep
    ANOTHER schedule (the quota's leftmost hidden places, no threshold: the
    confidence not read at all), the others are the forward's."""
    import numpy as np

    from chipbench.configs import model_fields

    mf = model_fields(cfg)
    B, steps, mask = mf["block_length"], mf["denoising_steps"], mf["mask_token_id"]
    log_thr = math.log(mf["confidence_threshold"]) if mf["confidence_threshold"] > 0 else -math.inf
    tokens, P = probe["tokens"], len(prompt)
    at = _claims(mf, steps, prompt, tokens, probe["extra"])
    if at is None:
        return {"top_lps": [[0.0] * len(t) for t in probe["top_ids"]],
                "argmax": [-1] * len(tokens), "argmax_lp": [0.0] * len(tokens), "finite": False}
    end = max(at) + 1
    known = list(prompt) + [at[p][0] for p in range(P, end)]
    firsts = list(range(P // B * B, end, B))
    ids, rows = [], []
    for first in firsts:
        for step in range(steps):
            block = [known[p] if p < P or at[p][1] < step else mask
                     for p in range(first, first + B)]
            ids.append(known[:first] + block + [0] * (end - first - B))
            rows.append(list(range(first, first + B)))
    forward_faults = tuple(f for f in faults if f != "order")
    import jax

    lps = np.asarray(jax.nn.log_softmax(
        _forward(params, mf, ids, rows, faults=forward_faults, **options), axis=-1), np.float32)
    finite = bool(np.isfinite(lps).all())
    raised: dict[int, float] = {}      # position -> what its claim lacks, in log-probability
    for n, (first, step) in enumerate((f, s) for f in firsts for s in range(steps)):
        hidden = [p for p in range(first, first + B) if p >= P and at[p][1] >= step]
        conf = {p: float(lps[n, p - first].max()) for p in hidden}
        quota = min(_quota(B, steps, step), len(hidden))
        over = [p for p in hidden if conf[p] > log_thr]
        if "order" in faults:
            mine = hidden[:quota]
        else:
            mine = over if len(over) >= quota else sorted(
                hidden, key=lambda p: (-conf[p], p))[:quota]
        claimed = [p for p in hidden if at[p][1] == step]
        spare = [p for p in mine if p not in claimed]          # the reference's, not claimed
        for p in claimed:
            if p not in mine:
                raised[p] = max([conf[q] for q in spare] or [log_thr]) - conf[p]
        if not any(p not in mine for p in claimed):
            for p in spare:     # should have gone a step earlier, by the threshold
                raised[p] = max(raised.get(p, 0.0), conf[p] - log_thr)
    top_lps, argmax, argmax_lp = [], [], []
    for j, tops in enumerate(probe["top_ids"]):
        pos = P + j
        row = lps[firsts.index(pos // B * B) * steps + at[pos][1], pos % B]
        top_lps.append([float(row[t]) for t in tops])
        if pos in raised:
            argmax.append(-1)
            argmax_lp.append(float(row[tokens[j]]) + raised[pos])
        else:
            argmax.append(int(row.argmax()))
            argmax_lp.append(float(row.max()))
    return {"top_lps": top_lps, "argmax": argmax, "argmax_lp": argmax_lp, "finite": finite}


# -- counts from shapes ------------------------------------------------------

def _act(mf: dict) -> int:
    return peaks._DTYPE_BYTES[mf.get("dtype", "bfloat16")]


def attention_params(mf: dict) -> int:
    """One attention operator's matrices and its two head norms."""
    h, d = mf["hidden_size"], mf["head_dim"]
    q, kv = mf["num_heads"] * d, mf["num_kv_heads"] * d
    return h * (q + 2 * kv) + q * h + 2 * d


def expert_params(mf: dict) -> int:
    return 3 * mf["hidden_size"] * mf["moe_intermediate_size"]


def decode_weight_bytes(mf: dict, quant: str | None, observed: Observed = UNKNOWN) -> int:
    """Bytes of weights ONE PASS over a block (a denoising pass, or the clean
    one) must read from HBM: every layer's attention, its two norms, its
    router and ALL its experts (a pass's 128 lanes x 4 rows x 8 choices
    reach every one), the final norm and the untied output matrix. The
    embedding LOOKUP reads a row a place and is left out. From shapes
    alone: ``observed`` is the surface's, and not read."""
    if quant is not None:
        raise ValueError(f"sdar_moe is served unquantised; no count for quant {quant!r}")
    h, L = mf["hidden_size"], mf["num_layers"]
    layer = (attention_params(mf) + 2 * h + h * mf["num_experts"]
             + mf["num_experts"] * expert_params(mf))
    return int((L * layer + h + h * mf["vocab_size"]) * _act(mf))


def kv_bytes_per_token(mf: dict, kv_bytes: int = 2) -> int:
    return mf["num_layers"] * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes


def attn_decode_bytes_per_layer(context_tokens: list[int], mf: dict,
                                block_size: int, kv_bytes: int = 2) -> int:
    """Bytes ONE layer's attention call of a pass must read: K and V of every
    page in use by the batch's sequences, ONCE (a lane's ``B`` rows are
    folded into one query of the kernel, so its pages are read once a pass
    and not once a row)."""
    pages = sum(-(-t // block_size) for t in context_tokens)
    return pages * block_size * 2 * mf["num_kv_heads"] * mf["head_dim"] * kv_bytes


def forward_flops_per_token(mf: dict, context: int = 0) -> int:
    """Multiply-adds x 2 ONE LANE's pass needs: its ``block_length`` rows,
    each through the attention projections, the router, its ``k`` chosen
    experts and the output matrix, and attention against ``context`` keys.
    ("Per token" in the surface's name is per lane of a step here: a step of
    this model is a block a lane.)"""
    h = mf["hidden_size"]
    sparse = h * mf["num_experts"] + mf["num_experts_per_tok"] * expert_params(mf)
    matmuls = (mf["num_layers"] * (attention_params(mf) + sparse) + h * mf["vocab_size"])
    attn = mf["num_layers"] * 4 * mf["num_heads"] * mf["head_dim"] * context
    return int(mf["block_length"] * (2 * matmuls + attn))
