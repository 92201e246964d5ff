"""A toy architecture whose step yields something other than the next token
of a sequence, for ``tests/chipbench/test_chipbench_probe.py``: generation by
unmasking over blocks. Not under ``chipbench/architectures`` (it is no
``model_type`` anyone publishes); the test hands it to ``reference.check`` in
that package's place.

The "model" (:func:`reference_logits`): row ``r``'s logits are a product of
the embeddings of EVERY position of the input, near ones weighing more, so a
row sees both ways. The "program" (:class:`Core`): a block of ``BLOCK``
places starts as mask tokens behind the prompt and the committed blocks; a
denoising step runs the model over that input and commits, at the hidden
place where the model is surest, that place's arg-max, read from its OWN
row. A block is emitted when it is whole. Each token's log-probability entry
says, beside ``top``, the ``block`` it lies in and the ``step`` that chose
it: enough for :func:`score_probe` to rebuild the input of every step and
score each token where the program read it. What the program says is a
claim: :func:`score_probe` checks that it is a legal schedule and the one
the reference itself would have kept (the surest hidden place first), so a
program that unmasks in another order fails although it tells the truth.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

BLOCK, VOCAB, WIDTH = 4, 24, 16
MASK = VOCAB - 1
KEYS: dict = {}                     # no published keys: configs.model_fields maps none


def derived(cfg: dict) -> dict:
    return {}


def make_params(seed: int) -> dict:
    rng = np.random.RandomState(seed)
    return {"embed": rng.standard_normal((VOCAB, WIDTH)).astype(np.float32),
            "head": (3.0 * rng.standard_normal((WIDTH, VOCAB)) / WIDTH ** 0.5).astype(np.float32)}


def reference_logits(params, mf, ids, rows, **options):
    """[len(rows), VOCAB], float32: every row sees the whole input."""
    x = params["embed"][np.asarray(ids)]
    at = np.arange(len(ids))
    weight = 0.6 ** np.abs(at[None, :] - np.asarray(rows)[:, None])
    hidden = np.tanh(weight @ x / weight.sum(-1, keepdims=True) * 3.0)
    return (hidden @ params["head"]).astype(np.float32)


def _log_softmax(logits):
    shifted = logits - logits.max(-1, keepdims=True)
    return shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))


def _legal(tokens, extra) -> bool:
    """The claimed schedule is one a program of this kind can have run: blocks
    in order, and every place of a block chosen at a step of its own."""
    if len(extra) != len(tokens):
        return False
    for first in range(0, len(tokens), BLOCK):
        block = extra[first:first + BLOCK]
        if any(e.get("block") != first // BLOCK for e in block):
            return False
        if sorted(e.get("step", -1) for e in block) != list(range(len(block))):
            return False
    return True


def _step_inputs(prompt, tokens, extra):
    """For each generated token j: the input of the denoising step that is
    CLAIMED to have chosen it, its row there, and the rows still hidden at
    that step, from every token's ``block`` and ``step``."""
    out = []
    for j, e in enumerate(extra):
        first = e["block"] * BLOCK
        last = min(first + BLOCK, len(tokens))
        block = [tokens[i] if extra[i]["step"] < e["step"] else MASK
                 for i in range(first, last)]
        hidden = [len(prompt) + i for i in range(first, last) if extra[i]["step"] >= e["step"]]
        out.append((list(prompt) + list(tokens[:first]) + block, len(prompt) + j, hidden))
    return out


def score_probe(cfg, params, prompt, probe, **options):
    """The optional member of an architecture module
    (``chipbench/architectures``): the reference's side of one probe, each
    token from its own row of its own step's input. ``probe["extra"]`` is
    the program's claim and is checked, not only replayed: an illegal
    schedule is not ``finite``; and where the reference, over a step's input,
    is surest of ANOTHER hidden place than the one claimed, the token's
    ``argmax`` is no token and its ``argmax_lp`` that place's confidence, so
    that ``compare`` holds the two places to its near-tie rule."""
    from chipbench.reference.check import reference_logprobs

    tokens, extra = probe["tokens"], probe["extra"]
    if not _legal(tokens, extra):
        return {"top_lps": [[0.0] * len(t) for t in probe["top_ids"]],
                "argmax": [-1] * len(tokens), "argmax_lp": [0.0] * len(tokens), "finite": False}
    top_lps, argmax, argmax_lp, finite = [], [], [], True
    for (ids, row, hidden), tops in zip(_step_inputs(prompt, tokens, extra),
                                        probe["top_ids"], strict=True):
        lps = reference_logprobs(cfg, params, ids, hidden, **options)
        lp = lps[hidden.index(row)]
        top_lps.append([float(lp[t]) for t in tops])
        surest = hidden[int(lps.max(-1).argmax())]
        argmax.append(int(lp.argmax()) if surest == row else -1)
        argmax_lp.append(float(lps.max()))
        finite &= bool(np.isfinite(lps).all())
    return {"top_lps": top_lps, "argmax": argmax, "argmax_lp": argmax_lp, "finite": finite}


class Core:
    """The program's side: what ``reference.check.run_probe`` drives
    (``add_request``, ``step``, ``params``, ``engine.megastep``). One call of
    ``step`` is one dispatch: a whole block, through its denoising steps."""

    def __init__(self, seed: int, order: str = "surest"):
        self.params = make_params(seed)
        self.order = order      # "left": a faulty program that unmasks left to right
        self.engine = SimpleNamespace(megastep=1)
        self._seq = None

    def add_request(self, request):
        self._seq = SimpleNamespace(
            finish=None, num_cached_tokens=0, prompt=list(request.token_ids), tokens=[],
            max_tokens=request.stop.max_tokens, top=request.output.logprobs)
        return self._seq

    def step(self):
        seq = self._seq
        first = len(seq.tokens)
        size = min(BLOCK, seq.max_tokens - first)       # max_tokens may cut a block
        block, entries = [MASK] * size, [None] * size
        for step in range(size):
            ids = seq.prompt + seq.tokens + block
            rows = [len(seq.prompt) + first + i for i in range(size)]
            lp = _log_softmax(reference_logits(self.params, None, ids, rows))
            hidden = [i for i in range(size) if block[i] == MASK]
            # the surest hidden place (the faulty program: the leftmost)
            i = max(hidden, key=lambda i: lp[i].max()) if self.order == "surest" else hidden[0]
            block[i] = int(lp[i].argmax())
            best = np.argsort(-lp[i])[:seq.top]
            entries[i] = {"token_id": block[i], "logprob": float(lp[i, block[i]]),
                          "top": [[int(t), float(lp[i, t])] for t in best],
                          "block": first // BLOCK, "step": step}
        seq.tokens += block
        if len(seq.tokens) >= seq.max_tokens:
            seq.finish = "length"
        return [(seq, SimpleNamespace(token_ids=block, logprobs=entries))]
