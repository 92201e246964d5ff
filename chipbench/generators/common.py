"""What every generator shares: low-discrepancy draws, prompt text, the
request record and the plan a generator returns.

**Low-discrepancy.** For ``n`` requests a quantity is not sampled ``n``
times: it takes the ``n`` evenly spaced quantiles ``(i + 0.5) / n`` of its
distribution, and the seed only permutes them. Every seed then offers
the same number of requests with the same multiset of lengths and gaps,
in another order, so two runs differ in order and not in amount of work.
(The arithmetic of lengths and sessions follows
``benchmarks/prefix_synthesizer.py`` and ``benchmarks/sin_load.py``, which
only ever drove the mocker; see PERF.md, Open questions.)
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass, field


def quantiles(spec: dict, n: int) -> list[float]:
    """The ``n`` mid-point quantiles of the distribution ``spec``:
    ``{"dist": "uniform"|"loguniform", "lo", "hi"}``,
    ``{"dist": "exponential", "mean"}`` or ``{"dist": "const", "value"}``;
    an optional ``"shift"`` is added to every value."""
    us = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "const":
        out = [float(spec["value"])] * n
    elif dist == "uniform":
        out = [spec["lo"] + u * (spec["hi"] - spec["lo"]) for u in us]
    elif dist == "loguniform":
        a, b = math.log(spec["lo"]), math.log(spec["hi"])
        out = [math.exp(a + u * (b - a)) for u in us]
    elif dist == "exponential":
        out = [-spec["mean"] * math.log(1.0 - u) for u in us]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    shift = float(spec.get("shift", 0.0))
    return [x + shift for x in out]


def draw(spec: dict, n: int, rng: random.Random) -> list[float]:
    """The quantiles of ``spec``, permuted by ``rng``."""
    out = quantiles(spec, n)
    rng.shuffle(out)
    return out


def draw_ints(spec: dict, n: int, rng: random.Random, *, quantum: int = 1,
              plus: int = 0) -> list[int]:
    """Integer draws; with ``quantum`` q each is rounded to ``q*m + plus``
    (at least ``q + plus``)."""
    return [max(1, round((x - plus) / quantum)) * quantum + plus
            for x in draw(spec, n, rng)]


_LETTERS = "abcdefghijklmnopqrstuvwxyz"


def text_of(n_bytes: int, rng: random.Random) -> str:
    """``n_bytes`` of lower-case words: one byte is one token of the byte
    tokenizer the presets are served with. Two texts from different rng
    states share no prefix worth a cache block."""
    words, size = [], 0
    while size <= n_bytes:   # the join drops one separator
        w = "".join(rng.choices(_LETTERS, k=rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return " ".join(words)[:n_bytes]


@dataclass
class Request:
    prompt: str
    max_tokens: int
    seed: int
    # Tokens of the prompt that an earlier request of this run also began
    # with (the generator's knowledge; the server may not report more as
    # cached).
    shared_tokens: int = 0
    # Open loop: seconds from window open at which the request is due
    # (negative: ramp). Closed loop: None.
    due: float | None = None
    session: int | None = None


@dataclass
class Plan:
    """What a generator hands the load generator.

    ``loop`` "open": ``requests`` sorted by ``due``; those with
    ``0 <= due < seconds`` are the measured ones. ``loop`` "closed":
    ``clients[i]`` is client i's list of requests, sent one after the
    other from ``-ramp_seconds`` on; a request completed inside the
    window is a measured one."""

    loop: str
    ramp_seconds: float
    requests: list[Request] = field(default_factory=list)
    clients: list[list[Request]] = field(default_factory=list)
    temperature: float = 0.7

    def all_requests(self) -> list[Request]:
        return self.requests + [r for c in self.clients for r in c]
