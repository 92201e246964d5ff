"""Open loop of independent requests at a fixed rate (chat).

Parameters: ``rate`` (requests per second), ``gaps`` (distribution of
inter-arrival gaps with mean 1: ``exponential`` for Poisson arrivals),
``prompt_tokens``, ``output_tokens``, ``output_quantum``,
``ramp_seconds``.

The window's ``N = round(rate * seconds)`` requests take the N quantiles
of each distribution, permuted by the seed; the gaps are scaled so that
they sum to the window exactly, so every seed offers N requests inside
the window with the same multiset of gaps, prompt and output lengths.
The ramp is drawn the same way, apart, at the same rate.

What a seed changes is the order: which long prompts arrive together
and beside which long answers. On the v5e that is most of what differs
between runs of the chat cell (PERF.md, Findings, PR 23: the same seed
repeats within ~1.5% in token-weighted TPOT, seeds lie up to 8% apart).
The order is not frozen: a run is one draw of it, a set of runs with
different seeds samples it, and a metric's bound has to hold that.

``output_quantum`` q makes every output length ``q*m + 1``: the engine's
megastep emits q tokens per dispatch after the prefill's one, and a
batch whose lanes all have fewer than q tokens left runs a shortened
program that the worker's warm-up does not compile. With lengths of this
form no lane ever has a remainder, so nothing compiles inside the window;
what that hides is listed in PERF.md ("cannot see")."""

from __future__ import annotations

import random

from chipbench.generators.common import Plan, Request, draw, draw_ints, text_of


def arrivals(gaps_spec: dict, n: int, span: float, rng: random.Random) -> list[float]:
    """``n`` arrival times in ``[0, span)``: permuted quantile gaps with
    mean 1, scaled to sum to ``span``; the first arrival falls half a gap
    in, so the last is half a gap before the end."""
    if n == 0:
        return []
    gaps = draw(gaps_spec, n, rng)
    scale = span / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        out.append(t + 0.5 * g * scale)
        t += g * scale
    return out


def generate(traffic: dict, seed: int, seconds: float) -> Plan:
    rng = random.Random(seed)
    rate, ramp = float(traffic["rate"]), float(traffic["ramp_seconds"])
    q = int(traffic.get("output_quantum", 1))
    plan = Plan("open", ramp, temperature=float(traffic.get("temperature", 0.7)))
    for start, span in ((-ramp, ramp), (0.0, seconds)):
        n = round(rate * span)
        due = arrivals(traffic["gaps"], n, span, rng)
        prompts = draw_ints(traffic["prompt_tokens"], n, rng)
        outputs = draw_ints(traffic["output_tokens"], n, rng, quantum=q,
                            plus=1 if q > 1 else 0)
        for t, p, o in zip(due, prompts, outputs, strict=True):
            plan.requests.append(Request(
                text_of(p, rng), o, rng.getrandbits(31), due=start + t))
    plan.requests.sort(key=lambda r: r.due)
    return plan
