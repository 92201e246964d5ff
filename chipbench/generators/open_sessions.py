"""Open loop of sessions: each session is one document asked several
questions (document QA, RAG).

Parameters: ``session_rate`` (sessions per second), ``gaps`` (as in
``open_requests``), ``document_tokens``, ``turns``, ``question_tokens``,
``think_seconds`` (gap from one question to the next, fixed in advance:
the load does not slow down when the system does), ``output_tokens``,
``output_quantum``, ``ramp_seconds`` (about one session lifetime, so
that the window opens with the cache in steady state).

Every quantity takes its quantiles, permuted by the seed, as in
``open_requests``. Sessions start through ramp and window alike; turns
that fall due after the window are not sent, so which late turns are cut
depends on the seed's think times, and the number of requests inside the
window with it (by a few per cent at a hundred requests).

Turn j's prompt is the document followed by question j, so the document
is the shared prefix: the first turn of a session misses the prefix
cache and the later ones hit it, unless the document was evicted or its
prefill has not finished. Expected share of prompt tokens that are
shared: see :func:`expected_shared_share`."""

from __future__ import annotations

import random

from chipbench.generators.common import Plan, Request, draw, draw_ints, text_of
from chipbench.generators.open_requests import arrivals


def generate(traffic: dict, seed: int, seconds: float) -> Plan:
    rng = random.Random(seed)
    rate, ramp = float(traffic["session_rate"]), float(traffic["ramp_seconds"])
    turns = int(traffic["turns"])
    q = int(traffic.get("output_quantum", 1))
    plan = Plan("open", ramp, temperature=float(traffic.get("temperature", 0.7)))
    session = 0
    for start, span in ((-ramp, ramp), (0.0, seconds)):
        n = round(rate * span)
        starts = arrivals(traffic["gaps"], n, span, rng)
        thinks = draw(traffic["think_seconds"], n * turns, rng)
        docs = draw_ints(traffic["document_tokens"], n, rng)
        questions = draw_ints(traffic["question_tokens"], n * turns, rng)
        outputs = draw_ints(traffic["output_tokens"], n * turns, rng, quantum=q,
                            plus=1 if q > 1 else 0)
        for s in range(n):
            doc = text_of(docs[s], rng)
            t = start + starts[s]
            for j in range(turns):
                i = s * turns + j
                if j:
                    t += thinks[i]
                if t >= seconds:
                    break
                plan.requests.append(Request(
                    doc + " " + text_of(questions[i], rng), outputs[i],
                    rng.getrandbits(31), shared_tokens=len(doc) if j else 0,
                    due=t, session=session))
            session += 1
    plan.requests.sort(key=lambda r: r.due)
    return plan


def expected_shared_share(plan: Plan) -> float:
    """Share of the prompt tokens of ``plan`` that the generator knows
    were sent before (an upper bound on the prefix-hit share)."""
    total = sum(len(r.prompt) for r in plan.requests)
    return sum(r.shared_tokens for r in plan.requests) / total if total else 0.0
