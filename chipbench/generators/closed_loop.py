"""Closed loop: ``clients`` callers, each sending its next request when
the previous one completed (batch and offline generation).

Parameters: ``clients``, ``prompt_tokens`` and ``output_tokens``
(distributions), ``pool_per_client`` (requests prepared per client: more
than a window can use), ``output_quantum`` (see chat: output lengths are
``q*m + 1``), ``ramp_seconds``. Each client's first request has its
output cut to the fraction ``(i + 0.5) / clients`` of its length, client
order permuted by the seed, so that completions are spread evenly and the
window opens in steady state instead of with all clients in step."""

from __future__ import annotations

import random

from chipbench.generators.common import Plan, Request, draw, draw_ints, text_of


def generate(traffic: dict, seed: int, seconds: float) -> Plan:
    rng = random.Random(seed)
    clients, pool = int(traffic["clients"]), int(traffic["pool_per_client"])
    n = clients * pool
    q = int(traffic.get("output_quantum", 1))
    prompts = draw_ints(traffic["prompt_tokens"], n, rng)
    outputs = draw_ints(traffic["output_tokens"], n, rng, quantum=q, plus=1 if q > 1 else 0)
    stagger = draw({"dist": "uniform", "lo": 0.0, "hi": 1.0}, clients, rng)
    plan = Plan("closed", float(traffic["ramp_seconds"]),
                temperature=float(traffic.get("temperature", 0.7)))
    for c in range(clients):
        reqs = []
        for j in range(pool):
            i = c * pool + j
            out = outputs[i]
            if j == 0:
                out = max(1, round(out * stagger[c] / q)) * q + (1 if q > 1 else 0)
            reqs.append(Request(text_of(prompts[i], rng), out, rng.getrandbits(31)))
        plan.clients.append(reqs)
    return plan
