"""Percentile over the measured requests of a per-request quantity:
``ttft_ms`` (due time to first token), ``tpot_ms`` ((last - first token
time) / (tokens - 1)) or ``late_ms`` (how late the generator sent it).
A failed request has no latency and is counted in ``failed`` instead."""

from chipbench import stats


def per_request(ctx, quantity: str) -> list[float]:
    out = []
    for r in ctx.measured:
        if quantity == "late_ms":
            out.append((r.sent - r.due_abs) * 1000.0)
        elif not r.ok:
            continue
        elif quantity == "ttft_ms":
            out.append((r.first - r.due_abs) * 1000.0)
        elif quantity == "tpot_ms":
            v = stats.tpot_ms(r.first, r.finished, r.completion_tokens or 0)
            if v is not None:
                out.append(v)
        else:
            raise ValueError(f"unknown per-request quantity {quantity!r}")
    return out


def read(ctx, quantity: str, q: float):
    return stats.percentile(per_request(ctx, quantity), q)
