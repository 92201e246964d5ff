"""Share (%) of the measured requests that met every limit given; a
failed request meets none."""

from chipbench import stats


def read(ctx, ttft_ms_max: float | None = None, tpot_ms_max: float | None = None):
    if not ctx.measured:
        return None
    good = 0
    for r in ctx.measured:
        if not r.ok:
            continue
        ttft = (r.first - r.due_abs) * 1000.0
        tpot = stats.tpot_ms(r.first, r.finished, r.completion_tokens or 0)
        if ttft_ms_max is not None and ttft > ttft_ms_max:
            continue
        if tpot_ms_max is not None and tpot is not None and tpot > tpot_ms_max:
            continue
        good += 1
    return 100.0 * good / len(ctx.measured)
