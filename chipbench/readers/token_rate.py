"""Output tokens delivered inside the window, per second of window, over
every stream of the run that ended (measured or not: a token delivered in
the window is work the system did in it). The run does not end before
every stream that overlaps the window has."""

from chipbench import stats


def read(ctx):
    total = sum(
        stats.tokens_in_window(r.first, r.finished, r.completion_tokens,
                               ctx.t_open, ctx.t_close)
        for r in ctx.records if r.ok)
    return total / (ctx.t_close - ctx.t_open)
