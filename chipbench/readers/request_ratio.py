"""Mean time per output token over all the measured requests' tokens:
the sum of (last - first token time) over the sum of (tokens - 1), in ms.
Every token weighs the same, so it is a time per step taken over all the
decode work of the measured requests, and steadier than a median over
requests."""


def read(ctx):
    done = [r for r in ctx.measured if r.ok and (r.completion_tokens or 0) > 1]
    tokens = sum(r.completion_tokens - 1 for r in done)
    return 1000.0 * sum(r.finished - r.first for r in done) / tokens if tokens else None
