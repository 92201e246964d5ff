"""Client-visible tokens committed per device dispatch inside the window.

The engine exports its dispatch counters and the cumulative ratio
``engine_dispatches_per_token`` but not the token count itself; tokens =
dispatches / ratio at each scrape, and the window's figure is the ratio
of the two differences."""

from chipbench.readers import prometheus

_DISPATCHES = ("dynamo_scheduler_megastep_dispatches_total",
               "dynamo_scheduler_single_step_dispatches_total")
_RATIO = "dynamo_engine_dispatches_per_token"


def _at(texts: list[str]):
    dispatches = tokens = 0.0
    for text in texts:  # one worker each: the ratio is per worker
        d = sum(prometheus.total([text], n) or 0.0 for n in _DISPATCHES)
        ratio = prometheus.total([text], _RATIO)
        if not ratio:
            return None
        dispatches += d
        tokens += d / ratio
    return dispatches, tokens


def read(ctx):
    a = _at(ctx.scrape_open.get("worker", []))
    b = _at(ctx.scrape_close.get("worker", []))
    if not a or not b or b[0] <= a[0]:
        return None
    return (b[1] - a[1]) / (b[0] - a[0])
