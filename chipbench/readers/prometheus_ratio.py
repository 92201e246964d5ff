"""``scale * d(numerator) / d(denominator)`` of counters (or histogram
``_sum`` / ``_count`` series) scraped at the window's open and close and
summed over the endpoints of one kind (``worker`` or ``frontend``)."""

from chipbench.readers import prometheus


def read(ctx, endpoint: str, numerator: dict, denominator: dict, scale: float = 1.0):
    num = prometheus.delta(ctx, endpoint, numerator)
    den = prometheus.delta(ctx, endpoint, denominator)
    if num is None or not den:
        return None
    return scale * num / den
