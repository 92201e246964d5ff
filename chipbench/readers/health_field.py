"""A number from the workers' ``/health`` at the window's close: ``path``
leads to it (list indices allowed); with ``over`` the value is
``scale * path / over``. The largest over the workers is reported."""


def _dig(doc, path):
    for key in path:
        if isinstance(doc, list):
            doc = doc[int(key)] if int(key) < len(doc) else None
        elif isinstance(doc, dict):
            doc = doc.get(key)
        else:
            return None
        if doc is None:
            return None
    return doc


def read(ctx, path: list, over: list | None = None, scale: float = 1.0):
    values = []
    for health in ctx.health_close:
        v = _dig(health, path)
        if over is not None:
            d = _dig(health, over)
            v = None if v is None or not d else v / d
        if v is not None:
            values.append(scale * v)
    return max(values) if values else None
