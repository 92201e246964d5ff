"""Parse Prometheus text exposition, as far as the program's endpoints
use it: ``name{label="v",...} value`` lines."""

from __future__ import annotations

import re

_LINE = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> list[tuple[str, dict, float]]:
    out = []
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line.strip())
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")), value))
    return out


def total(texts: list[str], name: str, labels: dict | None = None) -> float | None:
    """Sum over the endpoints' texts of the series ``name`` whose labels
    include ``labels``; None when no such series exists."""
    found, acc = False, 0.0
    for text in texts:
        for n, lab, v in parse(text):
            if n == name and all(lab.get(k) == w for k, w in (labels or {}).items()):
                found, acc = True, acc + v
    return acc if found else None


def delta(ctx, endpoint: str, series: dict) -> float | None:
    """Close-of-window minus open-of-window total of one series spec
    ``{"name", "labels"}``."""
    before = total(ctx.scrape_open.get(endpoint, []), series["name"], series.get("labels"))
    after = total(ctx.scrape_close.get(endpoint, []), series["name"], series.get("labels"))
    if before is None or after is None:
        return None
    return after - before
