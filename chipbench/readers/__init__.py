"""Readers: each metric is a small JSON file naming a reader module here
and its arguments. ``read(ctx, **args)`` returns the value, or None when
there is nothing to read (the harness then leaves the metric out)."""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from typing import Any

from chipbench import manifest


@dataclass
class RunContext:
    """Everything one run recorded, for the readers."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float
    t_open: float                      # monotonic
    t_close: float
    unix_minus_monotonic: float        # to place the worker's trace on our clock
    measured: list = field(default_factory=list)      # loadgen.Record, judged
    records: list = field(default_factory=list)       # every Record of the run
    harness: dict = field(default_factory=dict)       # scalars the harness took itself
    health_open: list = field(default_factory=list)   # per worker /health at window open
    health_close: list = field(default_factory=list)
    scrape_open: dict = field(default_factory=dict)   # {"worker": [text], "frontend": [text]}
    scrape_close: dict = field(default_factory=dict)
    trace: dict | None = None          # chipbench.trace.reduce summary
    device_kind: str = ""


def read_metric(kind: str, name: str, ctx: RunContext) -> Any:
    spec = json.loads(manifest.metric_file(kind, name).read_text())
    module = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    return module.read(ctx, **spec.get("args", {}))
