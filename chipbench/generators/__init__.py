"""Traffic generators. A traffic mix is a data file
(``chipbench/traffic/<name>.json``) whose ``kind`` names a module here;
:func:`generate` is the one entry the harness calls."""

from __future__ import annotations

import importlib
import json
from pathlib import Path

from chipbench.generators.common import Plan

TRAFFIC_DIR = Path(__file__).resolve().parent.parent / "traffic"


def load_traffic(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def generate(traffic: dict, seed: int, seconds: float) -> Plan:
    module = importlib.import_module(f"chipbench.generators.{traffic['kind']}")
    return module.generate(traffic, seed, seconds)
