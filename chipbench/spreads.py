"""Spreads of a file of result lines, as the builder's instructions
define them: per metric the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 -m chipbench.spreads runs.jsonl [runs2.jsonl ...]

Each file is one set of runs of one cell (one result line per run, as
``chipbench.run`` prints them, optionally prefixed by anything up to the
first ``{``). Prints per file the median and spread of every metric, and
last the wider spread per metric over the files."""

from __future__ import annotations

import json
import statistics
import sys

from chipbench import stats


def read_lines(path: str) -> list[dict]:
    out = []
    for line in open(path):
        at = line.find('{"correct"')
        if at >= 0:
            out.append(json.loads(line[at:]))
    return out


def main(paths: list[str]) -> int:
    widest: dict[str, float] = {}
    for path in paths:
        runs = read_lines(path)
        names = sorted({n for r in runs for n in r["metrics"]})
        bad = [i for i, r in enumerate(runs) if not r["correct"] or r["failed"]]
        print(f"{path}: {len(runs)} runs, attempted "
              f"{[r['attempted'] for r in runs]}, not correct or failed: {bad}")
        for n in names:
            vals = [r["metrics"][n]["value"] for r in runs if n in r["metrics"]]
            if len(vals) < 2:
                continue
            sp = stats.spread(vals)
            widest[n] = max(widest.get(n, 0.0), sp)
            print(f"  {n:28s} median {statistics.median(vals):12.4f}  spread {sp:.4f}  "
                  f"min {min(vals):.4f} max {max(vals):.4f}")
    print("widest spread per metric:", json.dumps({k: round(v, 4) for k, v in widest.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
