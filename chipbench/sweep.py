"""Find an open-loop cell's knee once, when the cell is defined:

    python3 -m chipbench.sweep --workload <cell> --rates 6,8,10,12 --seconds 20

Starts the cell's processes once and offers the cell's traffic at each
rate in turn (its own ramp, then ``--seconds``), all in one process. Per
rate it prints what was offered and what ended inside the step, the
latencies, and how deep the worker's waiting queue was at the step's
middle and end. The knee is the highest rate at which completions keep
up with arrivals and the queue at the end is no deeper than at the
middle; the cell's traffic file then fixes its rate at about three
quarters of it. A benchmark run never searches for a rate."""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

from chipbench import cluster as cluster_mod
from chipbench import generators, loadgen, manifest, stats
from chipbench.procs import HarnessFault, http_text
from chipbench.readers import prometheus
from chipbench.run import OUT, say


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, in the traffic's own unit")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    man = manifest.load(Path(args.manifest) if args.manifest else None)
    cell = manifest.cell(man, args.workload)
    traffic = generators.load_traffic(cell["traffic"])
    key = "rate" if "rate" in traffic else "session_rate"
    out = OUT / f"sweep-{args.workload}"
    rows = []
    try:
        cl = cluster_mod.start(cluster_mod.load_topology(manifest.topology_of(cell)),
                               cell["config"], args.seed, out, cpu=args.allow_cpu)
    except HarnessFault as e:
        say(f"HARNESS FAULT: {e}")
        return 1
    try:
        say(f"serving after {cl.start_to_serving_s:.1f} s")
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            plan = generators.generate(dict(traffic, **{key: rate}), args.seed + i, args.seconds)
            depth = {}

            def sample(when):
                async def hook():
                    texts = await asyncio.to_thread(
                        lambda: [http_text(w.metrics_url) for w in cl.workers])
                    depth[when] = (prometheus.total(texts, "dynamo_scheduler_waiting_seqs"),
                                   prometheus.total(texts, "dynamo_scheduler_running_seqs"))
                return hook

            load = asyncio.run(loadgen.run_plan(
                plan, cl.base_url, cluster_mod.MODEL, args.seconds, drain_timeout=60.0,
                at_mid=sample("mid"), at_close=sample("end")))
            due = [r for r in load.records if 0 <= r.req.due < args.seconds]
            ok = [r for r in due if r.ok]
            ttft = [(r.first - r.due_abs) * 1e3 for r in ok]
            tpot = [v for r in ok for v in [stats.tpot_ms(r.first, r.finished, r.completion_tokens)] if v]
            row = {
                key: rate, "offered": len(due), "ok": len(ok),
                "ended_inside": sum(r.finished < load.t_close for r in ok),
                "ttft_ms_p50": stats.percentile(ttft, 50), "ttft_ms_p90": stats.percentile(ttft, 90),
                "tpot_ms_p50": stats.percentile(tpot, 50), "tpot_ms_p90": stats.percentile(tpot, 90),
                "late_ms_p99": stats.percentile([(r.sent - r.due_abs) * 1e3 for r in due], 99),
                "waiting_mid_end": [depth.get("mid", (None,))[0], depth.get("end", (None,))[0]],
                "running_mid_end": [depth.get("mid", (None, None))[1], depth.get("end", (None, None))[1]],
                "drain_s": time.monotonic() - load.t_close,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
            cl.children.check_alive()
    finally:
        cl.stop()
    (out / "sweep.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
