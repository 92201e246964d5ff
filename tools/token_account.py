"""A token's account from two ``/metrics`` scrapes of a JAX worker.

The engine's step clock keeps, always on, the device's seconds by kind of
dispatch, the seconds the device had nothing queued (a lower and an upper
bound, by the phase they lay under and the kind of dispatch before them)
and the lane-seconds decode-ready lanes spent decoding, behind a prefill
wave and behind the host (``dynamo_tpu/tracing/stepclock.py``). Between
two scrapes this prints what a token cost, by cause:

    python -m tools.token_account open.txt close.txt [--json]
    python -m tools.token_account --url http://127.0.0.1:PORT/metrics --seconds 45

(the port: ``DYN_SYSTEM_PORT``, or the worker's "status server on" log line).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from pathlib import Path

from chipbench.readers import prometheus

P = "dynamo_engine_"


def account(before: str, after: str) -> dict:
    """The account over the interval between two scrape texts."""
    a = {(n, tuple(sorted(lab.items()))): v for n, lab, v in prometheus.parse(before)}
    series = [(n, lab, v - a.get((n, tuple(sorted(lab.items()))), 0.0))
              for n, lab, v in prometheus.parse(after) if n.startswith(P)]

    def total(name: str, **labels: str) -> float:
        return sum(v for n, lab, v in series if n == P + name
                   and all(lab.get(k) == w for k, w in labels.items()))

    def by(name: str, *keys: str, **labels: str) -> dict:
        out: dict = {}
        for n, lab, v in series:
            if n == P + name and v and all(lab.get(k) == w for k, w in labels.items()):
                key = "/".join(lab[k] for k in keys)
                out[key] = out.get(key, 0.0) + v
        return out

    tokens = total("decode_tokens_committed_total")
    elapsed = total("step_phase_seconds_total")
    late = total("late_landings_total")
    lower, upper = (total("device_starved_seconds_total", bound=b) for b in ("lower", "upper"))
    per_token = {state: 1e3 * total("lane_seconds_total", state=state) / tokens if tokens else None
                 for state in ("decode", "behind_prefill", "behind_host")}
    dispatches = total("dispatches_total")
    return {
        "elapsed_s": elapsed, "no_work_s": total("step_phase_seconds_total", phase="no_work"),
        "decode_tokens": tokens, "dispatches": dispatches,
        "decode_ms_per_token": per_token["decode"],
        "prefill_stall_ms_per_token": per_token["behind_prefill"],
        "host_stall_ms_per_token": per_token["behind_host"],
        "sum_ms_per_token": sum(per_token.values()) if tokens else None,
        "device_starved_share_lower": 100 * lower / elapsed if elapsed else None,
        "device_starved_share_upper": 100 * upper / elapsed if elapsed else None,
        "device_seconds": by("device_seconds_total", "kind"),
        "late_landings": by("late_landings_total", "kind"),
        "bound_width_ms_per_late_landing": 1e3 * (upper - lower) / late if late else 0.0,
        "starved_upper_s": by("device_starved_seconds_total", "phase", "after", bound="upper"),
        "starved_lower_s": by("device_starved_seconds_total", "phase", "after", bound="lower"),
        "starved_upper_by_after_s": by("device_starved_seconds_total", "after", bound="upper"),
        "starved_lower_by_after_s": by("device_starved_seconds_total", "after", bound="lower"),
        "host_ms_per_dispatch": (1e3 * total("step_phase_seconds_total", blocks="host")
                                 / dispatches if dispatches else None),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("scrapes", nargs="*", help="two saved scrape texts: open, close")
    ap.add_argument("--url", help="a worker's /metrics, scraped now and after --seconds")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.url:
        def scrape() -> str:
            return urllib.request.urlopen(args.url, timeout=10).read().decode()

        before = scrape()
        time.sleep(args.seconds)
        after = scrape()
    elif len(args.scrapes) == 2:
        before, after = (Path(p).read_text() for p in args.scrapes)
    else:
        ap.error("give two scrape files, or --url")
    acc = account(before, after)
    if args.json:
        print(json.dumps(acc))
        return 0
    if not acc["decode_tokens"]:
        print("no decode token was committed between the scrapes (or the worker keeps no account)")
        return 1
    print(f"{acc['elapsed_s']:.2f} s ({acc['no_work_s']:.2f} s without work), "
          f"{acc['decode_tokens']:.0f} decode tokens, {acc['dispatches']:.0f} dispatches, "
          f"host {acc['host_ms_per_dispatch']:.2f} ms a dispatch")
    print(f"a token: decode {acc['decode_ms_per_token']:.3f} + behind a wave "
          f"{acc['prefill_stall_ms_per_token']:.3f} + behind the host "
          f"{acc['host_stall_ms_per_token']:.3f} = {acc['sum_ms_per_token']:.3f} ms")
    print(f"device starved {acc['device_starved_share_lower']:.3f}% (lower) .. "
          f"{acc['device_starved_share_upper']:.3f}% (upper) of the time; "
          f"late landings {acc['late_landings']}, bounds "
          f"{acc['bound_width_ms_per_late_landing']:.3f} ms apart a late landing")
    print("device seconds by kind:", {k: round(v, 3) for k, v in acc["device_seconds"].items()})
    for bound in ("upper", "lower"):
        rows = sorted(acc[f"starved_{bound}_s"].items(), key=lambda kv: -kv[1])
        print(f"starved seconds ({bound}) by phase/after:",
              {k: round(v, 4) for k, v in rows})
    return 0


if __name__ == "__main__":
    sys.exit(main())
