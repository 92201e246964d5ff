"""One run of one cell:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``, starts the store, one worker per
chip and the frontend as child processes, checks the engine against the
plain reference, runs the cell's ramp, measures for ``--seconds``,
drains, checks every measured response, and prints one JSON line last.
Exits non-zero, with no result line, on a harness fault: no TPU (or
fewer chips than the cell asks for), a child that died, a wait that
timed out. This process never imports JAX.

``--manifest`` and ``--allow-cpu`` exist for the CPU rehearsal in
tests/chipbench; the driver passes neither, and without ``--allow-cpu`` a
worker that finds no TPU refuses to start.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

from chipbench import cluster as cluster_mod  # noqa: E402
from chipbench import generators, loadgen, manifest  # noqa: E402
from chipbench.configs import load_config  # noqa: E402
from chipbench.peaks import UnknownDevice  # noqa: E402
from chipbench.procs import HarnessFault, http_json, http_text, wait_for  # noqa: E402
from chipbench.readers import RunContext, read_metric  # noqa: E402
from chipbench.reference import check as ref_check  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "chipbench_out"          # git-ignored: logs, traces, run records
# The probe of ``correct`` where the configuration's file sizes none; ``top``
# is the yardstick's alone (``probe_of``).
PROBE = {"prompt_tokens": 96, "max_tokens": 17, "top": 5}


def say(msg: str) -> None:
    print(f"[chipbench +{time.monotonic() - T_START:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def side_call(worker, kind: str, body: dict, children, timeout: float) -> dict:
    """Ask the worker's side thread for ``kind`` and wait for its answer."""
    done = worker.side / f"{kind}.done"
    done.unlink(missing_ok=True)
    tmp = worker.side / f"{kind}.request.tmp"
    tmp.write_text(json.dumps(body))
    tmp.rename(worker.side / f"{kind}.request")
    wait_for(done.exists, children, timeout, f"{kind}.done from {worker.name}", every=0.05)
    result = json.loads(done.read_text())
    if "error" in result:
        raise HarnessFault(f"{kind} failed in {worker.name}:\n{result['error']}")
    return result


def probe_of(config: dict) -> dict:
    """The probe's sizes: ``PROBE``, under the configuration file's optional
    top-level ``probe`` object ``{"prompt_tokens", "max_tokens"}``. A
    configuration sizes it where the defaults cannot reach what its cells
    name (a window of 512 positions is never left by 96 + 17 tokens)."""
    own = config.get("probe", {})
    if not set(own) <= {"prompt_tokens", "max_tokens"} or not all(
            isinstance(v, int) and v > 0 for v in own.values()):
        raise HarnessFault(f"configuration {config.get('name')!r}: probe {own!r} is not "
                           "{prompt_tokens, max_tokens} of positive whole numbers")
    return {**PROBE, **own}


def reference_check(cl, config: dict, seed: int) -> dict:
    """(a) of ``correct``: the engine's prefill-then-decode through its
    cache against the plain reference, on a seeded probe; see
    chipbench/reference/check.py. The probe's ids stay inside the byte
    range: token ids come from the seed, and any id is as good as another
    to random weights."""
    import random

    probe = probe_of(config)
    rng = random.Random(seed ^ 0x5EED)
    hi = min(config["vocab_size"], 32000)
    ids = [rng.randrange(1, hi) for _ in range(probe["prompt_tokens"])]
    got = side_call(cl.workers[0], "ref", {"prompt_ids": ids, **probe}, cl.children, 900)
    verdict = ref_check.compare(got["served"], got["scored"])
    verdict["repeat_identical"] = got["served"][0]["tokens"] == got["served"][1]["tokens"]
    verdict["second_send_cached_tokens"] = got["served"][1]["cached_tokens"]
    verdict["ok"] = bool(verdict["ok"] and verdict["repeat_identical"])
    verdict["megastep_k"] = got["megastep_k"]
    return verdict


def check_record(rec, overhead: int) -> str | None:
    """(b) of ``correct``: served-path invariants of one measured
    response; the reason it fails, or None."""
    if not rec.ok:
        return rec.error or f"HTTP {rec.status}, finish {rec.finish!r}, done {rec.done}"
    if rec.completion_tokens != rec.req.max_tokens:
        return f"{rec.completion_tokens} tokens for max_tokens {rec.req.max_tokens}"
    if rec.finish != "length":
        return f"finish_reason {rec.finish!r}"
    if rec.prompt_tokens != len(rec.req.prompt) + overhead:
        return f"prompt_tokens {rec.prompt_tokens} for {len(rec.req.prompt)} bytes + {overhead}"
    if rec.cached_tokens > rec.req.shared_tokens + overhead:
        return (f"cached_tokens {rec.cached_tokens} but only "
                f"{rec.req.shared_tokens} were sent before")
    return None


def template_overhead(base_url: str) -> int:
    """Tokens the chat template adds to a prompt's bytes (one cheap
    request; also the first request through the HTTP path)."""
    text = "chipbench probe"
    status, body = http_json(f"{base_url}/v1/chat/completions", {
        "model": cluster_mod.MODEL, "messages": [{"role": "user", "content": text}],
        "max_tokens": 1, "temperature": 0.7, "seed": 1, "dyn": {"ignore_eos": True}})
    if status != 200:
        raise HarnessFault(f"probe request: HTTP {status}: {body}")
    return body["usage"]["prompt_tokens"] - len(text)


def reduce_trace(trace_dir: Path, out: Path) -> dict | None:
    """Child process, on the CPU, after the chip's processes have gone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.trace.reduce", str(trace_dir), str(out)],
        cwd=ROOT, env=env, capture_output=True, timeout=300)
    if proc.returncode != 0 or not out.exists():
        say(f"trace reduction failed: {proc.stderr.decode(errors='replace')[-800:]}")
        return None
    return json.loads(out.read_text())


def run(args) -> dict:
    man = manifest.load(Path(args.manifest) if args.manifest else None)
    cell = manifest.cell(man, args.workload)
    config = load_config(cell["config"])
    traffic = generators.load_traffic(cell["traffic"])
    topology = cluster_mod.load_topology(manifest.topology_of(cell))
    if not (ROOT / "dynamo_tpu").is_dir():
        raise HarnessFault("the system under test (dynamo_tpu/) is not in this directory")
    plan = generators.generate(traffic, args.seed, args.seconds)
    out = OUT / args.workload
    shutil.rmtree(out, ignore_errors=True)
    trace = bool(args.trace)

    cl = cluster_mod.start(topology, cell["config"], args.seed, out, cpu=args.allow_cpu)
    try:
        say(f"serving after {cl.start_to_serving_s:.1f} s")
        health0 = [http_json(w.health_url)[1] for w in cl.workers]
        device = dict(health0[0]["device"])
        device["count"] = sum(h["device"]["count"] for h in health0)
        if not args.allow_cpu and (device["platform"] != "tpu" or device["count"] != cell["chips"]):
            raise HarnessFault(f"cell asks for {cell['chips']} TPU chip(s); found {device}")

        t0 = time.monotonic()
        verdict = reference_check(cl, config, args.seed)
        correct_check_s = time.monotonic() - t0
        say(f"reference check {verdict} in {correct_check_s:.1f} s")
        overhead = template_overhead(cl.base_url)

        health_open: list = []
        scrape_open: dict = {}
        scrape_close: dict = {}
        trace_seconds = min(4.0, args.seconds / 3)

        def scrape() -> dict:
            return {"worker": [http_text(w.metrics_url) for w in cl.workers],
                    "frontend": [http_text(cl.frontend_metrics_url),
                                 http_text(f"{cl.base_url}/metrics")]}

        async def at_open() -> None:
            health_open.extend(await asyncio.to_thread(
                lambda: [http_json(w.health_url)[1] for w in cl.workers]))
            if trace:
                scrape_open.update(await asyncio.to_thread(scrape))

        async def at_mid() -> None:
            if trace:
                req = cl.workers[0].side / "trace.request"
                req.with_suffix(".tmp").write_text(json.dumps({"seconds": trace_seconds}))
                req.with_suffix(".tmp").rename(req)

        async def at_close() -> None:
            # An untraced run asks the worker nothing at the close: the
            # streams in flight there are the ones the rate is read from.
            if trace:
                scrape_close.update(await asyncio.to_thread(scrape))

        say(f"ramp {plan.ramp_seconds:.0f} s, then window {args.seconds} s "
            f"({plan.loop} loop, {len(plan.all_requests())} requests prepared)")
        load = asyncio.run(loadgen.run_plan(
            plan, cl.base_url, cluster_mod.MODEL, args.seconds,
            drain_timeout=args.drain_timeout, at_open=at_open,
            at_mid=at_mid, at_close=at_close,
            mid_offset=-trace_seconds / 2))
        setup_s = load.t_open - T_START
        cl.children.check_alive()
        trace_info = None
        if trace:
            done = cl.workers[0].side / "trace.done"
            wait_for(done.exists, cl.children, 120, "trace.done", every=0.1)
            trace_info = json.loads(done.read_text())
            if "error" in trace_info:
                raise HarnessFault(f"profiler failed:\n{trace_info['error']}")
        health_end = [http_json(w.health_url)[1] for w in cl.workers]
        frontend_log = (out / "frontend.log").read_text(errors="replace")
    finally:
        cl.stop()

    # Judged: open loop, the requests due inside the window; closed loop,
    # those sent inside it. The run waited for them to end.
    if plan.loop == "open":
        measured = [r for r in load.records if 0 <= r.req.due < args.seconds]
    else:
        measured = [r for r in load.records if load.t_open <= r.due_abs < load.t_close]
    reasons = [(r, check_record(r, overhead)) for r in measured]
    failed = [why for _, why in reasons if why]
    for why in failed[:5]:
        say(f"failed request: {why}")
    compiled = _compiled_in_window(health_open, health_end)
    if compiled:
        say(f"compiled inside the window: {compiled}")
    replayed = "migrating request" in frontend_log
    correct = bool(verdict["ok"] and not failed and not compiled and not replayed
                   and measured)

    ctx = RunContext(
        cell=cell, config=config, traffic=traffic, seconds=args.seconds,
        t_open=load.t_open, t_close=load.t_close,
        unix_minus_monotonic=time.time() - time.monotonic(),
        measured=measured, records=load.records,
        harness={"setup_s": setup_s, "correct_check_s": correct_check_s,
                 "start_to_serving_s": cl.start_to_serving_s,
                 "reference_max_abs_diff": verdict["max_abs_diff"],
                 "megastep_k": verdict["megastep_k"]},
        health_open=health_open, health_close=health_end,
        scrape_open=scrape_open, scrape_close=scrape_close,
        device_kind=device["kind"])
    if trace_info:
        ctx.harness["trace_started_unix"] = trace_info["started_unix"]
        ctx.harness["trace_seconds"] = trace_info["stopped_unix"] - trace_info["started_unix"]
        ctx.trace = reduce_trace(cl.workers[0].side / "trace", out / "trace_summary.json")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_of(man, kind, cell["name"]):
        try:
            value = read_metric(kind, m["name"], ctx)
        except UnknownDevice:
            if not args.allow_cpu:
                raise
            value = None   # a rehearsal's CPU has no peaks: no roofline share
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    peaks_b = [m.get("peak_bytes_in_use") for h in health_end for m in h.get("memory", [])]
    device["memory_peak_bytes"] = max([b for b in peaks_b if b] or [0])
    result = {"correct": correct, "attempted": len(measured),
              "failed": len(failed), "metrics": metrics,
              "device": device}
    if ctx.trace and ctx.trace.get("devices"):
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {
            "device_ops": [[k, s] for k, s, _ in ctx.trace["ops"][:10]],
            "idle_gaps": [[k, s] for k, s in ctx.trace["gaps"][:10]]}
    (out / "run.json").write_text(json.dumps({
        "args": vars(args), "result": result, "reference": verdict,
        "harness": ctx.harness, "compiled_in_window": compiled,
        "requests": {"prepared": len(plan.all_requests()), "sent": len(load.records),
                     "measured": len(measured), "unfinished": load.unfinished},
        "measured_ttft_ms": [round((r.first - r.due_abs) * 1e3, 3) for r in measured if r.ok],
        "measured_tokens": [r.completion_tokens for r in measured if r.ok],
        "measured_seconds": [round(r.finished - r.first, 4) for r in measured if r.ok],
        # [due, sent, first token, last token, tokens] of every stream of
        # the run, in seconds from the window's open: where in time a slow
        # stretch lay, and whether the generator stood still through it
        "streams": [[round(t - load.t_open, 3) for t in (r.due_abs, r.sent, r.first, r.finished)]
                    + [r.completion_tokens] for r in load.records if r.ok],
        "startup": [h.get("startup") for h in health_end],
        "compile": [h.get("compile") for h in health_end]}, indent=1, default=str))
    return result


def _compiled_in_window(health_open: list, health_end: list) -> list:
    """Programs (those CompileLog lists: half a second or more) that a
    worker compiled between the window's open and the end of the drain."""
    out = []
    for a, b in zip(health_open, health_end):
        before = len((a.get("compile") or {}).get("programs", []))
        out += (b.get("compile") or {}).get("programs", [])[before:]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--drain-timeout", type=float, default=60.0)
    args = ap.parse_args()
    try:
        result = run(args)
    except HarnessFault as e:
        say(f"HARNESS FAULT: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
