"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: qwen2-7b, int8 weights
    python chip_smoke.py --cpu-tiny   # rehearsal on the CPU, `tiny` preset
    python chip_smoke.py --cpu-tiny --cpu-preset tiny-lfm2   # the same, a hybrid model
    python chip_smoke.py --cpu-tiny --cpu-preset tiny-laguna # the same, window + full layers
    python chip_smoke.py --cpu-tiny --cpu-preset tiny-sdar   # the same, generation by blocks
    python chip_smoke.py --cpu-tiny --cpu-preset tiny-mimo   # the same, the wide-key page
    python chip_smoke.py --cpu-tiny --cpu-preset tiny-olmo-hybrid  # the same, a slab a lane

Starts the three processes a user starts (README "Run it"): the control-
plane store, the JAX worker and the OpenAI frontend with the KV router.
Sends HTTP traffic (one streaming chat completion, eight concurrent ones
with prompts of ~300 to ~2,000 byte-tokens, one prompt twice), checks what
came back, asks the worker what it ran on, stops the children so the chip
is free, and then compiles both Pallas attention kernels against their
references in a fresh child. Any failed phase makes the exit code 1 and
suppresses the result line. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

This process never imports JAX: a parent that has touched JAX holds the
chip, and the worker it starts would then fail or hang. Without
``--cpu-tiny`` a missing TPU is a failure, not a fallback. Text is not
checked: random weights over a 152k vocabulary produce ids the byte
tokenizer drops.

Builder's four-chip runs (not part of the driver's check):

    python chip_smoke.py --tp4   # one worker, --tp 4, bf16 weights
    python chip_smoke.py --pd    # --role prefill + --role decode, one chip each

``--inject {worker-start,bad-request,kernel-mismatch}`` breaks one phase
on purpose; tests use it to show that a broken phase fails the run.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE / "chip_smoke_out"
MODEL = "smoke"
MAX_TOKENS = 64
T0 = time.monotonic()
# Prompt sizes in bytes (= byte-tokenizer tokens): eight concurrent
# requests, the streaming one, the one sent twice. The tiny preset's
# context is 256 tokens, so its rehearsal scales them down.
CHIP_TRAFFIC = ((300, 500, 700, 900, 1100, 1400, 1700, 2000), 400, 1200)
TINY_TRAFFIC = ((20, 40, 60, 80, 100, 120, 140, 160), 50, 100)
# The programs a served request runs; each must hold the Mosaic custom
# call on a TPU (jit names as JAX writes them into its IR dump file names).
SERVING_PROGRAMS = ("_prefill_and_sample", "_megastep_body")

# Worker flags per mode: existing CLI flags only, sized as a deployment
# would size them. One chip: int8 weights 8.17 GB + 3072 blocks x 32
# tokens x 57,344 B = 5.64 GB of KV (32 sequences x 3,072 tokens) of the
# 16.9 GB the chip reports; a T=8192 prefill wave adds ~0.55 GB of
# transients (measured, PERF.md "Bring-up on v5e").
ONE_CHIP = ["--preset", "qwen2-7b", "--quant", "int8", "--num-kv-blocks", "3072",
            "--max-model-len", "8192", "--max-num-seqs", "32"]
MODES = {
    "chip": ONE_CHIP,
    "cpu-tiny": ["--preset", "tiny"],
    "tp4": ["--preset", "qwen2-7b", "--tp", "4", "--num-kv-blocks", "8192",
            "--max-model-len", "8192", "--max-num-seqs", "32"],
    "pd": ONE_CHIP,  # for each of the two workers
}


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


class PhaseFailed(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return "(no log)"


# -- children --------------------------------------------------------------------


class Children:
    """Every process the smoke starts, reaped on the way out whatever
    happened: SIGTERM (the worker drains and releases the chip), then
    SIGKILL for anything still alive."""

    def __init__(self) -> None:
        self.procs: list[tuple[str, subprocess.Popen, Path]] = []

    def start(self, name: str, argv: list[str], env: dict) -> subprocess.Popen:
        log = OUT / f"{name}.log"
        with open(log, "wb") as fh:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=HERE, env=env, stdout=fh,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        self.procs.append((name, proc, log))
        return proc

    def check_alive(self) -> None:
        for name, proc, log in self.procs:
            if proc.poll() is not None:
                raise PhaseFailed(
                    f"{name} exited with code {proc.returncode}:\n{tail(log)}"
                )

    def stop(self) -> None:
        # Last started, first stopped: the frontend, then the workers
        # (which drain against a store that is still there), then the store.
        for name, proc, _ in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(30)
            except subprocess.TimeoutExpired:
                say(f"{name} ignored SIGTERM; killing")
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait(10)
        self.procs.clear()


# -- HTTP ---------------------------------------------------------------------------


def http_json(url: str, body: dict | None = None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:400]}


def chat_body(prompt: str, model: str, **extra) -> dict:
    return {
        "model": model,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": MAX_TOKENS,
        # Random weights can sample the byte tokenizer's EOS id; the
        # length contract below needs every request to run its budget.
        "dyn": {"ignore_eos": True},
        **extra,
    }


def prompt_of(n_bytes: int, seed: int) -> str:
    rng = random.Random(seed)
    words = []
    while sum(len(w) + 1 for w in words) < n_bytes:
        words.append("".join(rng.choices("abcdefghijklmnopqrstuvwxyz",
                                         k=rng.randint(2, 9))))
    return " ".join(words)[:n_bytes]


def check_completion(status: int, body: dict, what: str) -> dict:
    if status != 200:
        raise PhaseFailed(f"{what}: HTTP {status}: {body}")
    usage = body.get("usage") or {}
    finish = body["choices"][0].get("finish_reason")
    if usage.get("completion_tokens") != MAX_TOKENS or finish != "length":
        raise PhaseFailed(
            f"{what}: expected {MAX_TOKENS} completion tokens and "
            f"finish_reason 'length', got usage={usage} finish={finish!r}"
        )
    return usage


def stream_chat(url: str, body: dict) -> dict:
    """One SSE chat completion; returns time to first content chunk,
    chunk count and whether the stream closed with ``data: [DONE]``."""
    req = urllib.request.Request(
        url, data=json.dumps(dict(body, stream=True)).encode(),
        headers={"Content-Type": "application/json"},
    )
    t0 = time.monotonic()
    first = None
    chunks = 0
    finish = None
    last = ""
    with urllib.request.urlopen(req, timeout=600) as r:
        if r.status != 200:
            raise PhaseFailed(f"streaming request: HTTP {r.status}")
        for raw in r:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("data:"):
                continue
            last = line
            payload = line[5:].strip()
            if payload == "[DONE]":
                continue
            chunk = json.loads(payload)
            for choice in chunk.get("choices", []):
                if first is None:
                    first = time.monotonic() - t0
                chunks += 1
                finish = choice.get("finish_reason") or finish
    if last != "data: [DONE]":
        raise PhaseFailed(f"stream did not end in 'data: [DONE]' but {last!r}")
    if finish != "length":
        raise PhaseFailed(f"stream finish_reason {finish!r}, expected 'length'")
    return {"ttft_s": first, "chunks": chunks,
            "total_s": time.monotonic() - t0}


# -- phases -------------------------------------------------------------------------


def pin_to_chip(i: int) -> dict:
    """libtpu environment that gives one process chip ``i`` of the host
    and ports of its own (both spellings of the visibility variable and
    of the inter-process address, for the libtpu versions that read
    each)."""
    port = 8476 + i
    return dict(
        TPU_VISIBLE_CHIPS=str(i), TPU_VISIBLE_DEVICES=str(i),
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1", TPU_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_ADDRESSES=f"localhost:{port}", TPU_PROCESS_PORT=str(port),
        TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{port}",
        TPU_MESH_CONTROLLER_PORT=str(port), CLOUD_TPU_TASK_ID="0",
        TPU_RUNTIME_METRICS_PORTS=str(8431 + i),
    )


def build_native_index(env: dict) -> str:
    """Build the C++ radix index from native/radix_tree.cpp on this
    machine (``make -B``: never a binary left in the tree by an earlier
    build), or run the Python tree on purpose and say so."""
    try:
        subprocess.run(
            ["make", "-B", "-C", str(HERE / "native")], check=True,
            capture_output=True, timeout=180,
        )
        return "native C++"
    except (OSError, subprocess.SubprocessError) as e:
        say(f"native radix build failed ({e}); frontend told to use Python")
        env["DYNAMO_TPU_NO_NATIVE"] = "1"
        return "Python"


def wait_for(predicate, children: Children, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        children.check_alive()
        got = predicate()
        if got:
            return got
        time.sleep(0.5)
    raise PhaseFailed(f"timed out after {timeout:.0f}s waiting for {what}")


def child_env(mode: str) -> dict:
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               DYN_FLIGHT_DIR=str(OUT / "flight"))
    if mode == "cpu-tiny":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def serve_phase(mode: str, inject: str | None, report: dict) -> None:
    env = child_env(mode)
    report["radix_index_built"] = build_native_index(env)

    store_port, http_port = free_port(), free_port()
    env["DYN_STORE_ADDRESS"] = f"127.0.0.1:{store_port}"
    base = f"http://127.0.0.1:{http_port}"
    ir_dir = OUT / "ir"
    children = Children()
    try:
        children.start(
            "store", ["-m", "dynamo_tpu.runtime.store", "--port", str(store_port)],
            env,
        )
        worker_flags = list(MODES[mode])
        if mode == "cpu-tiny":   # `tiny`, or a hybrid model (conv state pages, paired heads)
            worker_flags = ["--preset", report.get("cpu_preset", "tiny")]
            if worker_flags[1] != "tiny":
                # 128-wide paired heads: the CPU's reference attention gathers
                # [T, span, heads, 128] in float32, 137 GB at the default 8,192
                worker_flags += ["--max-model-len", "512", "--num-kv-blocks", "256",
                                 "--max-num-seqs", "8"]
        if inject == "worker-start":
            worker_flags += ["--preset", "no-such-preset"]
        workers = []
        roles = ("prefill", "decode") if mode == "pd" else ("aggregated",)
        t_start = time.monotonic()
        for i, role in enumerate(roles):
            status_port = free_port()
            (ir_dir / role).mkdir(parents=True, exist_ok=True)
            wenv = dict(env, DYN_SYSTEM_PORT=str(status_port),
                        JAX_DUMP_IR_TO=str(ir_dir / role))
            flags = ["--model-name", MODEL, *worker_flags]
            if mode == "pd":
                # One chip per process: each libtpu instance sees one chip
                # and gets its own ports, or the second one to start finds
                # the devices (or the first one's port) taken.
                wenv.update(pin_to_chip(i))
                flags += ["--role", role, "--max-local-prefill-length", "256"]
            children.start(f"worker-{role}",
                           ["-m", "dynamo_tpu.backends.jax", *flags], wenv)
            workers.append((role, f"http://127.0.0.1:{status_port}/health",
                            OUT / f"worker-{role}.log"))
        children.start(
            "frontend",
            ["-m", "dynamo_tpu.frontend", "--http-host", "127.0.0.1",
             "--http-port", str(http_port), "--router-mode", "kv"],
            dict(env, DYN_SYSTEM_PORT=str(free_port())),
        )

        # Cold compile of every serving program happens before "serving
        # model" (engine/warmup.py), so this wait is the long one.
        for role, _, log in workers:
            marker = "ready (model" if role == "prefill" else "serving model"
            wait_for(lambda: marker in log.read_text(errors="replace"),
                     children, 1000, f"'{marker}' in {log.name}")
        report["start_to_serving_s"] = round(time.monotonic() - t_start, 1)
        say(f"worker serving after {report['start_to_serving_s']} s")
        wait_for(lambda: http_json(f"{base}/v1/models")[1].get("data"),
                 children, 60, "the model at the frontend")

        health = {role: http_json(url)[1] for role, url, _ in workers}
        head = health[roles[-1]]
        report["device"] = head["device"]
        report["startup"] = {r: h["startup"] for r, h in health.items()}
        say(f"device: {head['device']}")
        for role, st in report["startup"].items():
            # Warm-up times a prefill wave per bucket; the waves planner
            # decides by that table, so a worker without it pads as before.
            if not st.get("prefill_bucket_ms"):
                raise PhaseFailed(f"{role}: no prefill_bucket_ms on /health "
                                  f"startup after warm-up: {sorted(st)}")

        chat = f"{base}/v1/chat/completions"
        model = "no-such-model" if inject == "bad-request" else MODEL
        prompt_bytes, stream_bytes, repeat_bytes = (
            TINY_TRAFFIC if mode == "cpu-tiny" else CHIP_TRAFFIC)
        streamed = stream_chat(chat, chat_body(prompt_of(stream_bytes, 1), model))
        report["start_to_first_token_s"] = round(
            time.monotonic() - t_start - streamed["total_s"]
            + streamed["ttft_s"], 1)
        report["stream"] = {k: round(v, 3) for k, v in streamed.items()}
        say(f"stream ok: first token after {streamed['ttft_s']:.2f} s, "
            f"{streamed['chunks']} chunks")

        results: list = [None] * len(prompt_bytes)

        def one(i: int, n: int) -> None:
            t0 = time.monotonic()
            status, body = http_json(chat, chat_body(prompt_of(n, 100 + i), model))
            results[i] = (status, body, time.monotonic() - t0)

        threads = [threading.Thread(target=one, args=(i, n))
                   for i, n in enumerate(prompt_bytes)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        wall = time.monotonic() - t0
        if any(r is None for r in results):
            raise PhaseFailed("a concurrent request never returned")
        usages = [check_completion(s, b, f"concurrent request {i}")
                  for i, (s, b, _) in enumerate(results)]
        report["concurrent"] = {
            "requests": len(results),
            "prompt_tokens": [u["prompt_tokens"] for u in usages],
            "latency_s": [round(r[2], 2) for r in results],
            "wall_s": round(wall, 2),
            "completion_tokens_per_s": round(len(results) * MAX_TOKENS / wall, 1),
        }
        say(f"{len(results)} concurrent completions ok in {wall:.1f} s")

        repeat = chat_body(prompt_of(repeat_bytes, 7), model)
        check_completion(*http_json(chat, repeat), "repeat prompt, first send")
        usage = check_completion(*http_json(chat, repeat),
                                 "repeat prompt, second send")
        cached = (usage.get("prompt_tokens_details") or {}).get("cached_tokens", 0)
        # A window model's blocks are found again by no one: its workers say
        # so on /health, and a hit there would be an unsound one.
        caching = all(st.get("prefix_caching", True) for st in report["startup"].values())
        if caching and not cached > 0:
            raise PhaseFailed(f"no cached_tokens on the repeated prompt: {usage}")
        if not caching and cached:
            raise PhaseFailed(f"{cached} cached_tokens from workers whose prefix "
                              f"caching is off: {usage}")
        report["repeat_cached_tokens"] = cached
        say(f"repeat prompt served {cached} cached tokens")

        health = {role: http_json(url)[1] for role, url, _ in workers}
        report["memory_after_requests"] = {r: h["memory"] for r, h in health.items()}
        report["compile"] = {r: h["compile"] for r, h in health.items()}
        children.check_alive()
        if mode == "pd":
            # Prompts above --max-local-prefill-length must really have
            # been prefilled on the other chip and their KV handed over.
            disagg = {
                line.split("{")[0].split()[0]: float(line.split()[-1])
                for line in metric_lines(workers[-1][1], "dynamo_disagg_")
            }
            report["disagg"] = disagg
            if not any(v > 0 for k, v in disagg.items() if "handoffs" in k):
                raise PhaseFailed(f"no KV handoff happened: {disagg}")

        frontend_log = (OUT / "frontend.log").read_text(errors="replace")
        if "migrating request" in frontend_log:
            raise PhaseFailed(
                "a request was replayed (stall or worker failure):\n"
                + "\n".join(l for l in frontend_log.splitlines()
                            if "migrating request" in l)[:2000]
            )
        used = "Python" if "kv index: Python" in frontend_log else (
            "native C++" if "kv index: native C++" in frontend_log else None)
        if used != report["radix_index_built"]:
            raise PhaseFailed(
                f"router index {used!r} but this run built "
                f"{report['radix_index_built']!r}"
            )
        report["radix_index"] = used
        report["attention"] = check_attention_lowering(
            ir_dir, roles, head["device"]["platform"])
        report["attention_traced"] = check_calls_traced(
            workers, head["device"]["platform"],
            "dynamo_engine_attention_calls_traced_total", judge_attention_traced)
        report["experts_traced"] = check_calls_traced(
            workers, head["device"]["platform"],
            "dynamo_engine_expert_calls_traced_total", judge_experts_traced)
        report["linear_traced"] = check_calls_traced(
            workers, head["device"]["platform"],
            "dynamo_engine_linear_calls_traced_total", judge_linear_traced)
        report["ssm_traced"] = check_calls_traced(
            workers, head["device"]["platform"],
            "dynamo_engine_ssm_calls_traced_total", judge_ssm_traced)
        for role, st in report["startup"].items():
            steps = {**report["linear_traced"][role], **report["ssm_traced"][role]}
            if st.get("state_slots") and not any(
                    k.startswith("step/") and v for k, v in steps.items()):
                raise PhaseFailed(f"{role}: a model with linear-attention or mamba layers "
                                  f"traced no state step: {steps}")
        for role, st in report["startup"].items():
            judge_window(role, st, report["attention_traced"][role])
            judge_blocks(role, st, report["attention_traced"][role],
                         next(url for r, url, _ in workers if r == role))
        report["token_account"] = token_accounts(workers)
    finally:
        children.stop()
        shutil.rmtree(ir_dir, ignore_errors=True)


def check_attention_lowering(ir_dir: Path, roles, platform: str) -> dict:
    """Read the programs the worker actually lowered (JAX_DUMP_IR_TO) and
    look for the Mosaic custom call in each serving program: the lowered
    program is the evidence, not a flag or a log line."""
    found: dict[str, dict] = {}
    for role in roles:
        for program in SERVING_PROGRAMS:
            files = sorted((ir_dir / role).glob(f"*jit_{program}*.mlir"))
            calls = [f.read_text(errors="replace").count("tpu_custom_call")
                     for f in files]
            found[f"{role}:{program}"] = {"lowered": len(files),
                                          "with_mosaic_call": sum(c > 0 for c in calls)}
    for name, got in found.items():
        if name == "prefill:_megastep_body":
            continue  # a prefill-role worker never decodes under traffic
        if not got["lowered"]:
            raise PhaseFailed(f"no lowered program found for {name} in {ir_dir}")
        if platform == "tpu" and got["with_mosaic_call"] != got["lowered"]:
            raise PhaseFailed(
                f"served attention did not lower to the Pallas kernel: {name} "
                f"{got} (expected a tpu_custom_call in every program)"
            )
        if platform != "tpu" and got["with_mosaic_call"]:
            raise PhaseFailed(f"Mosaic call in a {platform} program? {name} {got}")
    return found


def metric_lines(health_url: str, prefix: str) -> list[str]:
    """The sample lines of a worker's /metrics whose name starts with ``prefix``."""
    with urllib.request.urlopen(
            health_url.replace("/health", "/metrics"), timeout=30) as r:
        return [l for l in r.read().decode().splitlines() if l.startswith(prefix)]


def token_accounts(workers) -> dict:
    """Each worker's account of a token since its start (the step clock's
    lane-seconds over the tokens decode iterations committed, ms:
    ``tools/token_account.py``). Observed, never judged."""
    from tools.token_account import account

    found = {}
    for role, url, _ in workers:
        acc = account("", "\n".join(metric_lines(url, "dynamo_engine_")))
        found[role] = {k: acc[k] for k in (
            "decode_ms_per_token", "prefill_stall_ms_per_token", "host_stall_ms_per_token")}
    return found


def check_calls_traced(workers, platform: str, metric: str, judge) -> dict:
    """Each worker's ``metric`` (a ``..._calls_traced_total{shape, impl}``
    counter) as ``{"<shape>/<impl>": calls traced}``, held to ``judge``:
    :func:`judge_attention_traced` (a worker that decodes must have traced
    the decode shape, and on a TPU none may have fallen to a jnp path) or
    :func:`judge_experts_traced` (no series for a dense model; on a TPU a
    sparse wave may not have run every expert on every row)."""
    found: dict[str, dict] = {}
    for role, url, _ in workers:
        got = {}
        for line in metric_lines(url, metric + "{"):
            labels = dict(kv.split("=") for kv in line[line.index("{") + 1:line.index("}")]
                          .replace('"', "").split(","))
            got[f"{labels['shape']}/{labels['impl']}"] = float(line.split()[-1])
        found[role] = got
        judge(role, got, platform)
    return found


def judge_experts_traced(role: str, got: dict[str, float], platform: str) -> None:
    """``got``: ``{"<shape>/<impl>": calls traced}`` of one worker."""
    if platform == "tpu" and got.get("wave/all_rows"):
        raise PhaseFailed(
            f"{role}: a sparse prefill wave ran every held expert on every row on a "
            f"TPU, not the grouped product over the chosen pairs: {got}")


def judge_linear_traced(role: str, got: dict[str, float], platform: str) -> None:
    """``got``: ``{"<shape>/<impl>": calls traced}`` of one worker (no series
    for a model without linear-attention layers)."""
    if platform == "tpu" and got.get("step/jnp"):
        raise PhaseFailed(
            f"{role}: a linear-attention layer's decode state step ran its jnp path on "
            f"a TPU, not the kernel that reads and writes each lane's state once: {got}")


def judge_ssm_traced(role: str, got: dict[str, float], platform: str) -> None:
    """``got``: ``{"<shape>/<impl>": calls traced}`` of one worker (no series
    for a model without mamba layers)."""
    if platform == "tpu" and got.get("step/jnp"):
        raise PhaseFailed(
            f"{role}: a mamba layer's decode state step ran its jnp path on a TPU, not "
            f"the kernel that reads and writes each lane's state once: {got}")


def judge_attention_traced(role: str, got: dict[str, float], platform: str) -> None:
    """``got``: ``{"<shape>/<impl>": calls traced}`` of one worker."""
    traced = {k for k, v in got.items() if v}
    if role != "prefill" and not any(
            k.split("/")[0] in ("decode", "latent-decode", "block-decode", "gqa-decode")
            for k in traced):
        raise PhaseFailed(f"{role}: no decode-shaped attention call was traced: {got}")
    if platform == "tpu" and any(k.endswith("/reference") for k in traced):
        raise PhaseFailed(f"{role}: attention ran the jnp reference on a TPU: {got}")
    if platform == "tpu" and "latent-decode/jnp" in traced:
        raise PhaseFailed(
            f"{role}: latent decode attention ran its jnp path on a TPU, not the "
            f"paged kernel: {got}")
    if platform == "tpu" and {"gqa-decode/jnp", "window-gqa-decode/jnp", "gqa-ragged/jnp",
                              "window-gqa-ragged/jnp"} & traced:
        raise PhaseFailed(
            f"{role}: wide-key attention ran the chunked jnp walk on a TPU, not its "
            f"paged kernel (decode steps and waves have one each): {got}")


def judge_window(role: str, startup: dict, traced: dict[str, float]) -> None:
    """A worker of a model with window layers (``startup.window_blocks``):
    its decode steps must have traced a window call (on a TPU never the jnp
    reference: :func:`judge_attention_traced` has refused that), and its
    window table may be no wider than one dispatch's span: the window's
    blocks, one more where the window starts inside a block, and the blocks
    of the most queries a sequence has in one dispatch (a prefill chunk of
    the largest bucket that goes on through a megastep). A wider table
    means the window layers keep, or walk, more than their window."""
    if not startup.get("window_blocks"):
        return
    if role != "prefill" and not any(
            k.startswith(("window-decode/", "window-gqa-decode/")) and v
            for k, v in traced.items()):
        raise PhaseFailed(f"{role}: a window model traced no window-decode call: {traced}")
    bs, window = startup["block_size"], startup["sliding_window"]
    chunk = max(int(b) for b in startup["prefill_bucket_ms"]) + startup["megastep_k"]
    limit = window // bs + 1 + -(-chunk // bs)
    if startup["window_table_blocks"] > limit:
        raise PhaseFailed(
            f"{role}: window table of {startup['window_table_blocks']} columns for "
            f"window {window}, blocks of {bs} and chunks of {chunk}: at most {limit}")


def judge_blocks(role: str, startup: dict, traced: dict[str, float],
                 health_url: str | None = None) -> None:
    """A worker of a block-diffusion model (``startup.block_length``): its
    steps must have traced a ``block-decode`` call, the block's rows folded
    into one decode-shaped call of the kernel (on a TPU never the jnp
    reference: :func:`judge_attention_traced` has refused that), and none of
    the plain ``decode`` shape, whose mask is causal inside a block. With the
    worker's ``health_url``: of the rows its block passes ran, fewer went
    through the head (the clean pass, at the least, has none)."""
    if not startup.get("block_length"):
        return
    live = {k for k, v in traced.items() if v}
    if role != "prefill" and not any(k.startswith("block-decode/") for k in live):
        raise PhaseFailed(f"{role}: a block model traced no block-decode call: {traced}")
    if any(k.split("/")[0] in ("decode", "ragged") for k in live):
        raise PhaseFailed(
            f"{role}: a block model traced a causal attention call, which cannot see a "
            f"block both ways: {traced}")
    if health_url and role != "prefill":
        head, rows = (float(metric_lines(health_url, f"dynamo_engine_block_{n}_total{{")[0]
                            .split()[-1]) for n in ("head_rows", "rows"))
        if not 0 < head < rows:
            raise PhaseFailed(f"{role}: the head ran on {head:.0f} of {rows:.0f} block rows")


def kernel_phase(mode: str, inject: str | None, report: dict) -> None:
    env = child_env(mode)
    argv = [sys.executable, str(HERE / "chip_smoke.py"), "--kernel-check-child"]
    if inject == "kernel-mismatch":
        argv.append("--corrupt")
    log = OUT / "kernel_check.log"
    with open(log, "wb") as fh:
        proc = subprocess.run(argv, cwd=HERE, env=env, stdout=subprocess.PIPE,
                              stderr=fh, timeout=900)
    lines = proc.stdout.decode(errors="replace").strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        raise PhaseFailed(f"kernel check printed no result (exit "
                          f"{proc.returncode}):\n{tail(log)}") from None
    report["kernels"] = result
    bad = [c for c in result["checks"] if not c["ok"]]
    if proc.returncode != 0 or bad:
        raise PhaseFailed(f"kernel check failed: {bad or tail(log)}")
    kinds = [(d["platform"], d["kind"])
             for d in (result["device"], report.get("device")) if d]
    if len(set(kinds)) > 1:
        raise PhaseFailed(f"kernel check ran on {result['device']}, the "
                          f"worker on {report['device']}")
    report.setdefault("device", result["device"])


# -- the kernel-check child (the only code here that imports JAX) -------------------


def kernel_check_child(corrupt: bool) -> int:
    """Compile the library's Pallas attention kernel, at its ragged and
    its decode-shaped grids, WITHOUT interpret mode on a TPU and compare
    with the repo's reference.

    Tolerances. Inputs and outputs are bf16 (8 significand bits: one ulp
    is 2^-8 relative) and the kernels keep bf16 operands on the MXU with
    f32 accumulation, so an output of magnitude <= 4 may differ from an
    f32-exact reference by an ulp or two of 2^-6 (0.0156 was the largest
    difference seen on a v5e): atol 2^-5, rtol 2e-2. The
    references (and only they: Mosaic rejects an f32 x bf16 matmul at
    fp32 contract precision) run at ``highest`` matmul precision so that
    they, not the kernel, are the exact side.

    On the CPU (``--cpu-tiny``) the library kernel is reported as not run
    (the TPU interpreter cannot execute its reshaped refs); the serving
    entry's decode shape runs the jnp reference at a small geometry, so
    that the comparison itself is rehearsed."""
    sys.path.insert(0, str(HERE))
    from dynamo_tpu.device import device_info, enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.ragged_attention import (
        pallas_ragged_attention,
        ragged_paged_attention_ref,
    )

    info = device_info()
    on_tpu = info["platform"] == "tpu"
    checks: list[dict] = []
    ATOL, RTOL = 2.0 ** -5, 2e-2
    rng = np.random.RandomState(0)

    def check(name: str, run, reference) -> None:
        """Compile and run one kernel (the compiler's refusal is a finding,
        not a crash), then compare with its reference."""
        t0 = time.perf_counter()
        try:
            got = np.asarray(jax.block_until_ready(run()), np.float32)
        except Exception as e:  # noqa: BLE001 — recorded, the run fails
            checks.append({"name": name, "ok": False,
                           "error": f"{type(e).__name__}: {e}"[:1500]})
            return
        seconds = time.perf_counter() - t0
        if corrupt:
            got = got + 1.0
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(), np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(np.all(np.isfinite(got))) and bool(
            np.all(np.abs(got - want) <= ATOL + RTOL * np.abs(want)))
        checks.append({"name": name, "ok": ok, "max_abs_err": round(err, 5),
                       "shape": list(got.shape), "compile_run_s": round(seconds, 2)})

    # (i) library ragged kernel under this repo's explicit grids, at the
    # served geometry: 28 query heads over 4 KV heads (8 combined) x 128,
    # 32-token pages, block tables as wide as --max-model-len 8192.
    n_q, n_kv, d, ps, pages_per_seq, n_pages = 28, 4, 128, 32, 256, 1024
    sm = d ** -0.5

    def ragged_case(q_lens, kv_lens):
        S, T = len(q_lens), sum(q_lens)
        q = jnp.asarray(rng.randn(T, n_q, d), jnp.bfloat16)
        kv = jnp.asarray(rng.randn(n_pages, ps, 2 * n_kv, d), jnp.bfloat16)
        tables = np.zeros((S, pages_per_seq), np.int32)
        perm, used = rng.permutation(n_pages), 0
        for s, n in enumerate(kv_lens):
            need = -(-n // ps)
            tables[s, :need] = perm[used:used + need]
            used += need
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        return (q, kv, jnp.asarray(kv_lens, jnp.int32), jnp.asarray(tables),
                jnp.asarray(cu), jnp.asarray([S], jnp.int32))

    def ragged_ref(q, kv, kv_lens, tables, cu, _):
        """Reference one sequence at a time over just its live pages (the
        whole-batch reference gathers [T, span, 2kv, d] in f32)."""
        outs = []
        for s in range(kv_lens.shape[0]):
            lo, hi = int(cu[s]), int(cu[s + 1])
            need = -(-int(kv_lens[s]) // ps)
            outs.append(ragged_paged_attention_ref(
                q[lo:hi], kv, kv_lens[s:s + 1], tables[s:s + 1, :need],
                jnp.asarray([0, hi - lo], jnp.int32),
                jnp.asarray([1], jnp.int32), sm_scale=sm,
            ))
        return jnp.concatenate(outs)

    if on_tpu:
        kernel = jax.jit(lambda *a: pallas_ragged_attention(*a, sm_scale=sm))
        decode = ragged_case([1] * 32, [int(x) for x in rng.randint(100, 640, 32)])
        # Eight sequences, T = 2048: fresh prompts and chunks that continue
        # a cached prefix (kv_len > q_len), the chunked-prefill causality.
        q_lens = [512, 384, 256, 256, 256, 128, 128, 128]
        prefill = ragged_case(q_lens, [n + e for n, e in
                                       zip(q_lens, [0, 96, 0, 320, 32, 0, 64, 512])])
        check("library ragged kernel, decode 32 x q1",
              lambda: kernel(*decode), lambda: ragged_ref(*decode))
        check("library ragged kernel, prefill T=2048",
              lambda: kernel(*prefill), lambda: ragged_ref(*prefill))

        # The megastep calls the kernel inside lax.scan.
        def scanned(q, *rest):
            def body(carry, _):
                return carry, pallas_ragged_attention(carry, *rest, sm_scale=sm)
            return jax.lax.scan(body, q, None, length=2)[1][1]

        check("library ragged kernel inside lax.scan",
              lambda: jax.jit(scanned)(*decode), lambda: ragged_ref(*decode))
    else:
        checks.append({"name": "library ragged kernel", "ok": True,
                       "not_run": "cpu: the TPU interpreter cannot execute it"})

    # (i-b) the decode-shaped path (``cu_q_lens=None``: what a megastep's
    # attention runs: a first-party kernel where the geometry fits one, group
    # 1 or grouped, else the library kernel at one sequence a query block)
    # through the serving entry, at the cells' decode shapes and at
    # `--block-size` 128 with 8 KV heads (where a KV block counted in
    # pages outgrew VMEM): lanes, heads, page size, page-table width, one
    # layer's page array, contexts.
    from dynamo_tpu.ops.ragged_attention import ragged_paged_attention

    def decode_case(lanes, heads, kv_heads, ps, width, pages, lo, hi):
        """``pages``: one layer's page array as the cell has it, or as many
        pages as the drawn contexts fill where that is more."""
        lens = rng.randint(lo, hi, lanes).astype(np.int32)
        lens[:4] = [1, ps, ps + 1, hi]      # an inactive lane; page edges
        tables = np.zeros((lanes, width), np.int32)
        pages = max(pages, 1 + int(np.sum(-(-lens // ps))))
        perm, used = rng.permutation(pages - 1), 0
        for s_, n in enumerate(lens):
            need = -(-int(n) // ps)
            tables[s_, :need] = perm[used:used + need]
            used += need
        return (jnp.asarray(rng.randn(lanes, heads, d), jnp.bfloat16),
                jnp.asarray(rng.randn(pages, ps, 2 * kv_heads, d), jnp.bfloat16),
                jnp.asarray(lens), jnp.asarray(tables))

    def decode_ref(q, kv, lens, tables):
        one, ps = jnp.asarray([1], jnp.int32), kv.shape[1]
        return jnp.concatenate([
            ragged_paged_attention_ref(
                q[s_:s_ + 1], kv, lens[s_:s_ + 1],
                tables[s_:s_ + 1, :-(-int(lens[s_]) // ps)],
                jnp.asarray([0, 1], jnp.int32), one, sm_scale=sm)
            for s_ in range(q.shape[0])])

    from dynamo_tpu.ops.ragged_attention import first_party_decode

    if on_tpu:
        # (Laguna's and Nemotron's at their narrow decode widths and Laguna's
        # contexts cut short: the reference is a program a lane, and the
        # phase has 900 s)
        for label, geometry in [
            ("7B: 32 lanes, 28/4 heads", (32, 28, 4, 32, 256, 3073, 384, 1536)),
            ("1.5B: 8 lanes, 12/2 heads", (8, 12, 2, 32, 256, 11265, 160, 2560)),
            ("1.5B: 32 lanes, 12/2 heads", (32, 12, 2, 32, 256, 11265, 160, 2560)),
            ("Ouro: 8 lanes, 16/16 heads", (8, 16, 16, 32, 64, 676, 128, 608)),
            ("Laguna's full layers: 16 lanes, 48/8 heads", (16, 48, 8, 32, 338, 0, 1024, 4096)),
            ("Nemotron: 32 lanes, 32/2 heads", (32, 32, 2, 32, 128, 0, 256, 2048)),
            ("32 lanes, 32/8 heads, 128-token pages", (32, 32, 8, 128, 64, 769, 384, 1536)),
        ]:
            case = decode_case(*geometry)
            lanes_ = jnp.asarray([geometry[0]], jnp.int32)
            took = first_party_decode("tpu", case[0], case[1], None)
            label += f" ({'first-party kernel, ' + took[0] if took else 'library kernel'})"
            check(f"decode-shaped attention, {label}",
                  lambda: jax.jit(lambda *a: ragged_paged_attention(
                      *a, None, lanes_, sm_scale=sm))(*case),
                  lambda: decode_ref(*case))
    else:
        case = decode_case(4, 4, 2, 8, 4, 17, 2, 32)
        check("decode-shaped attention, rehearsal: 4 lanes, 4/2 heads",
              lambda: ragged_paged_attention(
                  *case, None, jnp.asarray([4], jnp.int32), sm_scale=sm),
              lambda: decode_ref(*case))

    # (i-c) 64-wide heads cached in pairs (a model of head width 64 and an
    # even number of KV heads: LFM2's 32/8 heads): the serving entry for
    # them, against the per-head reference on pages of 64-wide heads that
    # hold the same K and V. On a TPU the pair's decode call gets the
    # first-party kernel of grouped heads (32 query heads on 4 paired rows).
    from dynamo_tpu.ops.ragged_attention import paired_heads_attention

    def paired_case(lanes, heads, kv_heads, ps, width, pages, lo, hi):
        q, kv, lens, tables = decode_case(lanes, heads, kv_heads // 2, ps, width, pages, lo, hi)
        pages = kv.shape[0]
        half = d // 2        # kv [pages, ps, 2 x kv_heads / 2, 128] = pairs, K even V odd
        plain = kv.reshape(pages, ps, kv_heads // 2, 2, 2, half).transpose(
            0, 1, 2, 4, 3, 5).reshape(pages, ps, 2 * kv_heads, half)
        return q[..., :half], kv, lens, tables, plain

    def paired_ref(q, _, lens, tables, plain):
        one, ps = jnp.asarray([1], jnp.int32), plain.shape[1]
        return jnp.concatenate([
            ragged_paged_attention_ref(
                q[s_:s_ + 1], plain, lens[s_:s_ + 1],
                tables[s_:s_ + 1, :-(-int(lens[s_]) // ps)],
                jnp.asarray([0, 1], jnp.int32), one, sm_scale=(d // 2) ** -0.5)
            for s_ in range(q.shape[0])])

    geometry = (128, 32, 8, 32, 128, 4097, 300, 2800) if on_tpu else (4, 4, 2, 8, 4, 17, 2, 32)
    case = paired_case(*geometry)
    lanes_ = jnp.asarray([geometry[0]], jnp.int32)
    check(f"paired 64-wide heads, decode shape: {geometry[0]} lanes, "
          f"{geometry[1]}/{geometry[2]} heads",
          lambda: jax.jit(lambda q, kv, lens, tables: paired_heads_attention(
              q, kv, lens, tables, None, lanes_, sm_scale=(d // 2) ** -0.5))(*case[:4]),
          lambda: paired_ref(*case))

    for c in checks:
        print(f"  {'ok  ' if c['ok'] else 'FAIL'} {c}", file=sys.stderr, flush=True)
    print(json.dumps({"device": info, "atol": ATOL, "rtol": RTOL, "checks": checks}))
    return 0 if all(c["ok"] for c in checks) else 1


# -- entry ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    which = ap.add_mutually_exclusive_group()
    which.add_argument("--cpu-tiny", action="store_const", const="cpu-tiny",
                       dest="mode", help="rehearse on the CPU with the tiny preset")
    which.add_argument("--tp4", action="store_const", const="tp4", dest="mode",
                       help="four chips: one worker with --tp 4, bf16 weights")
    which.add_argument("--pd", action="store_const", const="pd", dest="mode",
                       help="two one-chip workers: --role prefill and --role decode")
    which.add_argument("--kernel-check-child", action="store_true",
                       help=argparse.SUPPRESS)
    ap.add_argument("--cpu-preset", choices=["tiny", "tiny-lfm2", "tiny-laguna", "tiny-sdar", "tiny-mimo",
                                             "tiny-olmo-hybrid", "tiny-nemotron-h"],
                    default="tiny",
                    help="what --cpu-tiny serves: the dense tiny preset, the "
                         "hybrid one (conv layers beside paired 64-wide heads), "
                         "the one of window and full attention layers (two pools), "
                         "the one that generates by diffusion over blocks, the one "
                         "whose key is wider than its value (two pools of unequal pages), "
                         "the one of linear-attention layers (a slab a lane), or the one of "
                         "one-sub-layer blocks (Mamba-2 mixers in the slab, experts alone)")
    ap.add_argument("--corrupt", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--inject", choices=["worker-start", "bad-request",
                                         "kernel-mismatch"], default=None,
                    help="break one phase on purpose (the run must fail)")
    args = ap.parse_args()
    if args.kernel_check_child:
        return kernel_check_child(args.corrupt)
    mode = args.mode or "chip"

    if not (HERE / "dynamo_tpu").is_dir():
        say("dynamo_tpu/ is not beside chip_smoke.py: nothing to smoke")
        return 1
    if mode != "cpu-tiny" and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        say("JAX_PLATFORMS=cpu: this environment has no accelerator. The chip "
            "check needs a TPU; pass --cpu-tiny for the CPU rehearsal.")
        return 1
    OUT.mkdir(exist_ok=True)

    report: dict = {"mode": mode}
    if mode == "cpu-tiny":
        report["cpu_preset"] = args.cpu_preset
    failures: list[str] = []
    phases = [("serve", serve_phase)]
    if mode in ("chip", "cpu-tiny"):  # the kernels are the same on four chips
        phases.append(("kernels", kernel_phase))
    for name, phase in phases:
        try:
            phase(mode, args.inject, report)
            say(f"phase {name}: ok")
        except PhaseFailed as e:
            failures.append(f"{name}: {e}")
        except Exception as e:  # noqa: BLE001 — report, then fail the run
            failures.append(f"{name}: {type(e).__name__}: {e}")

    device = report.get("device")
    if not failures and mode != "cpu-tiny" and (device or {}).get("platform") != "tpu":
        failures.append(f"device is {device}, not a TPU")
    assert "jax" not in sys.modules, "the smoke's parent must never import JAX"
    report["seconds"] = round(time.monotonic() - T0, 1)
    (OUT / "report.json").write_text(json.dumps(report, indent=1, default=str))
    if failures:
        for log in sorted(OUT.glob("*.log")):
            say(f"---- tail of {log.name} ----\n{tail(log, 25)}")
        for f in failures:
            say(f"FAILED {f}")
        return 1

    startup = report["startup"]
    print(f"mode {mode}: device platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}")
    print(f"worker start -> 'serving model': {report['start_to_serving_s']} s; "
          f"-> first token: {report['start_to_first_token_s']} s "
          f"(stream TTFT {report['stream']['ttft_s']} s)")
    for role, st in startup.items():
        comp = report["compile"][role]
        print(f"[{role}] build {st['build_seconds']} s, warm-up "
              f"{st.get('warmup_seconds')} s; compile {comp['total_seconds']} s "
              f"backend + {comp['trace_lower_seconds']} s trace/lower, persistent "
              f"cache hits {comp['cache_hits']} misses {comp['cache_misses']}")
        for program, seconds in comp["programs"]:
            print(f"    compile {seconds:7.2f} s  {program}")
        print(f"[{role}] peak device bytes after init "
              f"{[m['peak_bytes_in_use'] for m in st['memory_after_init']]}, after "
              f"requests {[m['peak_bytes_in_use'] for m in report['memory_after_requests'][role]]}"
              f" (limit {[m['bytes_limit'] for m in st['memory_after_init']]})")
        print(f"[{role}] bytes per device: params {st['param_bytes_per_device']}, "
              f"cache {st['cache_bytes_per_device']}")
        print(f"[{role}] prefill wave ms by bucket: {st['prefill_bucket_ms']}")
    print(f"smoke observations (not benchmark results): {report['concurrent']}")
    print(f"repeat prompt cached_tokens={report['repeat_cached_tokens']}; router "
          f"index: {report['radix_index']} (built this run)")
    print(f"served attention lowering: {report['attention']}")
    print(f"served attention traced (shape/impl: calls): {report['attention_traced']}")
    print(f"served experts traced (shape/impl: calls): {report['experts_traced']}")
    print(f"served linear state calls traced (shape/impl: calls): {report['linear_traced']}")
    print(f"served ssm state calls traced (shape/impl: calls): {report['ssm_traced']}")
    print("a token's account since start, ms (decode / behind a wave / behind "
          f"the host; warm-up and compiles included): {report['token_account']}")
    for c in report.get("kernels", {}).get("checks", ()):
        print(f"kernel check: {c}")
    if "disagg" in report:
        print(f"prefill -> decode handoffs: {report['disagg']}")
    print(f"total {report['seconds']} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
