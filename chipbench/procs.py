"""Child processes of one run: start, watch, stop, and talk to over HTTP.

Copied in spirit from ``chip_smoke.py`` (``Children``, ``pin_to_chip``,
``wait_for``), which stays what it is: a start-up proof. Nothing here
imports JAX: the parent must never hold the chip its workers need.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path


class HarnessFault(Exception):
    """The benchmark itself could not do its work (a child died, a wait
    timed out, no chip): the run exits non-zero and prints no result."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return "(no log)"


def pin_to_chip(i: int) -> dict:
    """libtpu environment that gives one process chip ``i`` of the host
    and ports of its own (both spellings of the visibility variable and of
    the inter-process address, for the libtpu versions that read each)."""
    port = 8476 + i
    return dict(
        TPU_VISIBLE_CHIPS=str(i), TPU_VISIBLE_DEVICES=str(i),
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1", TPU_PROCESS_BOUNDS="1,1,1",
        TPU_PROCESS_ADDRESSES=f"localhost:{port}", TPU_PROCESS_PORT=str(port),
        TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{port}",
        TPU_MESH_CONTROLLER_PORT=str(port), CLOUD_TPU_TASK_ID="0",
        TPU_RUNTIME_METRICS_PORTS=str(8431 + i),
    )


# Seconds a child gets to leave after SIGTERM, and seconds a killed child
# gets to be gone. On the v5e the worker left 3-5 s after its SIGTERM and
# the frontend 0.1 s after its own in the open loop's cell, but 18-20 s in
# the closed loop's, whose clients are cut with streams in flight (PERF.md,
# Findings, PR 26): a frontend that takes longer is killed, and the worker
# then ends the streams of a peer that died. A killed child is waited for
# well beyond what any took so far, and one that stays is reported once
# every other child has had both signals: the 10 s of before, uncaught,
# ended a run with a traceback and left the store running.
TERM_WAIT_S = 20.0
REAP_WAIT_S = 120.0


class Children:
    """Every process a run starts, reaped on the way out whatever
    happened: SIGTERM (the worker drains and releases the chip), then
    SIGKILL of the whole process group for anything still alive. Every
    child gets both, however long another took."""

    def __init__(self, cwd: Path, log_dir: Path) -> None:
        self.cwd = cwd
        self.log_dir = log_dir
        self.procs: list[tuple[str, subprocess.Popen, Path]] = []

    def start(self, name: str, argv: list[str], env: dict) -> Path:
        log = self.log_dir / f"{name}.log"
        with open(log, "wb") as fh:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=self.cwd, env=env, stdout=fh,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        self.procs.append((name, proc, log))
        return log

    def check_alive(self) -> None:
        for name, proc, log in self.procs:
            if proc.poll() is not None:
                raise HarnessFault(
                    f"{name} exited with code {proc.returncode}:\n{tail(log)}")

    def stop(self) -> None:
        # Last started, first stopped: the frontend, then the workers
        # (which drain against a store that is still there), then the store.
        left = []
        for name, proc, _ in reversed(self.procs):
            t0 = time.monotonic()
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(TERM_WAIT_S)
            except subprocess.TimeoutExpired:
                pass
            t1 = time.monotonic()
            killed = proc.poll() is None
            # The group may hold grandchildren whether or not the leader
            # has gone; nothing of a run may outlive it.
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                proc.wait(REAP_WAIT_S)
            except subprocess.TimeoutExpired:
                left.append(name)
            how = f"left {t1 - t0:.1f} s after SIGTERM"
            if killed:
                how = (f"killed {t1 - t0:.1f} s after SIGTERM, "
                       f"{'still there' if name in left else 'gone'} "
                       f"{time.monotonic() - t1:.1f} s later")
            print(f"[chipbench] {name} {how}", file=sys.stderr, flush=True)
        self.procs.clear()
        if left:
            raise HarnessFault(f"still running {REAP_WAIT_S:.0f} s after SIGKILL: {left}")


def wait_for(predicate, children: Children, timeout: float, what: str,
             every: float = 0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        children.check_alive()
        got = predicate()
        if got:
            return got
        time.sleep(every)
    raise HarnessFault(f"timed out after {timeout:.0f}s waiting for {what}")


def http_json(url: str, body: dict | None = None, timeout: float = 600.0):
    """(status, parsed body). A refused connection is (0, {})."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:400]}
    except (urllib.error.URLError, ConnectionError, TimeoutError):
        return 0, {}


def http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode(errors="replace")
