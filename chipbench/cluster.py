"""Start the processes a topology file names and wait until they serve.

A topology is data (``chipbench/topologies/<name>.json``): the frontend's
flags and a list of workers, each with a role and the chip it is pinned
to. One process per chip; the parent never imports JAX.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from pathlib import Path

from chipbench.procs import (
    Children,
    HarnessFault,
    free_port,
    http_json,
    pin_to_chip,
    wait_for,
)

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
MODEL = "bench"


def load_topology(name: str) -> dict:
    return json.loads((HERE / "topologies" / f"{name}.json").read_text())


@dataclass
class Worker:
    name: str
    health_url: str
    metrics_url: str
    side: Path
    log: Path


@dataclass
class Cluster:
    children: Children
    base_url: str
    frontend_metrics_url: str
    workers: list[Worker] = field(default_factory=list)
    start_to_serving_s: float = 0.0

    def stop(self) -> None:
        self.children.stop()


def start(topology: dict, config_name: str, seed: int, out_dir: Path,
          *, cpu: bool, serve_timeout: float = 1100.0) -> Cluster:
    """Store, workers (through ``chipbench.worker_entry``), frontend; returns
    once every worker logged "serving model" and the frontend lists the
    model. On any failure the children are stopped before raising."""
    out_dir.mkdir(parents=True, exist_ok=True)
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               DYN_FLIGHT_DIR=str(out_dir / "flight"))
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    store_port, http_port = free_port(), free_port()
    env["DYN_STORE_ADDRESS"] = f"127.0.0.1:{store_port}"
    children = Children(ROOT, out_dir)
    fe_status = free_port()
    cluster = Cluster(children, f"http://127.0.0.1:{http_port}",
                      f"http://127.0.0.1:{fe_status}/metrics")
    t0 = time.monotonic()
    try:
        children.start("store", ["-m", "dynamo_tpu.runtime.store", "--port",
                                 str(store_port)], env)
        pin = len(topology["workers"]) > 1 and not cpu
        for i, w in enumerate(topology["workers"]):
            if w["role"] != "aggregated":
                raise HarnessFault(f"worker role {w['role']!r}: only "
                                   "'aggregated' workers are started so far")
            port = free_port()
            side = out_dir / f"side-{i}"
            wenv = dict(env, DYN_SYSTEM_PORT=str(port))
            if pin:
                wenv.update(pin_to_chip(int(w["chip"])))
            log = children.start(
                f"worker-{i}",
                ["-m", "chipbench.worker_entry", "--config", config_name,
                 "--model-name", MODEL, "--seed", str(seed),
                 "--side-dir", str(side)], wenv)
            base = f"http://127.0.0.1:{port}"
            cluster.workers.append(Worker(
                f"worker-{i}", f"{base}/health", f"{base}/metrics", side, log))
        children.start(
            "frontend",
            ["-m", "dynamo_tpu.frontend", "--http-host", "127.0.0.1",
             "--http-port", str(http_port), *topology["frontend"]],
            dict(env, DYN_SYSTEM_PORT=str(fe_status)))
        for w in cluster.workers:
            wait_for(lambda: "serving model" in w.log.read_text(errors="replace"),
                     children, serve_timeout, f"'serving model' in {w.log.name}",
                     every=0.5)
        wait_for(lambda: http_json(f"{cluster.base_url}/v1/models")[1].get("data"),
                 children, 60, "the model at the frontend")
        if len(cluster.workers) > 1:
            # The router must know every replica before traffic is offered.
            time.sleep(2.0)
    except BaseException:
        children.stop()
        raise
    cluster.start_to_serving_s = time.monotonic() - t0
    return cluster
