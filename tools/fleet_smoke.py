"""Fleet autoscaling smoke (ISSUE 14): 3 mocker workers on the fleet
harness's virtual clock with the closed-loop planner ON, hit by a burst
that forces one reactive scale-up and, once it passes, one drained
scale-down.

Asserts the user-visible contract:

- the burst actuates ``scale_up`` and the quiet tail actuates
  ``scale_down`` through the connector, and the scaled-down worker
  retires via GRACEFUL DRAIN (finishes everything it accepted — never a
  kill);
- every client stream is byte-identical to an equal-workload run with a
  frozen pool (autoscaling moves capacity, never tokens), with zero
  broken streams and zero sheds;
- the planner's decision counters and replica gauges populate on a real
  MetricsRegistry through the PR 13 aggregator export path
  (``planner_decisions_total{action=...}``, ``planner_current_replicas``
  / ``planner_target_replicas`` per pool, ``planner_cycles_total``) and
  the ``/fleet`` payload carries the controller's actions and reasons.

CI usage (`.github/workflows/ci.yml` fleet-smoke step) and local:

    python tools/fleet_smoke.py
"""

from __future__ import annotations

import sys
from pathlib import Path

# Runnable straight from a checkout (CI also pip-installs the package).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    from dynamo_tpu.fleet.harness import FleetHarness, FleetSpec
    from dynamo_tpu.fleet.workload import TenantSpec

    # Quiet base load a 3-worker pool holds easily, then one hard burst
    # window (4x) that a frozen pool could also absorb — the point here
    # is the ACTUATION, not an SLO gap (tests/test_fleet.py has that).
    tenants = [
        TenantSpec(
            name="smoke", users=2_000, rps=8.0,
            burst_rps=32.0, burst_every_s=60.0, burst_len_s=12.0,
            isl=32, osl=8, shared_prefix_tokens=16,
        ),
    ]

    def spec(planner_on: bool) -> FleetSpec:
        return FleetSpec(
            tenants=tenants, duration_s=55.0, seed=11,
            planner_on=planner_on, static_replicas=3, initial_replicas=3,
            min_replicas=2, max_replicas=8, keep_streams=True,
        )

    # Frozen-pool twin first: the byte-identity reference.
    static = FleetHarness(spec(False)).run()
    h = FleetHarness(spec(True))
    report = h.run()

    assert report.scale_ups >= 1, (
        f"burst never actuated a scale-up: {report.decisions}"
    )
    assert report.scale_downs >= 1, (
        f"quiet tail never actuated a scale-down: {report.decisions}"
    )
    assert report.drained_retired >= 1, (
        "scale-down did not retire a worker via graceful drain"
    )
    assert report.peak_replicas > 3, report.peak_replicas
    assert report.broken_streams == 0 and report.shed == 0, (
        report.broken_streams, report.shed,
    )
    assert report.completed == report.requests == static.requests
    assert report.streams == static.streams, (
        "autoscaling changed client-visible bytes"
    )

    # Planner observability through the PR 13 aggregator export path.
    import asyncio

    from dynamo_tpu.obs.aggregator import FleetAggregator
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    async def export() -> tuple[str, dict]:
        agg = FleetAggregator(store=None)
        agg.attach_controller(h.controller)
        registry = MetricsRegistry()
        before = []
        agg.bind(registry, before)
        for cb in before:
            cb()
        return registry.render().decode(), agg.fleet_payload()

    text, payload = asyncio.new_event_loop().run_until_complete(export())
    for needle in (
        'planner_decisions_total{action="scale_up"',
        'planner_decisions_total{action="scale_down"',
        "planner_cycles_total",
        'planner_current_replicas{component="backend"',
        'planner_target_replicas{component="backend"',
    ):
        assert needle in text, f"missing planner series: {needle}\n{text}"

    planner_section = payload["planner"]
    assert planner_section is not None
    assert planner_section["cycles"] == h.controller.cycles > 0
    assert planner_section["decisions"]["scale_up"] >= 1
    assert planner_section["pools"]["backend"]["last_action"]
    assert planner_section["last_plan"] is not None

    print(
        "fleet smoke OK: "
        f"{report.requests} requests, peak {report.peak_replicas} workers, "
        f"{report.scale_ups} scale-up(s), {report.scale_downs} "
        f"scale-down(s), {report.drained_retired} drained, "
        f"0 broken streams, streams byte-identical to the frozen pool, "
        f"planner gauges + /fleet section populated"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
