"""The engine loop's step clock: one reading per phase boundary.

The engine thread is always in exactly one phase (:data:`PHASES`); the
end of one phase is the start of the next, so a single
``time.perf_counter_ns()`` per boundary partitions the loop's wall time:
from any scrape to any later one the phases' seconds add up to the time
elapsed. Each boundary does two things:

- adds the closed phase's duration to cumulative counters — always on,
  exported as ``dynamo_engine_step_phase_seconds_total{phase, blocks}``
  (``blocks`` says what the engine thread was held up by: its own host
  work, a wait for the device, or no work at all);
- closes the phase's ``jax.profiler.TraceAnnotation`` and opens the next,
  so when a profile is being taken the phases lie in the ``.xplane.pb``
  on the same clock as the device ops, as ``engine/<phase>`` events
  nested in one ``engine/step`` event per ``step()`` call. Outside a
  profile an annotation is a ~0.4 µs no-op.

The two phases between ``step()`` calls (``between_steps``, ``no_work``)
run across threads (``asyncio.to_thread`` hands each step to a pool
thread), so they carry no annotation of their own: the next
``engine/step`` event names the gap before it in its ``after`` stat and
a reduction derives the gap from the space between two step events —
the same two readings the counters use.

Stat spans that cover whole runs of phases (``engine_plan``,
``engine_commit``) are filed from these readings too
(:meth:`StepClock.close_at_next`) instead of timing the same intervals a
second time.
"""

from __future__ import annotations

import time
from typing import Any

from jax.profiler import TraceAnnotation

__all__ = ["PHASES", "StepClock"]

# phase -> what the engine thread is blocked by while in it
PHASES: dict[str, str] = {
    "no_work": "no_work",
    "between_steps": "host",
    "admit": "host",
    "plan": "host",
    "assemble": "host",
    "h2d": "host",
    "dispatch": "host",
    "land": "device_wait",
    "commit": "host",
}


class StepClock:
    """Owned by one engine core; ``mark`` is called from the thread that
    runs ``step()`` (under the step lock), :meth:`seconds` from any."""

    def __init__(self, tracer: Any = None):
        self._tracer = tracer
        self._ns = dict.fromkeys(PHASES, 0)
        self._phase: str | None = None   # None until the first step
        self._t = 0                      # reading that opened _phase
        self._ann: TraceAnnotation | None = None
        self._step_ann: TraceAnnotation | None = None
        self._open_spans: list[tuple[str, int, dict | None]] = []
        # perf_counter_ns -> time.time() seconds, for the stat spans
        # (Span.start_s is wall-clock so processes order in a waterfall).
        self._epoch = time.time() - time.perf_counter_ns() * 1e-9

    @property
    def phase(self) -> str | None:
        """The running phase (None before the first step)."""
        return self._phase

    # -- boundaries --------------------------------------------------------

    def _switch(self, phase: str) -> int:
        now = time.perf_counter_ns()
        prev = self._phase
        if prev is not None:
            self._ns[prev] += now - self._t
        self._phase = phase
        self._t = now
        if self._open_spans:
            self._file_open_spans(now)
        return now

    def step_begin(self) -> int:
        """Entry of ``step()``: closes the gap since the last step and
        opens ``admit``."""
        after = self._phase or "no_work"
        now = self._switch("admit")
        self._step_ann = TraceAnnotation("engine/step", after=after)
        self._step_ann.__enter__()
        self._ann = TraceAnnotation("engine/admit")
        self._ann.__enter__()
        return now

    def mark(self, phase: str, **attrs: Any) -> int:
        """Boundary inside a step: the running phase ends, ``phase``
        begins; returns the reading. ``attrs`` go on the annotation.
        Marking the phase that is running is no boundary: it returns the
        reading that opened it."""
        if self._step_ann is None:
            # Not inside step(): a plan or dispatch driven from outside the
            # loop (tests) keeps no time.
            return time.perf_counter_ns()
        if phase == self._phase:
            return self._t
        now = self._switch(phase)
        self._ann.__exit__(None, None, None)
        self._ann = TraceAnnotation("engine/" + phase, **attrs)
        self._ann.__enter__()
        return now

    def step_end(self, pending: bool) -> int:
        """Exit of ``step()``: what follows is ``between_steps`` when work
        is pending and ``no_work`` when the engine goes idle."""
        now = self._switch("between_steps" if pending else "no_work")
        self._ann.__exit__(None, None, None)
        self._step_ann.__exit__(None, None, None)
        self._ann = self._step_ann = None
        return now

    # -- stat spans from the same readings -----------------------------------

    def close_at_next(self, name: str, start_ns: int, attrs: dict | None = None) -> None:
        """File the stat span ``name`` from ``start_ns`` (a reading this
        clock returned) to the next boundary's reading."""
        if self._tracer is not None and self._step_ann is not None:
            self._open_spans.append((name, start_ns, attrs))

    def _file_open_spans(self, now: int) -> None:
        spans, self._open_spans = self._open_spans, []
        for name, start_ns, attrs in spans:
            self._tracer.record(
                name, self.wall_s(start_ns), self.wall_s(now), attrs=attrs, stat=True,
            )

    def wall_s(self, reading_ns: int) -> float:
        """A reading as ``time.time()`` seconds."""
        return self._epoch + reading_ns * 1e-9

    # -- readers -------------------------------------------------------------

    def seconds(self) -> dict[str, float]:
        """Cumulative seconds per phase, the running phase counted up to
        now: the values add up to the time since the first step began."""
        phase, t = self._phase, self._t
        out = {p: ns * 1e-9 for p, ns in self._ns.items()}
        if phase is not None:
            out[phase] += max(0, time.perf_counter_ns() - t) * 1e-9
        return out

