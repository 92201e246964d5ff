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

**The device's account** is kept from the same readings, a record per
dispatch (:meth:`StepClock.dispatch_begin` -> :meth:`in_flight` ->
:meth:`landed`). A dispatch is *enqueued* at the boundary that ends its
``dispatch`` phase (the jitted call returned) and *finishes* at the
``land`` -> ``commit`` boundary where the blocking fetch waited for it:
the landing then IS the device's finish to within a wake-up. It *starts*
at the later of its enqueue and its predecessor's finish. Where the fetch
did not wait (a **late landing**: the output was ready before the host
came for it, the one case in which the device may have had nothing
queued) the finish is bracketed instead: while a dispatch is in flight
each boundary polls its output's ``is_ready()`` once, so the finish lies
after the last reading that saw it not ready and before the first that saw
it ready. Both ends are kept: the seconds the device *starved* between a
finish and the next enqueue have a lower and an upper bound, equal
wherever the landing waited. Always on, exported by
:meth:`StepClock.account`:

- device seconds by the dispatch's ``kind`` (a late landing counts up to
  the upper end of its finish) and late landings by kind;
- starved seconds by bound, by the phase they lay under (exact overlap
  with the boundary readings; ``no_work`` is not starvation) and by the
  kind of the dispatch whose end began them (``after``);
- lane-seconds of decode-ready lanes: ``decode`` (lanes a dispatch carried
  x its device seconds), ``behind_prefill`` (lanes a wave or a mixed step
  left waiting x its device seconds), ``behind_host`` (decode-ready lanes
  x the starved seconds, upper bound, before the dispatch).

The ``engine/commit`` annotation opened at a landing carries the record
just closed (``no``, ``kind``, ``device_ms``, ``starved_lower_ms``,
``starved_upper_ms``, ``late``) and ``engine/dispatch`` its ``no``, so
that on a profile each estimate lies beside the program it describes.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any

from jax.profiler import TraceAnnotation

__all__ = ["KINDS", "LANE_STATES", "PHASES", "StepClock"]

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


# the ``kind`` of a dispatch, as its annotation names it
KINDS = ("prefill", "megastep", "decode", "mixed")
# Between boundaries (:meth:`StepClock.poll`) an output is polled once a
# millisecond at most: a late landing's bounds then lie ~1 ms apart, which
# is all the account needs, at a handful of ``is_ready()`` a phase (0.28 us
# a call alone on a v5e; with one before each of ``h2d``'s 13-16 transfers
# the phase read 0.23-0.29 ms longer in both dense cells).
_POLL_EVERY_NS = 1_000_000
LANE_STATES = ("decode", "behind_prefill", "behind_host")


class _Dispatch:
    """One dispatch between the mark that opened its ``dispatch`` phase
    and its landing. Readings in ns; 0 = not taken."""

    __slots__ = ("no", "kind", "carried", "waiting", "enq", "out",
                 "not_ready_at", "ready_at")

    def __init__(self, no: int, kind: str, carried: int, waiting: int):
        self.no = no
        self.kind = kind
        self.carried = carried        # decode-ready lanes it carries
        self.waiting = waiting        # decode-ready lanes it leaves waiting
        self.enq = 0                  # the reading that ended ``dispatch``
        self.out: Any = None          # an output array to poll
        self.not_ready_at = 0         # last reading at which it was seen not ready
        self.ready_at = 0             # first reading at which it was seen ready


class StepClock:
    """Owned by one engine core; ``mark`` is called from the thread that
    runs ``step()`` (under the step lock), :meth:`seconds` and
    :meth:`account` from any."""

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
        # The device's account (module docstring). ``_marks``: the newest
        # boundaries ``(reading, phase begun)``, a dozen a step; a starved
        # interval is laid over them when its later end's dispatch lands,
        # at most a step after it began.
        self._marks: deque[tuple[int, str]] = deque(maxlen=256)
        self._opening: _Dispatch | None = None   # in its ``dispatch`` phase
        self._open: deque[_Dispatch] = deque()   # enqueued, not landed, oldest first
        self._last: tuple[str, int, int] | None = None  # (kind, finish lower, upper)
        self._polled = 0                         # reading of the newest poll
        self._device_ns = dict.fromkeys(KINDS, 0)
        self._late = dict.fromkeys(KINDS, 0)
        self._lane_ns = dict.fromkeys(LANE_STATES, 0)
        self._starved_ns = {
            (bound, phase, after): 0
            for bound in ("lower", "upper")
            for phase in PHASES if phase != "no_work"
            for after in KINDS
        }

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
        self._marks.append((now, phase))
        if self._open_spans:
            self._file_open_spans(now)
        d = self._opening
        if d is not None:
            # The jitted call returned: the dispatch is enqueued.
            d.enq = now
            self._opening = None
            self._open.append(d)
        if self._open:
            self._poll(now)
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

    # -- the device's account ---------------------------------------------

    def dispatch_begin(self, no: int, kind: str, carried: int, waiting: int,
                       **attrs: Any) -> int:
        """Open the ``dispatch`` phase of dispatch ``no`` (its annotation
        carries ``no``, ``kind`` and ``attrs``) and its record: the next
        boundary is its enqueue. ``carried`` / ``waiting``: decode-ready
        lanes it carries / leaves waiting. Outside a step: no record."""
        now = self.mark("dispatch", no=no, kind=kind, **attrs)
        if self._step_ann is not None:
            self._opening = _Dispatch(no, kind, carried, waiting)
        return now

    def in_flight(self, no: int, out: Any) -> None:
        """``out`` (anything with ``is_ready()``) is an output of
        dispatch ``no``: what the boundaries poll until it lands."""
        d = self._opening
        if d is None and self._open:
            d = self._open[-1]
        if d is not None and d.no == no:
            d.out = out

    def poll(self) -> None:
        """A reading of its own inside a long phase (the planner's loops
        over the lanes, the transfers of ``h2d``): with a dispatch in
        flight and not yet seen ready, one more ``is_ready()`` if the last
        was a millisecond ago, so that a late landing's finish is
        bracketed by less than the phase."""
        if self._open:
            now = time.perf_counter_ns()
            if now - self._polled >= _POLL_EVERY_NS:
                self._poll(now)

    def _poll(self, now: int) -> None:
        """One ``is_ready()`` of the oldest dispatch not yet seen ready
        (its successors cannot finish before it)."""
        self._polled = now
        for d in self._open:
            if d.ready_at:
                continue
            if d.out is not None:
                if d.out.is_ready():
                    # a reading AFTER the poll: the finish is before it
                    d.ready_at = time.perf_counter_ns()
                else:
                    d.not_ready_at = now
            return

    def landed(self, no: int) -> int:
        """The blocking fetch of dispatch ``no`` returned: ``land`` ends
        and ``commit`` begins, as ``mark("commit")`` would have it, and
        the record closes (with any older one left open) onto the
        counters and the ``engine/commit`` annotation."""
        if self._step_ann is None:
            return time.perf_counter_ns()
        closing = []
        while self._open and self._open[0].no <= no:
            closing.append(self._open.popleft())
        now = self._switch("commit")
        attrs: dict[str, Any] = {}
        for d in closing:
            attrs = self._close(d, now)
        self._ann.__exit__(None, None, None)
        self._ann = TraceAnnotation("engine/commit", **attrs)
        self._ann.__enter__()
        return now

    def _close(self, d: _Dispatch, now: int) -> dict[str, Any]:
        late = d.ready_at != 0
        if late:
            # Seen ready before the host came for it: the finish lies
            # between two polls.
            fin_lo, fin_hi = max(d.not_ready_at, d.enq), d.ready_at
        else:
            # The fetch waited: its return is the finish.
            fin_lo = fin_hi = now
        start = d.enq
        lower = upper = 0
        if self._last is not None:
            after, prev_lo, prev_hi = self._last
            start = max(start, prev_hi)
            fin_lo = max(fin_lo, prev_lo)
            if d.enq > prev_lo:
                upper = self._starved(prev_lo, d.enq, "upper", after)
                if d.enq > prev_hi:
                    lower = self._starved(prev_hi, d.enq, "lower", after)
        fin_hi = max(fin_hi, start)
        fin_lo = min(fin_lo, fin_hi)
        device = fin_hi - start
        self._device_ns[d.kind] += device
        if late:
            self._late[d.kind] += 1
        lanes = self._lane_ns
        lanes["decode"] += d.carried * device
        lanes["behind_prefill"] += d.waiting * device
        lanes["behind_host"] += (d.carried + d.waiting) * upper
        self._last = (d.kind, fin_lo, fin_hi)
        return {"no": d.no, "kind": d.kind, "device_ms": device * 1e-6,
                "starved_lower_ms": lower * 1e-6,
                "starved_upper_ms": upper * 1e-6, "late": int(late)}

    def _starved(self, a: int, b: int, bound: str, after: str) -> int:
        """Count the part of ``[a, b]`` (nothing queued on the device)
        that lay under a phase in which the engine had work, by phase;
        returns its ns."""
        total, end = 0, b
        for t, phase in reversed(self._marks):
            if t >= end:
                continue
            begin = max(t, a)
            if phase != "no_work":
                self._starved_ns[bound, phase, after] += end - begin
                total += end - begin
            end = begin
            if t <= a:
                break
        return total

    def account(self) -> dict[str, dict]:
        """The device's account so far, seconds (module docstring):
        ``device_seconds`` and ``late_landings`` by kind,
        ``starved_seconds`` by ``(bound, phase, after)``, ``lane_seconds``
        by state. A dispatch counts when it lands."""
        return {
            "device_seconds": {k: ns * 1e-9 for k, ns in self._device_ns.items()},
            "late_landings": dict(self._late),
            "starved_seconds": {k: ns * 1e-9 for k, ns in self._starved_ns.items()},
            "lane_seconds": {k: ns * 1e-9 for k, ns in self._lane_ns.items()},
        }

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

