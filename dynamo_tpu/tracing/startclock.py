"""The worker's start-up clock: one reading per stage boundary.

From the operating system's start of the process to the line "serving
model" a worker is always in exactly one stage (:data:`STAGES`); the end
of one is the start of the next, so a single ``time.perf_counter_ns()``
per boundary partitions that wall time, as :mod:`.stepclock` partitions
the step loop's: the stages' seconds add up to ``total_s``. A stage
entered twice adds up; marking the running stage is no boundary. No
boundary waits for the device: a stage whose device work is still in
flight reads the host's share, and ``device_settle`` (the
``block_until_ready`` the worker makes anyway) reads the wait.

- ``interpreter``: from the process's start (``/proc/self/stat`` field
  22 against ``/proc/uptime``; where ``/proc`` is missing, the clock's
  own opening) to the first line of this package (``dynamo_tpu/
  __init__.py``'s reading): Python itself and whatever a launcher
  imported before any of the program's code.
- ``imports``: until the worker's entry point runs (``jax`` and
  ``dynamo_tpu.engine`` are imported by then).
- ``runtime_connect``: store, lease, status server, the tokenizer's eos.
- ``backend_init``: ``device.require_accelerator``, the TPU client.
- ``weights``: ``init_params`` or a checkpoint's load, quantising.
- ``cache_alloc``: pages, window pool, slabs (``init_cache``).
- ``engine_init``: the rest of ``build_engine``.
- ``device_settle``: ``block_until_ready`` on the weights and the cache.
- ``inventory``: memory statistics, bytes per device, the start-up facts.
- ``warmup``: the serving programs, a row each (below).
- ``waves_timed``: each prefill bucket's wave once more, timed.
- ``register``: KV events, gauges, ``endpoint.serve``, ``register_llm``.

**Compiles are booked where they happen.** ``device.CompileLog`` stays
the one listener of JAX's monitoring events; its ``sink`` is this
clock's :meth:`StartClock.compile_event`, which books every duration and
every persistent-cache hit or miss to the OPEN stage (``after_serving``
once the clock is closed: the reference check's and a window's compiles)
and to the open warm-up row. An event ends now and began ``seconds`` ago;
what lies inside an interval already booked is not booked again (a jit
traced inside a jit fires ``jaxpr_trace_duration`` for both), so in every
row ``trace_s + lower_s + backend_s + tiny_s <= wall_s``. ``tiny_s`` /
``tiny_n`` are the backend compiles quicker than
``CompileLog._LISTED_FROM_SECONDS``, which the log sums but does not
list; ``run_s`` is the rest of a row: first executions, transfers, the
host's planning. A stage also keeps the plain sum of its trace and lower
events (``trace_lower_sum_s``): against ``trace_s + lower_s`` it says how
much a nested trace was counted twice.

The clock of the context is what :func:`mark` moves:
``runtime.worker.dynamo_worker`` opens the process's, ``asyncio.to_thread``
carries it to the build and warm-up threads, and an in-process worker of a
test, which finds none running, opens one of its own (:func:`running`).
This module imports nothing heavy: it is read before ``jax`` is.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from typing import Iterator

import dynamo_tpu
from dynamo_tpu.device import CompileLog

__all__ = ["AFTER_SERVING", "BUILD_STAGES", "STAGES", "WARMUP_STAGES", "StartClock",
           "mark", "open_process_clock", "running"]

STAGES: tuple[str, ...] = (
    "interpreter", "imports", "runtime_connect", "backend_init", "weights",
    "cache_alloc", "engine_init", "device_settle", "inventory", "warmup",
    "waves_timed", "register",
)
# Where compile events land once the clock is closed.
AFTER_SERVING = "after_serving"
# ``startup.build_seconds`` and ``startup.warmup_seconds`` are these stages' sums.
BUILD_STAGES = ("weights", "cache_alloc", "engine_init", "device_settle")
WARMUP_STAGES = ("warmup", "waves_timed")
# Booked intervals kept (each compile adds three at most; an outer trace
# swallows the inner ones it covers): a long-lived server that compiles now
# and then forgets the oldest, which no new event can reach back to.
_BOOKED_KEPT = 4096
# Unlisted programs named in a snapshot, the costliest first.
_TINY_LISTED = 12


def _process_age_s() -> float | None:
    """Seconds since the operating system started this process, or None
    where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        # Field 2, the command, may hold spaces and parentheses: count from
        # its closing one. Field 22 is the start in clock ticks since boot.
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


class _Compiles:
    """What JAX's compile events cost while one stage or row was open, ns."""

    __slots__ = ("trace", "lower", "backend", "tiny", "tiny_n", "hits",
                 "misses", "trace_lower_sum")

    def __init__(self) -> None:
        self.trace = self.lower = self.backend = self.tiny = 0
        self.tiny_n = self.hits = self.misses = 0
        self.trace_lower_sum = 0   # plain sum: nested traces counted twice

    def seconds(self) -> dict:
        return {
            "trace_s": round(self.trace * 1e-9, 6),
            "lower_s": round(self.lower * 1e-9, 6),
            "backend_s": round(self.backend * 1e-9, 6),
            "tiny_s": round(self.tiny * 1e-9, 6),
            "tiny_n": self.tiny_n,
        }

    @property
    def cache(self) -> str:
        return "miss" if self.misses else "hit" if self.hits else "none"


class _Row(_Compiles):
    """One warm-up program: the compiles of its first run and its wall."""

    __slots__ = ("name", "t0", "wall")

    def __init__(self, name: str, t0: int):
        super().__init__()
        self.name = name
        self.t0 = t0
        self.wall = 0

    def seconds(self) -> dict:
        out = {"name": self.name, "wall_s": round(self.wall * 1e-9, 6),
               **super().seconds(), "cache": self.cache}
        rest = self.wall - self.trace - self.lower - self.backend - self.tiny
        out["run_s"] = round(rest * 1e-9, 6)
        return out


class StartClock:
    """``mark``, ``row`` and ``close`` are called by whichever thread does
    the stage's work (one at a time: each hop is awaited),
    ``compile_event`` by the thread that compiles, ``snapshot`` by any."""

    def __init__(self, first_line_ns: int | None = None, from_proc: bool = False):
        """Opened now, in ``imports``. ``first_line_ns``: the reading at the
        program's first line, where ``interpreter`` ended; ``from_proc``
        puts the start at the process's own (else, and where ``/proc`` is
        missing, at ``first_line_ns``)."""
        now = time.perf_counter_ns()
        first = now if first_line_ns is None else min(first_line_ns, now)
        age = _process_age_s() if from_proc else None
        start = first if age is None else min(first, now - int(age * 1e9))
        self._lock = threading.Lock()
        self.process_start_unix = time.time() - (now - start) * 1e-9
        self._ns = dict.fromkeys(STAGES, 0)
        self._ns["interpreter"] = first - start
        self._phase: str | None = "imports"
        self._t = first               # reading that opened _phase
        self._compiles: dict[str, _Compiles] = {}
        self._rows: list[_Row] = []
        self._row: _Row | None = None
        self._booked: list[tuple[int, int]] = []   # disjoint, ascending
        self._tiny: dict[str, list[int]] = {}      # program -> [count, ns]

    # -- boundaries --------------------------------------------------------

    @property
    def stage_now(self) -> str | None:
        """The running stage (None once serving)."""
        return self._phase

    def mark(self, stage: str) -> None:
        """Boundary: the running stage ends, ``stage`` begins. Nothing
        once the clock is closed, nor when ``stage`` is running already."""
        if stage not in self._ns:
            raise ValueError(f"no start-up stage {stage!r}; known: {STAGES}")
        now = time.perf_counter_ns()
        with self._lock:
            if self._phase is None or stage == self._phase:
                return
            self._ns[self._phase] += now - self._t
            self._phase = stage
            self._t = now

    def close(self) -> None:
        """"serving model": the running stage ends and no other begins."""
        now = time.perf_counter_ns()
        with self._lock:
            if self._phase is None:
                return
            self._ns[self._phase] += now - self._t
            self._phase = None

    @contextlib.contextmanager
    def row(self, name: str) -> Iterator[None]:
        """One warm-up program's row, open for the body."""
        row = _Row(name, time.perf_counter_ns())
        with self._lock:
            self._row = row
        try:
            yield
        finally:
            now = time.perf_counter_ns()
            with self._lock:
                row.wall = now - row.t0
                self._row = None
                self._rows.append(row)

    # -- the compile log's sink --------------------------------------------

    def compile_event(self, kind: str, name: str, seconds: float) -> None:
        """One of JAX's compile events, from ``device.CompileLog``:
        ``kind`` is ``trace``, ``lower``, ``backend`` (a compile or a
        persistent-cache load), ``hit`` or ``miss``."""
        now = time.perf_counter_ns()
        with self._lock:
            stage = self._phase or AFTER_SERVING
            scope = self._compiles.get(stage)
            if scope is None:
                scope = self._compiles[stage] = _Compiles()
            row = self._row
            scopes = ((scope, 0),) if row is None else ((scope, 0), (row, row.t0))
            if kind == "hit":
                for s, _ in scopes:
                    s.hits += 1
                return
            if kind == "miss":
                for s, _ in scopes:
                    s.misses += 1
                return
            ns = int(seconds * 1e9)
            fresh = self._book(now - ns, now)
            if kind in ("trace", "lower"):
                scope.trace_lower_sum += ns
            elif seconds < CompileLog._LISTED_FROM_SECONDS:
                kind = "tiny"
            for s, since in scopes:
                # a row books what lies inside it
                booked = sum(b - max(a, since) for a, b in fresh if b > since)
                setattr(s, kind, getattr(s, kind) + booked)
                if kind == "tiny":
                    s.tiny_n += 1
            if kind == "tiny":
                entry = self._tiny.setdefault(name, [0, 0])
                entry[0] += 1
                entry[1] += ns

    def _book(self, a: int, b: int) -> list[tuple[int, int]]:
        """Add ``[a, b]`` to the booked intervals; returns its parts that
        were not among them."""
        ivs = self._booked
        i = len(ivs)
        while i > 0 and ivs[i - 1][1] > a:   # ends ascend: a suffix overlaps
            i -= 1
        fresh, cur, j = [], a, i
        while j < len(ivs) and ivs[j][0] < b:
            s, e = ivs[j]
            if s > cur:
                fresh.append((cur, s))
            cur = max(cur, e)
            j += 1
        if cur < b:
            fresh.append((cur, b))
        if i < j:
            a, b = min(a, ivs[i][0]), max(b, ivs[j - 1][1])
        ivs[i:j] = [(a, b)]
        if len(ivs) > _BOOKED_KEPT:
            del ivs[: _BOOKED_KEPT // 2]
        return fresh

    # -- readers -----------------------------------------------------------

    def seconds(self, *stages: str) -> float:
        """Seconds of ``stages`` together, the running one counted up to
        now."""
        now = time.perf_counter_ns()
        with self._lock:
            ns = sum(self._ns[s] for s in stages)
            if self._phase in stages:
                ns += now - self._t
        return ns * 1e-9

    def row_walls(self) -> dict[str, float]:
        """Wall seconds by warm-up program, as ``startup.warmup_phases``
        rounds them."""
        with self._lock:
            return {r.name: round(r.wall * 1e-9, 2) for r in self._rows}

    def snapshot(self) -> dict:
        """``/health`` ``startup.clock``."""
        now = time.perf_counter_ns()
        with self._lock:
            phase = self._phase
            ns = dict(self._ns)
            if phase is not None:
                ns[phase] += now - self._t
            rows = [r.seconds() for r in self._rows]
            tiny = sorted(self._tiny.items(), key=lambda kv: -kv[1][1])[:_TINY_LISTED]
            by_stage = {
                stage: {**c.seconds(), "cache_hits": c.hits, "cache_misses": c.misses,
                        "trace_lower_sum_s": round(c.trace_lower_sum * 1e-9, 6)}
                for stage, c in self._compiles.items()
            }
        by_stage.setdefault(AFTER_SERVING, {
            **_Compiles().seconds(), "cache_hits": 0, "cache_misses": 0,
            "trace_lower_sum_s": 0.0})
        stages = {s: round(v * 1e-9, 6) for s, v in ns.items()}

        def of_rows(*keys: str) -> float:
            return round(sum(r[k] for r in rows for k in keys), 6)

        return {
            "process_start_unix": self.process_start_unix,
            "stage_now": phase,
            "stages": stages,
            "total_s": round(sum(ns.values()) * 1e-9, 6),
            "programs": rows,
            "compile_by_stage": by_stage,
            # where the unlisted compiles are: [program, count, seconds]
            "tiny_programs": [[name, n, round(t * 1e-9, 3)] for name, (n, t) in tiny],
            # sums of stages or of rows, as single keys: a reader follows
            # one path to one number
            "sums": {
                "process_s": round((ns["interpreter"] + ns["imports"]) * 1e-9, 6),
                "register_s": round((ns["runtime_connect"] + ns["register"]) * 1e-9, 6),
                "warmup_trace_lower_s": of_rows("trace_s", "lower_s"),
                "warmup_backend_s": of_rows("backend_s"),
                "warmup_tiny_compile_s": of_rows("tiny_s"),
                "warmup_run_s": of_rows("run_s"),
            },
        }

    def table(self) -> str:
        """The start-up as one table, for the worker's log."""
        snap = self.snapshot()
        lines = [f"start-up: {snap['total_s']:.1f} s from the process's start"]
        lines.append(f"  {'stage':<16}{'s':>8}{'trace':>8}{'lower':>8}"
                     f"{'backend':>8}{'tiny (n)':>14}")
        for stage, s in snap["stages"].items():
            c = snap["compile_by_stage"].get(stage)
            cols = (f"{c['trace_s']:8.1f}{c['lower_s']:8.1f}{c['backend_s']:8.1f}"
                    f"{c['tiny_s']:8.1f} ({c['tiny_n']:3d})") if c else ""
            lines.append(f"  {stage:<16}{s:8.2f}{cols}")
        if snap["programs"]:
            lines.append(f"  {'warm-up program':<30}{'wall':>7}{'trace':>7}{'lower':>7}"
                         f"{'backend':>8}{'cache':>6}{'tiny (n)':>13}{'run':>7}")
        for r in snap["programs"]:
            lines.append(
                f"  {r['name']:<30}{r['wall_s']:7.1f}{r['trace_s']:7.1f}"
                f"{r['lower_s']:7.1f}{r['backend_s']:8.1f}{r['cache']:>6}"
                f"{r['tiny_s']:7.1f} ({r['tiny_n']:3d}){r['run_s']:7.1f}")
        return "\n".join(lines)


# -- the clock of the context ------------------------------------------------

_CURRENT: contextvars.ContextVar[StartClock | None] = contextvars.ContextVar(
    "dynamo_start_clock", default=None)


def open_process_clock() -> StartClock:
    """The process's clock, from the operating system's start of it, made
    the context's: called once, by the worker's entry point."""
    clock = StartClock(dynamo_tpu.FIRST_LINE_NS, from_proc=True)
    _CURRENT.set(clock)
    return clock


def current() -> StartClock | None:
    """The context's clock while it runs, else None."""
    clock = _CURRENT.get()
    return clock if clock is not None and clock.stage_now is not None else None


def running() -> StartClock:
    """The context's clock while it runs (the process's, in a worker's own
    process), else one opened now and made the context's (an in-process
    worker of a test: no interpreter and no imports of its own)."""
    clock = current()
    if clock is None:
        clock = StartClock()
        _CURRENT.set(clock)
    return clock


def mark(stage: str) -> None:
    """A boundary on the context's clock; nothing where none is open (an
    engine built outside a worker)."""
    clock = _CURRENT.get()
    if clock is not None:
        clock.mark(stage)
