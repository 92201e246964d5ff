"""What the process runs on, said out loud: device identity, the
no-silent-CPU guard, published peak rates, the persistent compile cache,
and a per-program compile-time log.

Every entry point that compiles for the chip (the JAX worker,
tools/attn_decode_bench.py, chip_smoke.py's children) calls
:func:`enable_compile_cache` first and names its device with
:func:`device_info`; measurement paths take their peaks from
:func:`device_peaks`, which raises on a device nobody has looked up.

JAX is imported inside the functions: importing this module never
initialises a backend (one process per chip — a launcher that merely
imports helpers must not take the chip from the child it starts).
"""

from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass
from pathlib import Path

# Fixed in-checkout location of the persistent compile cache (git-ignored).
# The directory is part of what a cache lookup depends on, so it never
# carries a temp name, pid or timestamp: a second start in the same
# checkout finds what the first one compiled.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    function sets no path in code. Unset: ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` exactly as JAX reports them."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def cpu_requested() -> bool:
    """True when the process was explicitly pointed at the CPU:
    ``JAX_PLATFORMS=cpu`` in its environment, or the ``jax_platforms``
    config update that ``--local-cpu-devices`` performs (both surface as
    the same config value)."""
    import jax

    first = (jax.config.jax_platforms or "").split(",")[0]
    return first.strip().lower() == "cpu"


def require_accelerator(what: str) -> dict:
    """Device identity, or RuntimeError when JAX found no TPU and nobody
    asked for the CPU. JAX itself falls back to the CPU with a warning
    when libtpu finds no chip; a server or a benchmark that carried on
    from there would report CPU behaviour under a device's name."""
    info = device_info()
    if info["platform"] != "tpu" and not cpu_requested():
        raise RuntimeError(
            f"{what}: JAX found no TPU (platform={info['platform']!r}, "
            f"device_kind={info['kind']!r}). Refusing to continue on a "
            "fallback device; set JAX_PLATFORMS=cpu (or pass "
            "--local-cpu-devices) to run on the CPU on purpose."
        )
    return info


def memory_stats() -> list[dict]:
    """Per-device ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``
    where the backend reports them (the CPU backend reports none)."""
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": st.get("bytes_in_use"),
            "peak_bytes_in_use": st.get("peak_bytes_in_use"),
            "bytes_limit": st.get("bytes_limit"),
        })
    return out


def bytes_per_device(tree) -> dict[int, int]:
    """Bytes of a pytree's arrays resident on each local device, summed
    over ``addressable_shards`` — shows whether parameters and cache are
    really split over a mesh or replicated on every chip."""
    import jax

    held: dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            held[shard.device.id] = (
                held.get(shard.device.id, 0) + shard.data.nbytes
            )
    return dict(sorted(held.items()))


# -- published peak rates ----------------------------------------------------


@dataclass(frozen=True)
class Peaks:
    bf16_tflops: float
    int8_tops: float
    hbm_gbps: float
    hbm_gb: float
    source: str


# Keyed by ``jax.devices()[0].device_kind``. A device that is not here is
# an error, not a default: a roofline share against another chip's peak is
# a wrong number with a right-looking name.
DEVICE_PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_tflops=197.0, int8_tops=393.0, hbm_gbps=819.0, hbm_gb=16.0,
        source='Google Cloud documentation, "TPU v5e" (per chip)',
    ),
}


def device_peaks(device_kind: str) -> Peaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for device_kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}. Add the chip to "
            "dynamo_tpu.device.DEVICE_PEAKS with its source before "
            "computing a roofline share on it."
        ) from None


# -- compile-time log ----------------------------------------------------------


class CompileLog:
    """Seconds JAX spent compiling each program, from JAX's own
    monitoring events (``backend_compile_duration`` carries the jitted
    function's name and covers a persistent-cache load as well as a real
    compile), the host time spent tracing and lowering (Python-unrolled
    layers make that visible), plus persistent-cache hit and miss
    counts. Host-side listeners only; nothing is added to a dispatch."""

    # JAX's duration events by what they time
    _KINDS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    _CACHE = {
        "/jax/compilation_cache/cache_hits": "hit",
        "/jax/compilation_cache/cache_misses": "miss",
    }
    # Programs quicker than this are summed but not listed one by one
    # (every jnp op outside a jit is a tiny program of its own).
    _LISTED_FROM_SECONDS = 0.5

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: list[tuple[str, float]] = []
        self._total = 0.0
        self._trace_lower = 0.0
        self._hits = 0
        self._misses = 0
        # ``sink(kind, name, seconds)`` hears every event this log counts
        # (kind: a value of _KINDS or _CACHE), after it is counted: the
        # start-up clock books them by stage (tracing/startclock.py).
        self.sink = None

    def install(self) -> "CompileLog":
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        kind = self._KINDS.get(event)
        if kind is None:
            return
        name = str(kw.get("fun_name", "?"))
        with self._lock:
            if kind != "backend":
                self._trace_lower += seconds
            else:
                self._total += seconds
                if seconds >= self._LISTED_FROM_SECONDS:
                    self._programs.append((name, round(seconds, 2)))
        if self.sink is not None:
            self.sink(kind, name, seconds)

    def _on_event(self, event: str, **kw) -> None:
        kind = self._CACHE.get(event)
        if kind is None:
            return
        with self._lock:
            if kind == "hit":
                self._hits += 1
            else:
                self._misses += 1
        if self.sink is not None:
            self.sink(kind, "", 0.0)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "total_seconds": round(self._total, 2),
                "trace_lower_seconds": round(self._trace_lower, 2),
                "programs": [list(p) for p in self._programs],
                "cache_hits": self._hits,
                "cache_misses": self._misses,
            }


@functools.cache
def compile_log() -> CompileLog:
    """The process-wide log (JAX's monitoring listeners are process-wide
    and cannot be removed one by one, so there is one of these)."""
    return CompileLog().install()
