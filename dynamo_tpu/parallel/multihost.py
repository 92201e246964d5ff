"""Multi-host bootstrap and helpers: one global device mesh over many
processes.

The reference serves multi-node models by plumbing engine flags
(`/root/reference/components/backends/sglang/docs/multinode-examples.md:10`
— ``dist-init-addr``, ``nnodes``, ``node-rank``); the engines' NCCL/MPI
stacks do the rest. Here the equivalent is first-party and TPU-native:
``jax.distributed`` forms the multi-controller runtime, the engine's mesh
spans every process's chips (`jax.devices()` is global after init), and
XLA/GSPMD inserts the ICI/DCN collectives. Every process runs the same
jitted programs in the same order (classic JAX SPMD); the worker CLI's
leader/follower step replication (backends/jax/multihost.py) keeps the
host-side schedulers in lockstep.
"""

from __future__ import annotations

import logging

import numpy as np

log = logging.getLogger("dynamo_tpu.multihost")


def init_multihost(
    coordinator: str,
    num_processes: int,
    process_id: int,
    local_cpu_devices: int | None = None,
) -> None:
    """Join the multi-controller runtime. Call BEFORE any other jax use.

    ``local_cpu_devices`` forces the CPU platform with that many virtual
    devices per process — the cluster-free validation mode (a 2-process x
    4-device CPU "pod"); on real TPU hosts leave it None and the local
    chips attach themselves. Mirrors the reference's dist-init-addr /
    nnodes / node-rank worker flags (multinode-examples.md:10).
    """
    import jax

    if local_cpu_devices:
        force_cpu_devices(local_cpu_devices)
    jax.distributed.initialize(
        coordinator, num_processes=num_processes, process_id=process_id
    )
    log.info(
        "multihost runtime up: process %d/%d, %d local / %d global devices",
        process_id, num_processes,
        len(jax.local_devices()), len(jax.devices()),
    )


def force_cpu_devices(n: int) -> None:
    """Virtual-device validation mode: N CPU devices stand in for a
    multi-chip host. The config update has the same effect as
    ``JAX_PLATFORMS=cpu`` in the environment (which libtpu honours) but
    also wins over an ambient ``JAX_PLATFORMS=tpu,cpu``. Call BEFORE any
    other jax use."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))


def fetch_replicated(x) -> np.ndarray:
    """Host value of a program output on a (possibly multi-host) mesh.

    Single-host arrays fetch directly. On a mesh spanning processes the
    array is not fully addressable; a REPLICATED output still has the
    full value in every local shard, which is what the engine's
    scheduler needs — identical on every host. A sharded output would
    silently hand each host a partial view, so that is a hard error."""
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    shard = x.addressable_shards[0]
    if tuple(shard.data.shape) != tuple(x.shape):
        raise RuntimeError(
            f"multi-host fetch of a non-replicated output: global shape "
            f"{tuple(x.shape)}, local shard {tuple(shard.data.shape)} — "
            "the program must produce replicated host-visible outputs"
        )
    return np.asarray(shard.data)


def start_host_copy(x) -> None:
    """Enqueue an async device->host copy of one array (no-op on arrays
    that don't support it, e.g. plain numpy): the later blocking fetch
    then lands data that has been streaming in the background instead of
    paying the full transfer at the sync point."""
    fn = getattr(x, "copy_to_host_async", None)
    if fn is not None:
        fn()


def fetch_replicated_many(arrays) -> list[np.ndarray]:
    """Batched host fetch: start async D2H copies for EVERY array first,
    then land them in order — the transfers overlap each other (and any
    still-running device work) instead of serializing one blocking fetch
    per array. Used for the sampler's (chosen, top_ids, top_lps) logprob
    tuple, which the engine previously fetched as three serial syncs."""
    arrs = list(arrays)
    for a in arrs:
        start_host_copy(a)
    return [fetch_replicated(a) for a in arrs]
