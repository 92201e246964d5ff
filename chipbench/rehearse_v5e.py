"""Compile the serving programs of a configuration at its real widths for
a TPU v5e that is described and not attached, and print what the
compiler says each needs (``memory_analysis()``). Nothing runs, no chip
time is spent; what the chip's compiler would refuse, it refuses here.

    JAX_PLATFORMS=cpu python -m chipbench.rehearse_v5e qwen2.5-1.5b-bf16 [--quick]

How: the host-side argument shapes of a program (token buffers, block
tables, sampling arrays) depend on the engine settings and not on the
model's size. So a proxy engine with a one-layer toy model and the
configuration's own engine settings runs the program's warm-up on the
CPU, and the arguments of every ``_prefill_and_sample`` and
``_megastep_body`` dispatch are recorded as shapes. Each recorded call is
then lowered again with the real model's parameter and cache shapes
(``jax.eval_shape``: no array is made) on the described device, with
``jax.default_backend`` steered to "tpu" for the duration so that the
attention dispatch takes its Pallas branch, as it does on the chip.

A compile that passes is not a chip run. The figures go into the
configuration files' ``assumed.serve`` and PERF.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def describe_v5e():
    """The described topology; called from :func:`main` (or a test's
    fixture), never at import."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


class _Recorded(Exception):
    """Raised in place of running a large program on the CPU."""


def record_calls(engine_cfg, quick: bool):
    """[(program name, args as ShapeDtypeStructs, static kwargs)], one per
    distinct shape: the waves the worker's warm-up drives
    (engine/warmup.py: every prefill bucket one wave can fill, every
    decode width ``max_num_seqs`` reaches; sampled and greedy), offered to
    a proxy engine on the CPU. A recorded prefill of more than 512 tokens
    is not run (the CPU's reference attention would need > 100 GB for a
    T=8192 wave): the proxy is abandoned there and the next wave gets a
    new one."""
    import jax
    import numpy as np

    from dynamo_tpu.engine import EngineCore, ModelConfig
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    toy = ModelConfig(name="proxy", vocab_size=512, hidden_size=128,
                      intermediate_size=256, num_layers=1, num_heads=2,
                      num_kv_heads=1, head_dim=128, tie_embeddings=True)
    proxy_engine = dataclasses.replace(
        engine_cfg, num_kv_blocks=min(engine_cfg.num_kv_blocks, 640))
    calls, seen = [], set()

    def recording(name, fn, stop_after):
        def wrapper(*args, **kw):
            spec = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args[2:])
            key = (name, str(spec), tuple(sorted(kw.items())))
            if key not in seen:
                seen.add(key)
                calls.append((name, spec, dict(kw)))
            if name == stop_after:
                raise _Recorded
            return fn(*args, **kw)
        return wrapper

    def offer(n: int, length: int, max_tokens: int, temperature: float, stop_after: str):
        core = EngineCore(toy, proxy_engine, seed=0)
        core._prefill = recording("_prefill_and_sample", core._prefill, stop_after)
        core._decode = recording("_megastep_body", core._decode, stop_after)
        rng = np.random.RandomState(0)
        seqs = [core.add_request(PreprocessedRequest(
            model="proxy", token_ids=rng.randint(1, 512, size=length).tolist(),
            request_id=f"r{i}",
            sampling=SamplingOptions(temperature=temperature, seed=i),
            stop=StopConditions(max_tokens=max_tokens, ignore_eos=True)))
            for i in range(n)]
        try:
            while any(s.finish is None for s in seqs):
                core.step()
        except _Recorded:
            pass

    eng = engine_cfg
    k, bs = eng.megastep, eng.block_size
    lanes = min(eng.max_num_seqs, eng.max_waiting or eng.max_num_seqs)
    for temperature in (1.0, 0.0):
        prev = 0
        for bucket in eng.prefill_buckets:
            n = min(eng.prefill_batch, lanes)
            length = min(bucket // n, eng.max_model_len - k - 2)
            if n * length <= prev:
                break
            offer(n, length, 1, temperature, "_prefill_and_sample")
            prev = bucket
        prev = 0
        for width in eng.decode_buckets:
            n = min(width, lanes)
            if n <= prev:
                break
            offer(n, bs, 1 + k, temperature, "_megastep_body")
            prev = width
    if quick:   # the largest prefill and the widest megastep, sampled
        big = {}
        for c in calls:
            if not c[2].get("all_greedy"):
                big[c[0]] = c
        calls = list(big.values())
    return calls


def compile_calls(config_name: str, quick: bool, device=None) -> list[dict]:
    import jax
    from jax.sharding import SingleDeviceSharding

    from chipbench.configs import engine_overrides, load_config, model_fields
    from dynamo_tpu.engine import EngineConfig, ModelConfig
    from dynamo_tpu.engine import core as core_mod
    from dynamo_tpu.engine.model import init_cache, init_params, init_params_quantized

    cfg = load_config(config_name)
    model_cfg = ModelConfig(**model_fields(cfg))
    engine_cfg = EngineConfig(**engine_overrides(cfg))
    device = device or describe_v5e().devices[0]
    here = SingleDeviceSharding(device)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), tree)
    key = jax.ShapeDtypeStruct((2,), "uint32")
    init = init_params_quantized if cfg["serve"].get("quant") == "int8" else init_params
    params = place(jax.eval_shape(lambda k: init(k, model_cfg), key))
    cache = place(jax.eval_shape(lambda: init_cache(model_cfg, engine_cfg)))
    bytes_of = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    print(f"{config_name}: params {bytes_of(params):,} B, cache {bytes_of(cache):,} B "
          f"({engine_cfg.num_kv_blocks} blocks x {engine_cfg.block_size} tokens)")
    programs = {
        "_prefill_and_sample": (core_mod._prefill_and_sample,
                                ("need_mask", "all_greedy", "want_logprobs", "want_mm")),
        "_megastep_body": (core_mod._megastep_body,
                           ("n_steps", "need_mask", "all_greedy", "want_logprobs")),
    }
    out = []
    for name, spec, statics in record_calls(engine_cfg, quick):
        fn, static_names = programs[name]
        jitted = jax.jit(
            core_mod._program(fn, cfg=model_cfg, engine=engine_cfg, mesh=None),
            static_argnames=static_names, donate_argnums=(1,))
        t0 = time.perf_counter()
        with mock.patch("jax.default_backend", return_value="tpu"):
            lowered = jitted.lower(params, cache, *place(spec), **statics)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        row = {
            "program": name, "statics": statics,
            "tokens_shape": list(jax.tree.leaves(spec)[0].shape),
            "mosaic_calls": lowered.as_text().count("tpu_custom_call"),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "compile_s": round(time.perf_counter() - t0, 1),
        }
        # What the device must hold while this program runs: arguments
        # (params + cache + small inputs; the donated cache is aliased to
        # the output), outputs that are not aliases, and temporaries.
        row["resident_bytes"] = (row["argument_bytes"] + row["output_bytes"]
                                 - row["alias_bytes"] + row["temp_bytes"])
        print(row, flush=True)
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="+")
    ap.add_argument("--quick", action="store_true",
                    help="only the largest prefill and the widest megastep")
    args = ap.parse_args()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    for name in args.config:
        rows = compile_calls(name, args.quick)
        worst = max(r["resident_bytes"] for r in rows)
        print(f"{name}: {len(rows)} programs compiled for v5e; the largest needs "
              f"{worst:,} B resident of the chip's 16,909,336,064 B limit")
    return 0


if __name__ == "__main__":
    sys.exit(main())
