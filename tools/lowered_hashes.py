"""sha256 of the lowered text (``jax.jit(...).lower(...).as_text()``) of the
decode and the ragged program of every tiny preset, on the CPU, from shapes
alone: the evidence that a PR which adds a model left the other models'
programs as they were (PR 46 used it; ROADMAP D18 asks that such tools be in
the tree).

    JAX_PLATFORMS=cpu python -m tools.lowered_hashes [ROOT] > change.json
    JAX_PLATFORMS=cpu python -m tools.lowered_hashes _parent > parent.json
    diff parent.json change.json

``ROOT`` is the checkout whose ``dynamo_tpu`` is imported (default: this
one), so that a ``git archive`` of the parent commit in a directory beside
it is hashed by the same script. A preset the checkout lacks is left out.
"""

from __future__ import annotations

import hashlib
import json
import sys

PRESETS = ("tiny", "tiny-moe", "tiny-loop", "tiny-axk1", "tiny-lfm2", "tiny-laguna",
           "tiny-sdar", "tiny-mimo", "tiny-olmo-hybrid", "tiny-nemotron-h")


def main() -> int:
    if len(sys.argv) > 1:
        sys.path.insert(0, sys.argv[1])
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import config as C
    from dynamo_tpu.engine import model as M

    out = {}
    for name in PRESETS:
        if name not in C.PRESETS:
            continue
        cfg = C.PRESETS[name]()
        eng = C.tiny_engine(block_size=8 if name == "tiny-lfm2" else 4, num_kv_blocks=32,
                            max_model_len=128, num_window_blocks=64 if cfg.windowed else 0)
        params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: M.init_cache(cfg, eng))
        width = eng.max_blocks_per_seq
        if cfg.windowed:
            width += 1 + eng.window_table_blocks(cfg.sliding_window)
        if getattr(cfg, "has_slab", getattr(cfg, "linear", False)):   # the lane slot's column
            # (model.split_slots)
            width += 1
        S, T = 4, 32
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        programs = {}
        if not cfg.block_length:   # a block model has no next-token decode program
            programs["decode"] = jax.jit(
                lambda p, c, t, bt, pos, act: M.decode_tokens(p, c, t, bt, pos, act, cfg, eng)
            ).lower(params, cache, i32(S), i32(S, width), i32(S),
                    jax.ShapeDtypeStruct((S,), jnp.bool_))
        programs["ragged"] = jax.jit(
            lambda p, c, t, pos, wp, wo, kl, bt, cu, ns, lr: M.forward_tokens(
                p, c, t, pos, wp, wo, kl, bt, cu, ns, lr, cfg, eng)
        ).lower(params, cache, i32(T), i32(T), i32(T), i32(T), i32(S), i32(S, width),
                i32(S + 1), i32(1), i32(S))
        for kind, lowered in programs.items():
            out[f"{name}/{kind}"] = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
