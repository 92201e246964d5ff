"""dynamo_tpu — TPU-native distributed LLM inference-serving framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of NVIDIA Dynamo
(surveyed in SURVEY.md): OpenAI-compatible frontend, KV-cache-aware routing,
disaggregated prefill/decode, multi-tier KV block management, SLA planning,
and a native JAX TPU engine with paged attention and continuous batching.

Layer map (bottom → top):

- ``dynamo_tpu.runtime``  — distributed runtime: control-plane store
  (discovery/leases/watch, pub-sub, work queues), component model
  (Namespace → Component → Endpoint → Instance), AsyncEngine streaming
  abstraction, TCP response data plane, metrics, config, logging.
- ``dynamo_tpu.tokens``   — block-aligned token sequences with chained
  content hashes (shared scheme across router / KVBM / mocker / engine).
- ``dynamo_tpu.llm``      — OpenAI protocols, preprocessor, incremental
  detokenizer + stop engine, model cards/discovery, KV router, KVBM,
  migration, disaggregation, mocker engine.
- ``dynamo_tpu.engine``   — the native JAX TPU worker: paged KV cache,
  continuous batching scheduler, sampling, model presets (llama family +
  mixtral-MoE in ``engine/config.py``), HF weight loading.
- ``dynamo_tpu.ops``      — Pallas TPU kernels (ragged paged attention,
  chunked prefill flash attention, fused rmsnorm/rope, ...).
- ``dynamo_tpu.parallel`` — mesh construction, TP/DP/EP/SP sharding rules,
  ring attention for long context.
"""

import time as _time

# The first line of the program's own code a process runs: where the
# start-up clock's ``interpreter`` stage ends (tracing/startclock.py).
FIRST_LINE_NS = _time.perf_counter_ns()

__version__ = "0.1.0"
