"""Production serving plane: AOT-compiled inference with continuous batching.

Three layers (docs/architecture/serving.md):

* :mod:`program_store` — per ``(model, shape-bucket, dtype)`` signature the
  inference program is lowered and compiled **ahead of time**
  (``jax.jit(...).lower(...).compile()``) into a bounded LRU keyed like
  ``cached_op.py``'s; arbitrary request sizes are padded up to a small set
  of configured bucket edges and the pad rows sliced back off the outputs.
* :mod:`scheduler` — :class:`ServingEngine`, a continuous-batching request
  scheduler: one engine thread drains a request queue into the largest
  bucket that fits within a per-request latency budget
  (``MXNET_SERVE_MAX_DELAY_MS`` / ``MXNET_SERVE_MAX_BATCH``), with
  per-request futures, timeout/cancellation, and graceful shutdown that
  drains in-flight work.
* :mod:`registry` — :class:`ModelRegistry`, multi-model tenancy: N models
  served from one process, each with its own program store and optional
  serving weight dtype (bf16, or int8 weight-only through the fused
  dequant-matmul door — ``docs/architecture/serving.md``'s dtype
  matrix).

The decode plane (docs/architecture/decode_engine.md) adds
autoregressive generation on the same registry: :mod:`program_store`'s
:class:`GenerativeProgramStore` splits a generative model into AOT
prefill programs (per batch/prompt bucket, filling the KV cache) and
decode-step programs (per batch/cache bucket, one token per sequence,
cache donated), and :mod:`decode_engine`'s :class:`GenerationEngine`
runs continuous-batched generation over them — admitting newly
prefilled sequences into the running decode batch between steps and
retiring finished ones.

:mod:`loadgen` provides the seeded open-loop load generator (deterministic
arrival schedule, ``faultinject``-style) behind the p50/p99 + QPS
scenarios of ``tools/serve_smoke.py`` and the tests — and, for the decode
plane, ``run_gen_loadgen``'s tokens/sec + TTFT + inter-token latency.

The control plane (docs/architecture/serving.md, control-plane section)
closes the loop over all of it: :mod:`controller`'s :class:`AutoScaler`
grows and shrinks a :class:`ReplicaSet` off the metrics registry's
queue-wait/shed/utilization signals against an SLO target, the replica
set's ``swap_params`` is a zero-downtime rolling weight swap with
abort-and-rollback, admission understands priority tiers and per-tenant
quotas, and :mod:`loadgen`'s ``autoscale_protocol`` /
``rolling_swap_protocol`` / ``chaos_protocol`` prove the behaviors under
seeded shaped load and composed fault schedules.
"""
from .program_store import (GenerativeProgramStore, ProgramStore,
                            bucket_edges, bucket_for, host_sample,
                            sample_tokens)
from .registry import ModelRegistry
from .scheduler import (TIERS, FutureCompleter, ServeClosed,
                        ServeOverloaded, ServeRequest, ServeTimeout,
                        ServingEngine)
from .decode_engine import GenerationEngine, GenerationResult, TokenStream
from .replica_set import (NoLiveReplicas, Replica, ReplicaDied,
                          ReplicaSet)
from .controller import AutoScaler
from .frontdoor import HttpClient, HttpFrontDoor
from .loadgen import (OpenLoopSchedule, autoscale_protocol,
                      chaos_protocol, failover_protocol,
                      frontdoor_protocol, latency_protocol,
                      rolling_swap_protocol, run_gen_loadgen,
                      run_loadgen, swap_protocol)

__all__ = [
    "ProgramStore", "GenerativeProgramStore", "bucket_edges", "bucket_for",
    "sample_tokens", "host_sample",
    "ModelRegistry",
    "ServingEngine", "ServeRequest", "ServeTimeout", "ServeClosed",
    "ServeOverloaded", "FutureCompleter", "TIERS",
    "GenerationEngine", "GenerationResult", "TokenStream",
    "Replica", "ReplicaSet", "ReplicaDied", "NoLiveReplicas",
    "AutoScaler",
    "HttpFrontDoor", "HttpClient",
    "OpenLoopSchedule", "run_loadgen", "latency_protocol",
    "run_gen_loadgen", "frontdoor_protocol",
    "failover_protocol", "swap_protocol", "autoscale_protocol",
    "rolling_swap_protocol", "chaos_protocol",
]
