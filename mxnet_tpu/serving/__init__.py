"""LLM serving engine — paged KV-cache attention + continuous batching.

The "millions of users" workload the substrate exists for (ROADMAP #1): a
standing inference engine over the Transformer-LM zoo model. Sequences share
one device's KV memory through a block-paged ragged cache (per "Ragged Paged
Attention", PAPERS.md) and a continuous-batching scheduler mixes prefill and
decode into padded shape buckets, so the decode step compiles once per
bucket (provable via compileobs) and thousands of variable-length streams
multiplex one set of weights.

Layers:

* :mod:`.kv_cache`  — what a K/V page is (:class:`PageSpec`, one a cache)
  and the device block pool + host allocator of such pages
  (``serving.kv_blocks_*`` accounting).
* :mod:`.model`     — the functional Transformer-LM forward sharing
  ``models/transformer_lm.py`` parameter names: full-sequence prefill
  (flash attention) and the fused one-token paged decode step
  (``ops.attention.paged_attention``).
* :mod:`.scheduler` — admission queue, per-request state machine,
  FCFS continuous batching, block-exhaustion preemption.
* :mod:`.engine`    — :class:`ServingEngine`: the Python API
  (``submit``/``step``/``generate``) with per-request TTFT / latency /
  tokens-per-sec flowing through the telemetry registry.
* :mod:`.obs`       — the per-request observability plane: lifecycle
  event stream keyed by ``request_id``, phase attribution (queue_wait /
  prefill / decode / replay / compile_stall summing to end-to-end),
  SLO accounting (``MXNET_SERVING_SLO_*``), the step occupancy timeline.
* :mod:`.resilience` — failure-as-routine: classified load shedding
  (:class:`ServingOverloadError` + Retry-After hints), per-request
  deadlines/cancellation (TIMED_OUT/CANCELLED terminal states swept
  every step), and :class:`EngineSupervisor` — abort → salvage →
  backoff → rebuild warm from the compile cache → replay survivors
  bit-identically. docs/serving.md §resilience.

Front ends: ``tools/serve.py`` (HTTP/JSON standing server with live stat
columns), ``benchmark/run.py`` (the serving cells' drivers), and
``tools/serving_report.py`` (per-request waterfalls + occupancy timeline
from telemetry JSONL). See docs/serving.md.
"""
from .engine import ServingConfig, ServingEngine
from .kv_cache import KVBlockPool, KVCacheOOM, PageSpec
from .obs import PHASES, RequestTrace, ServingObs
from .resilience import EngineSupervisor, ServingOverloadError, retry_after_s
from .scheduler import (CANCELLED, FAILED, FINISHED, TIMED_OUT, Request,
                        Scheduler)

__all__ = ["ServingConfig", "ServingEngine", "KVBlockPool", "KVCacheOOM",
           "PageSpec",
           "Request", "Scheduler", "ServingObs", "RequestTrace", "PHASES",
           "EngineSupervisor", "ServingOverloadError", "retry_after_s",
           "FINISHED", "FAILED", "TIMED_OUT", "CANCELLED"]
