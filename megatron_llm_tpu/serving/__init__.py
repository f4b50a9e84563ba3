"""Continuous-batching serving engine (see docs/serving.md).

- ``engine.py`` — iteration-level scheduler: admission, batched decode,
  retirement, per-request streaming and cancellation.
- ``slots.py`` — KV-slot allocator over one long-lived fixed-shape cache.
- ``queue.py`` — bounded admission queue with backpressure (``QueueFull``).
- ``prefix_cache.py`` — automatic prefix caching: block-granular radix
  cache of shared-prefix K/V consulted at admission, fed at retirement.
- ``metrics.py`` — serving counters / gauges / latency histograms, plus
  the SLO tracker; registered into the shared ``obs.REGISTRY`` for
  Prometheus export (docs/observability.md).
- ``adapters/`` — multi-tenant LoRA: adapter registry + device-arena
  residency (LRU + ref pinning) so thousands of registered adapters
  share one base model, different adapters coexisting per-row in one
  decode batch.
- ``cluster/`` — multi-chip serving: engines sharded over tp×pp(×fsdp)
  submeshes
  (``cluster/sharded.py``) behind a replicated health-aware router with
  drain-based failover (``cluster/router.py``), plus disaggregated
  prefill/decode — prefill-specialized replicas shipping paged KV
  blocks to decode replicas, with live decode migration
  (``build_disagg_cluster``); see docs/serving.md, 'Multi-chip serving'
  and 'Disaggregated prefill/decode'.  ``cluster/supervisor.py`` adds
  self-healing: dead or wedged replicas are rebuilt on their original
  submesh and rejoined to rotation (docs/robustness.md, 'Cluster
  self-healing').
"""

from .adapters import AdapterRegistry
from .cluster import ReplicaSupervisor, Router, RouterConfig, \
    RouterHandle, SupervisorConfig, build_cluster, build_disagg_cluster, \
    build_sharded_engine
from .engine import (
    EngineConfig,
    FinishedRequest,
    KVShipment,
    RequestHandle,
    ServingEngine,
)
from .metrics import LatencyHistogram, ServingMetrics
from .prefix_cache import PrefixCache, PrefixLease
from .queue import QueueFull, RequestQueue
from .slots import SlotAllocator

__all__ = [
    "AdapterRegistry",
    "EngineConfig",
    "ReplicaSupervisor",
    "Router",
    "RouterConfig",
    "RouterHandle",
    "SupervisorConfig",
    "build_cluster",
    "build_disagg_cluster",
    "build_sharded_engine",
    "FinishedRequest",
    "KVShipment",
    "LatencyHistogram",
    "PrefixCache",
    "PrefixLease",
    "QueueFull",
    "RequestHandle",
    "RequestQueue",
    "ServingEngine",
    "ServingMetrics",
    "SlotAllocator",
]
