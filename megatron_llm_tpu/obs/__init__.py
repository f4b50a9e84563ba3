"""Unified observability spine: metrics registry, tracing, logs, SLOs.

One process-wide home for the signals the serving and training stacks
emit, replacing the three disconnected registries that grew organically
(``serving/metrics.py:ServingMetrics``, ``metrics.py:RESILIENCE_EVENTS``,
``utils/timers.py:Timers``):

- ``registry``: labeled counters / gauges / histograms plus pluggable
  collectors, exported in Prometheus text exposition format
  (``GET /metrics?format=prometheus`` on the serving HTTP server).
- ``trace``: a low-overhead ring buffer of per-request and per-iteration
  spans, exported as Chrome trace-event JSON (``GET /trace``,
  ``tools/dump_trace.py``), joined to device profiles by ``profile``'s
  clock-sync pair.
- ``profile``: the process's one ``jax.profiler`` session, started and
  stopped while it runs (trainer step windows, ``POST /profile``).
- ``compile``: the process's one ``jax.monitoring`` listener; every
  compilation is a record (program, stage seconds, cache outcome) that
  the trace export, the registry and the event log each show.
- ``logging``: rank-aware structured JSON event log carrying
  ``request_id`` correlation ids end-to-end.
- ``slo``: rolling-window TTFT / ITL / availability objectives with
  burn-rate gauges for router health checks and drain decisions.

Everything here is host-side, stdlib-only, and safe to import before JAX.
"""

from .logging import EVENT_LOG, StructuredLog
from .registry import (REGISTRY, Counter, Gauge, Histogram, MetricFamily,
                       MetricsRegistry, Sample)
from .slo import SLOConfig, SLOTracker
from . import profile
from .trace import TRAIN_TRACE, TraceRecorder, device_annotation

__all__ = [
    "Counter",
    "EVENT_LOG",
    "Gauge",
    "Histogram",
    "MetricFamily",
    "MetricsRegistry",
    "REGISTRY",
    "Sample",
    "SLOConfig",
    "SLOTracker",
    "StructuredLog",
    "TRAIN_TRACE",
    "TraceRecorder",
    "device_annotation",
    "profile",
]
