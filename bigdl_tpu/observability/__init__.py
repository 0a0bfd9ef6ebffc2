"""Unified training/inference observability.

Four layers over the one shared driver loop:

- ``StepTelemetry`` -- structured per-step JSONL events (split
  wall/data-wait/device timers, loss, records/s, memory stats) plus a
  run header with the compiled step's flops (``telemetry.py``).
- ``span`` / ``recorder`` -- the span recorder: every span the program
  opens lands in one in-memory ring on the device trace's clock, with
  no telemetry object attached; ``SpanTracer`` is a sink that also
  streams them to a Perfetto-viewable chrome trace (``spans.py``).
- ``RecompileWatchdog`` / ``MemoryWatchdog`` -- WARNING-level detectors
  for silent per-step recompiles and monotonic device-memory growth
  (``watchdogs.py``).
- ``HealthMonitor`` + ``NonFiniteWatchdog`` / ``LossSpikeWatchdog`` --
  sampled ON-DEVICE numerics stats fused into the jitted train step
  (per-layer grad norms, update ratios, non-finite counts) with a
  warn/dump/halt anomaly policy and re-executable incident bundles
  (``health.py``).
- ``BlockingStepTimer`` / ``TimingAuditor`` -- trusted timing:
  ``block_until_ready``-fenced per-step measurement (the only basis
  MFU math may use) and triangulated trust verdicts
  (``trusted`` / ``suspect:async_dispatch`` / ``invalid:*``) stamped
  on telemetry streams (``profiling.py``).
- ``MemoryLedger`` -- per-subsystem device-byte attribution (params /
  fp32 twin / KV block pool / staged deploy buffers) reconciled
  against ``device_memory_stats()`` (leaks surface as a growing
  residual), with one-shot durable OOM forensic dumps
  (``memory.py``; ``tools/mem_report.py`` replays the timeline).
- ``MetricsRegistry`` / ``MetricsExporter`` / ``SloTracker`` -- LIVE
  fleet telemetry: a dependency-free Counter/Gauge/Histogram registry
  bridged from the same telemetry events, served over ``/metrics``
  (Prometheus text) + ``/healthz`` (ok/degraded/halted) by a stdlib
  http thread, with declarative SLO objectives under multi-window
  burn-rate alerting feeding the warn/dump/halt policy framework
  (``metrics.py``).

``tools/obs_report.py`` merges a run's JSONL + xplane trace into one
report; the event schema is documented in ``docs/observability.md``.
"""

from bigdl_tpu.observability.health import (HealthMonitor, dump_incident,
                                            global_grad_norm, layer_labels,
                                            load_incident,
                                            per_layer_grad_norms)
from bigdl_tpu.observability.memory import (MemoryLedger, is_oom_error,
                                            tree_bytes)
from bigdl_tpu.observability.metrics import (Counter, Gauge, Histogram,
                                             MetricsExporter,
                                             MetricsRegistry, SloObjective,
                                             SloTracker)
from bigdl_tpu.observability.profiling import (BlockingStepTimer,
                                               TimingAuditor)
from bigdl_tpu.observability.spans import (SpanTracer, instant,
                                           read_trace_events, recorder,
                                           span)
from bigdl_tpu.observability.telemetry import (StepTelemetry,
                                               device_memory_stats,
                                               peak_flops)
from bigdl_tpu.observability.tracing import (HeadSampler, RequestTrace,
                                             TraceContext)
from bigdl_tpu.observability.watchdogs import (LossSpikeWatchdog,
                                               MemoryWatchdog,
                                               NonFiniteWatchdog,
                                               RecompileWatchdog,
                                               backend_compile_count)

__all__ = [
    "StepTelemetry", "SpanTracer", "span", "recorder", "instant",
    "RecompileWatchdog",
    "MemoryWatchdog", "NonFiniteWatchdog", "LossSpikeWatchdog",
    "HealthMonitor", "backend_compile_count", "device_memory_stats",
    "peak_flops", "layer_labels", "per_layer_grad_norms",
    "global_grad_norm", "dump_incident", "load_incident",
    "BlockingStepTimer", "TimingAuditor",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "MetricsExporter", "SloObjective", "SloTracker",
    "TraceContext", "HeadSampler", "RequestTrace",
    "read_trace_events",
    "MemoryLedger", "tree_bytes", "is_oom_error",
]
