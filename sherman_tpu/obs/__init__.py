"""sherman_tpu.obs — the unified observability plane.

The reference Sherman has no observability layer (SURVEY.md §5):
profiling is a manual ns ``Timer`` plus hand-rolled latency histograms,
and op counters live inside ``DSM``.  This package is the single
instrumentation surface every layer reports through:

- :mod:`sherman_tpu.obs.registry` — process-wide metrics registry
  (counters, gauges, histograms) with snapshot/delta semantics so
  drivers and tests can diff op counts around a timed region.  Hot-path
  increments are a plain attribute add — no locks, no dict lookups.
- :mod:`sherman_tpu.obs.spans` — the one span API (``obs.span``):
  nested, thread-safe spans that also land on the profiler trace's host
  plane (``jax.profiler.TraceAnnotation``), per-name aggregates, and a
  bounded ring of recent spans exported as Chrome-trace-event JSON
  (loadable in ``chrome://tracing`` / Perfetto).
- :mod:`sherman_tpu.obs.slo` — the SLO telemetry layer: per-op-class
  (read/insert/delete/mixed/scan) amortized latency with sliding-window
  ops/s and p50/p99/p999, fed by every batch wall (engine entry points
  + the device-staged step factories' ``record_slo``).  Registered as
  the ``slo.*`` pull collector.
- :mod:`sherman_tpu.obs.recorder` — the black-box flight recorder: a
  bounded ring of structured events (chaos injections, lease
  revocations, degraded transitions, journal poisonings,
  recovery/repair steps, compile retraces, span closes) with env-gated
  auto-dump bundles (Chrome trace + events JSONL) on degraded entry,
  typed-error raise, watchdog fire, or steady-state retrace.
- :mod:`sherman_tpu.obs.device` — the white-box device-telemetry
  plane: the compile ledger (every jit compilation as a structured
  {program, shape signature, compile ms} entry, with the post-seal
  steady-state retrace detector), the HBM/live-buffer accountant
  (pool/journal/checkpoint byte gauges with a peak watermark,
  per-program ``memory_analysis``), and roofline receipts
  (``cost_analysis`` flops/bytes joined with measured phase walls into
  achieved-fraction-of-peak).  Registered as the ``device.`` pull
  collector beside ``slo.``; ``SHERMAN_DEVICE_OBS=0`` kills it.
- :mod:`sherman_tpu.obs.export` — JSONL periodic snapshots, the
  one-call :func:`~sherman_tpu.obs.export.dump` used by ``bench.py``,
  Prometheus text exposition (textfile mode + optional stdlib HTTP
  scrape endpoint behind ``SHERMAN_METRICS_PORT``).

Wired-in sources: the DSM registers its device op/byte counters as a
pull collector (``dsm.*`` keys in every snapshot), the transports count
collective builds and payload bytes, the batched engine wraps its
combine/descend/apply phases in spans AND attributes every host-path
batch wall to its op class, and the host B+Tree counts index cache
hits/misses/invalidations.
"""

from __future__ import annotations

from sherman_tpu.obs.device import (CompileLedger, MemoryAccountant,
                                    device_peaks, get_accountant,
                                    get_ledger, program_cost,
                                    program_memory, roofline, rooflines,
                                    wrap_program)
from sherman_tpu.obs.export import (MetricsServer, PeriodicExporter, dump,
                                    maybe_serve_http, obs_section,
                                    prometheus_text, write_prometheus,
                                    write_snapshot_jsonl)
from sherman_tpu.obs.recorder import (FlightRecorder, auto_dump,
                                      get_recorder, record_event)
from sherman_tpu.obs.registry import (Counter, Gauge, Histogram,
                                      MetricsRegistry, counter, delta, gauge,
                                      get_registry, histogram,
                                      register_collector, snapshot)
from sherman_tpu.obs.slo import (LatencyTracker, SloTracker, WindowedRate,
                                 get_slo, observe, observe_op, slo_window)
from sherman_tpu.obs.spans import SpanTracer, device_trace, get_tracer, span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram", "snapshot", "delta",
    "register_collector", "get_registry",
    "SpanTracer", "device_trace", "get_tracer", "span",
    "dump", "obs_section", "write_snapshot_jsonl", "PeriodicExporter",
    "prometheus_text", "write_prometheus", "MetricsServer",
    "maybe_serve_http",
    "LatencyTracker", "WindowedRate", "SloTracker",
    "get_slo", "observe", "observe_op", "slo_window",
    "FlightRecorder", "get_recorder", "record_event", "auto_dump",
    "CompileLedger", "MemoryAccountant", "get_ledger", "get_accountant",
    "wrap_program", "program_cost", "program_memory", "roofline",
    "rooflines", "device_peaks",
]
