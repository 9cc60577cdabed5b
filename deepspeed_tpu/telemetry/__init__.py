"""Unified telemetry: timeline spans, Perfetto export, MFU profiling.

Before this package the repo's observability was fragmented — scalar
fan-out in ``monitor/monitor.py``, request spans in
``serving/frontend/tracing.py``, retrace accounting in
``analysis/auditor.py``, and an unwired ``profiling/flops_profiler.py``.
This package is the one runtime they all feed:

* :mod:`.core` — a process-wide, thread-safe, lock-light
  :class:`TelemetryRuntime`: ``span(name, **attrs)`` context managers
  (optionally ``sync=``-honest, same contract as ``utils/timer.py``),
  instant events, counters and gauges, recorded into a bounded ring
  buffer. A live span also enters a ``jax.profiler.TraceAnnotation``, so
  it lies in the profiler's trace on the clock the device's ops are on.
  Disabled telemetry is a single flag check — the hot paths stay
  instrumented permanently.
* :mod:`.export` — Chrome-trace/Perfetto JSON: one thread lane per
  emitting thread, spans + instants + counter tracks, plus the bridge
  that renders the serving frontend's per-request ``TraceLog`` records
  as request lanes with flow arrows in the SAME file.
* :mod:`.summary` — per-span count/total/p50/p95/p99 (reusing the
  serving ``Reservoir``) and counter totals; feeds the existing
  ``MonitorMaster`` fan-out and the ``BENCH_*.json`` phase breakdowns.
* :mod:`.mfu` — compile-time FLOPs via
  ``jitted.lower(...).compile().cost_analysis()`` and model-FLOPs-
  utilization reports (powers ``profiling/flops_profiler.py``).
* :mod:`.cli` — ``bin/tputrace``: summarize/validate a captured trace
  (stdlib-only; never imports JAX).
* :mod:`.fleetobs` — the fleet observability plane: one
  :class:`FleetMetricsAggregator` scraping every pod's replicas (local
  render, remote ``GET /v1/metrics``) into a single ``/fleet/metrics``
  exposition with ``pod=``/``replica=`` labels, pod rollups, and
  pod-level anomaly wiring (stdlib-only).

Module-level helpers (``span`` / ``instant`` / ``count`` / ``gauge``)
write to one process-wide default runtime so instrumentation sites never
thread a handle around; ``enable()`` / ``disable()`` flip capture.

This module imports no JAX — ``bin/tputrace`` and ``bin/tracelint``
stay in the millisecond range. See docs/observability.md.
"""

from .core import (NOOP_SPAN, TelemetryRuntime, configure,  # noqa: F401
                   count, current_replica, disable, enable, gauge,
                   get_runtime, instant, record_span, replica_label, span)
from .export import (chrome_trace, request_trace_events,  # noqa: F401
                     write_chrome_trace)
from .summary import (emit_summary, phase_breakdown,  # noqa: F401
                      summarize)
from .mfu import (compiled_cost_analysis, mfu_report,  # noqa: F401
                  peak_flops_per_device)
from .memory import (compiled_memory_analysis, format_bytes,  # noqa: F401
                     live_array_census)
from .exposition import (MetricsServer, parse_prometheus_text,  # noqa: F401
                         render_prometheus)
from .regression import (MetricSpec, detect_kind,  # noqa: F401
                         diff_benchmarks)
from .journey import (PID_JOURNEYS, PID_PODS,  # noqa: F401
                      assemble_journeys, journey_trace_events,
                      new_trace_id, pod_lane_events,
                      summarize_journeys, validate_journeys)
from .fleetobs import (FleetMetricsAggregator,  # noqa: F401
                       ScrapeTarget)
from .slo import SLOEngine, SLOSpec, default_slos  # noqa: F401
from .flight_recorder import (FlightRecorder, dump_all,  # noqa: F401
                              install_sigterm_handler)
from .anomaly import (AnomalyDetector, AnomalySpec,  # noqa: F401
                      default_specs)

__all__ = [
    "TelemetryRuntime", "get_runtime", "configure", "enable", "disable",
    "span", "record_span", "instant", "count", "gauge", "NOOP_SPAN",
    "replica_label", "current_replica",
    "chrome_trace", "write_chrome_trace", "request_trace_events",
    "summarize", "phase_breakdown", "emit_summary",
    "compiled_cost_analysis", "mfu_report", "peak_flops_per_device",
    "compiled_memory_analysis", "live_array_census", "format_bytes",
    "render_prometheus", "parse_prometheus_text", "MetricsServer",
    "MetricSpec", "diff_benchmarks", "detect_kind",
    "PID_JOURNEYS", "PID_PODS", "new_trace_id", "assemble_journeys",
    "journey_trace_events", "pod_lane_events", "validate_journeys",
    "summarize_journeys",
    "FleetMetricsAggregator", "ScrapeTarget",
    "SLOSpec", "SLOEngine", "default_slos",
    "FlightRecorder", "install_sigterm_handler", "dump_all",
    "AnomalySpec", "AnomalyDetector", "default_specs",
]
