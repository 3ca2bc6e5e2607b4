"""Observability layer: distributed tracing + live metrics.

The reference's observability story is a GpuMetric surface plus offline
event-log miners (SURVEY.md §2.2-F, §5.1). After the fault-tolerant
scheduler, the interesting behavior — retries, respawns, speculation,
spill cascades, shuffle waits — happens *across processes*; this package
makes it visible live:

- ``tracer``  — span-based distributed tracing. Driver query/stage/
  operator spans, scheduler attempt spans, and worker-side spans joined
  through a trace context (trace_id + parent span id) propagated in
  ``TaskSpec`` payloads and committed alongside task output, so the
  driver stitches ONE coherent Chrome ``trace_event`` JSON per query
  (chrome://tracing / Perfetto). A live span is also a
  ``jax.profiler.TraceAnnotation`` (``spark:query``, ``spark:op``,
  ``spark:scan.read`` ...), so under a JAX profiler session the same
  spans lie on the profiler's host plane beside the device operations.
- ``metrics`` — a process-wide MetricsRegistry (counters / gauges /
  histograms with bounded label sets) exposed as Prometheus text via
  ``dump_prometheus`` and an optional HTTP endpoint
  (``spark.rapids.metrics.port``); cluster workers flush snapshots
  through the filesystem rendezvous for driver-side aggregation.
- ``recorder`` / ``anomaly`` — the always-on flight recorder: a
  bounded per-process ring of recent spans, memory-ledger transitions,
  scheduler events and shuffle waits that turns into a self-contained
  incident bundle exactly when something goes wrong (task failure,
  worker death, OOM/spill cascade, statistical straggler) — forensics
  for queries that ran with tracing and metrics fully OFF.

Metrics export is off by default. Tracing is on for a query when
``spark.rapids.trace.dir`` is set or a JAX profiler session is running
when the query's ``ExecCtx`` is made (``spark.rapids.profile.path``, or
a session the caller started); with neither it is near-zero overhead
(the null tracer's ``span()`` is a shared no-op context manager, and a
stage whose seconds feed a counter is timed by a bare stopwatch;
registry updates are plain attribute arithmetic); the flight
recorder is ON by default — its records are bounded deque appends.
What any of it costs on the chip has not been measured (ROADMAP D8).
"""
from .tracer import (NULL_TRACER, Span, Tracer, TRACE_DIR, TRACE_MAX_FILES,
                     TRACE_MAX_SPANS, tracer_from_conf)
from .metrics import (METRICS_ENABLED, METRICS_PORT, MetricsRegistry,
                      REGISTRY, dump_prometheus, maybe_start_http_server,
                      render_merged_snapshots)
from .recorder import RECORDER, FlightRecorder
from .anomaly import AnomalyDetector, build_incident_bundle

__all__ = ["NULL_TRACER", "Span", "Tracer", "TRACE_DIR", "TRACE_MAX_SPANS",
           "TRACE_MAX_FILES", "tracer_from_conf", "METRICS_ENABLED",
           "METRICS_PORT", "MetricsRegistry", "REGISTRY",
           "dump_prometheus", "maybe_start_http_server",
           "render_merged_snapshots", "RECORDER", "FlightRecorder",
           "AnomalyDetector", "build_incident_bundle"]
