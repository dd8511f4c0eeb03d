"""Observability layer: metrics, request spans, step traces, kernel stats.

The package is deliberately dependency-light and sits *below* both
``repro.serve`` and ``repro.kernels`` in the import graph: the engines
construct a recorder (or keep the no-op default) and call its hooks; the
autotuner accepts a hook callable installed by
:func:`repro.obs.kernelstats.enable`.  Nothing here imports those
packages at module level.

Entry points:

  * :class:`span` — the one timing primitive: a profiler annotation plus
    ``<phase>_s``/``<phase>_calls`` counters, never a fence;
    :class:`Recorder` / :data:`NULL_RECORDER` — the engines' recorder
    duck type, whose ``span()`` is that primitive (``repro.obs.record``);
  * :class:`MetricsRegistry` / :class:`EngineStats` — counters, gauges,
    histograms; snapshot + Prometheus rendering (``repro.obs.metrics``);
  * :class:`SpanLog` — per-request TTFT/TPOT/queue/preemption spans
    (``repro.obs.spans``);
  * :class:`TraceBuffer` / :func:`validate_trace` — Perfetto
    ``trace_event`` export (``repro.obs.trace``; also a CLI:
    ``python -m repro.obs.trace out.json``);
  * :mod:`repro.obs.kernelstats` — measured kernel wall-clock vs the
    roofline model;
  * :func:`audit_engine` — lifecycle-counter cross-check against the
    request log (``repro.obs.audit``).
"""
from . import kernelstats
from .audit import audit_engine, derive_counts
from .metrics import (SCHEMA_VERSION, Counter, Gauge, Histogram,
                      MetricsRegistry, EngineStats, bench_payload,
                      exponential_buckets, DURATION_BUCKETS_S)
from .record import NULL_RECORDER, NullRecorder, Recorder, span
from .spans import RequestSpan, Segment, SpanLog, percentile, percentile_table
from .trace import TraceBuffer, validate_trace, validate_trace_file

__all__ = [
    "SCHEMA_VERSION",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "EngineStats",
    "bench_payload", "exponential_buckets", "DURATION_BUCKETS_S",
    "span", "Recorder", "NullRecorder", "NULL_RECORDER",
    "SpanLog", "RequestSpan", "Segment", "percentile", "percentile_table",
    "TraceBuffer", "validate_trace", "validate_trace_file",
    "audit_engine", "derive_counts",
    "kernelstats",
]
