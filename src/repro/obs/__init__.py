"""Observability for the serving stack: tracing, metrics, profiling.

Three cooperating pieces, each usable alone:

* ``registry`` — a general counter / gauge / histogram registry with
  per-metric locks and labeled families. ``repro.serve.metrics`` is a
  facade over one of these; ``repro.obs.export`` renders it in the
  Prometheus text exposition format.
* ``trace`` — request tracing: a ``Trace`` is minted per admitted
  query, ``Span``s are appended by every serving layer it crosses
  (queue wait, flush, plan, tile fetch, kernel, hedged shard dispatch,
  gather, delivery), and the finished trace lands in a ring buffer —
  plus the slow-query JSONL log when it blows a latency budget.
* ``profile`` — ``KernelProfiler`` wraps the score-kernel dispatch,
  recording per-(method, bucket, word_block) wall time, and optionally
  feeds the measurements back into the autotuner's cost cache as live
  "observed" entries.
"""
from .events import EventLog
from .profile import KernelProfiler
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .trace import BatchRecorder, Span, Trace, Tracer, span
from .export import render_prometheus

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "BatchRecorder", "Span", "Trace", "Tracer", "span",
    "EventLog", "KernelProfiler", "render_prometheus",
]
