"""``repro.obs`` — execution tracing, run profiles, and service metrics.

The observability layer promised by the paper's premise that a workflow's
distributed execution trace is a first-class object:

* :class:`TraceRecorder` / :class:`SpanEvent` — low-overhead span capture
  shared by all four backends (``lower(..., trace=True)``);
* :func:`profiler_span` — a span on the profiler's clock
  (``jax.profiler.TraceAnnotation``), always on, read from a device trace;
* :class:`RunProfile` — the structured artifact on every traced result
  (``result.profile``), exportable as Perfetto-loadable Chrome trace JSON;
* :func:`align` / :class:`ProfileReport` — predicted-vs-actual drift
  against the sched simulator (``Plan.profile(result)``);
* :class:`MetricsRegistry` — the Prometheus text registry behind the
  gateway's ``GET /v1/metrics``.
"""

from repro.obs.events import (
    SpanEvent,
    TraceRecorder,
    current_trace_id,
    payload_nbytes,
    profiler_span,
)
from repro.obs.export import (
    chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.profile import ProfileReport, RunProfile, StepDrift, align

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "ProfileReport",
    "RunProfile",
    "SpanEvent",
    "StepDrift",
    "TraceRecorder",
    "align",
    "chrome_trace",
    "current_trace_id",
    "payload_nbytes",
    "profiler_span",
    "validate_chrome_trace",
    "write_chrome_trace",
]
