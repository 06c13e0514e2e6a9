"""repro.obs — low-overhead observability for the serving stack.

Four pieces, one facade:

* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket
  histograms in a lock-free per-engine :class:`MetricsRegistry` shard,
  rolled up across replicas on read (:func:`aggregate`), with
  Prometheus-text and JSON-snapshot exporters. ``StatsView`` keeps the
  engine's historical ``stats`` dict surface alive as a view over the
  registry.
* :mod:`repro.obs.tracing` — per-request span traces and the per-step
  phase timeline as Chrome trace-event JSON (Perfetto-loadable), plus
  the schema validator and the shared ``Stopwatch`` timing helper. The
  step phases' seconds are always counted (``serve.phase.<p>_s``) and
  always annotated for the JAX profiler (``serve.<p>``).
* :mod:`repro.obs.flight` — a bounded ring of recent events dumped to
  disk on step exceptions / ``EngineStopped`` / front-end shutdown.
* :mod:`repro.obs.core` — :class:`Observability`, the per-engine bundle
  the serving loop talks to. All hooks are host-side Python over values
  the loop already fetched: metrics/tracing ON adds zero device syncs
  and zero executables (pinned by the sanitizer ``observability``
  scenario).
"""
from repro.obs.core import Observability
from repro.obs.flight import FLIGHT_SCHEMA_VERSION, FlightRecorder
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               StatsView, aggregate, aggregate_registry)
from repro.obs.tracing import (PHASES, SpanTracer, StepPhases, Stopwatch,
                               validate_chrome_trace)

__all__ = [
    "Observability",
    "FlightRecorder",
    "FLIGHT_SCHEMA_VERSION",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StatsView",
    "aggregate",
    "aggregate_registry",
    "PHASES",
    "SpanTracer",
    "StepPhases",
    "Stopwatch",
    "validate_chrome_trace",
]
