"""Observability: span tracing + process-wide metrics for the runtime.

Independent primitives, stdlib only at import time (importable from any
layer without cycles):

* :mod:`repro.obs.trace` — a bounded-ring span recorder with a
  Chrome-trace/Perfetto JSON exporter.  Disabled by default; the
  instrumentation threaded through ingest, planner, executor, cache and
  wave layers costs one branch per call site until
  :func:`~repro.obs.trace.tracing` (or ``TRACER.start()``) attaches the
  ring.  While enabled, each span is also a ``jax.profiler``
  annotation, so it shows in a profiler trace beside the device events.
* :mod:`repro.obs.metrics` — always-on counters/gauges/histograms
  (cache hits per tier, compile-cache hits, exchanged records, queue
  depth, per-phase walls), snapshotted by ``MaRe.metrics()``.
* :mod:`repro.obs.scopes` — :func:`op_scopes`, the map from a compiled
  program's HLO instructions to the named scopes they ran under.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, METRICS)
from repro.obs.scopes import UNSCOPED, op_scopes  # noqa: F401
from repro.obs.trace import (TRACER, Tracer, instant, span,  # noqa: F401
                             timed, tracing)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "METRICS",
    "TRACER", "Tracer", "UNSCOPED", "instant", "op_scopes", "span",
    "timed", "tracing",
]
