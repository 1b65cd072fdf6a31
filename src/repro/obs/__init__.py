"""Unified observability layer (DESIGN.md §10): span tracing on one
monotonic clock, a typed metrics registry the engine stats emit into,
and Chrome-trace export — dependency-free (stdlib only; the
``jax.profiler`` bridge is opt-in and lazily imported).

    from repro.obs import Tracer, use_tracer, span
    tracer = Tracer()
    with use_tracer(tracer):
        report = run_pipeline(..., trace=tracer)
    write_chrome_trace(tracer, "pipeline_trace.json")
"""
from repro.obs.export import (TraceValidationError, chrome_trace,
                              validate_chrome_trace, write_chrome_trace)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               StatsMixin)
from repro.obs.trace import (Span, Tracer, active_tracer, now, span,
                             use_tracer)

__all__ = [
    "Span", "Tracer", "span", "use_tracer", "active_tracer", "now",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StatsMixin",
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "TraceValidationError",
]
