"""Telemetry layer: host spans on the profiler's clock, counters, gauges;
JSONL sink + summary rollup.

See :mod:`repro.telemetry.core`.  The serve engine instruments against
the ``Telemetry`` it is given (the module default when none is), which
is disabled -- a true no-op -- until enabled; ``telemetry.configure(...)``
turns the module default on.  While enabled, each span also enters a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it beside
the device's operations.
"""
from repro.telemetry.core import (Telemetry, configure, count, default,
                                  event, gauge, span, summary)

__all__ = ["Telemetry", "configure", "count", "default", "event", "gauge",
           "span", "summary"]
