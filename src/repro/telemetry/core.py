"""Lightweight telemetry: host spans on the profiler's clock, counters,
gauges, events.

The serve/kernel stack hangs its operational numbers on this module:

* ``span(name, **attrs)`` -- a context manager timing one host
  operation on ``time.perf_counter()`` (the clock of the serve engine's
  frame log), with thread-local nesting: each record keeps its name,
  start, end, parent and attrs, so a ``serve.round`` decomposes into
  its ``serve.admit`` / ``serve.kernel`` / ``serve.audit`` / ...
  children.  The span also enters ``jax.profiler.TraceAnnotation(name,
  **attrs)``, so a profiler trace taken meanwhile holds it on its host
  plane, on the clock the device's operations use: an idle gap of the
  device can be put down to the span the host was in;
* ``interval(name, start, end, **attrs)`` -- a finished interval that
  is no nested context (a job's wait in the queue, from submission to
  placement);
* ``count(name, n)`` / ``gauge(name, value)`` -- monotone event tallies
  and last-value measurements;
* ``event(name, critical=False, **attrs)`` -- a point-in-time record;
  ``critical`` events (rollback, quarantine) flush **and fsync** the
  JSONL sink, so the trace of a fault survives the process death that
  ``CAServeEngine.resume`` recovers from.

Sinks: an in-memory registry (bounded per span name; ``summary()`` rolls
spans up to count/total/p50/p95/p99/max, ``records()`` returns them)
and an optional JSONL file -- one self-describing object per line
(``kind``: span | counter | gauge | event), opened line-buffered so
every record is its own ``write()``.

Disabled telemetry is a **true no-op**: ``span`` hands back a shared
null context manager and ``interval``/``count``/``gauge``/``event``
return before touching any state -- no clock read, no annotation, no
allocation beyond the call itself.

A span opened while jax traces (inside ``jit``) is the null span too:
the body of a jitted function runs once at trace time, not per call, so
a clock there would time tracing.  Instrumented code therefore compiles
to the same program whether telemetry is on or off.

The module-level default instance is what library code instruments
against; ``configure()`` switches it on and points it at a sink.
Constructing private ``Telemetry`` instances keeps tests and engines
isolated.
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

__all__ = ["Telemetry", "configure", "default", "span", "count", "gauge",
           "event", "summary"]

# (start, end, parent, attrs) of one span, on time.perf_counter().
_Record = Tuple[float, float, Optional[str], Dict]


def _tracing() -> bool:
    """True while jax is tracing (inside jit/scan/shard_map staging)."""
    return not jax.core.trace_ctx.is_top_level()


class _NullSpan:
    """Shared do-nothing context manager: the disabled-telemetry span."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    """One live span: a profiler annotation plus a perf_counter interval,
    recorded on exit."""
    __slots__ = ("_tel", "name", "attrs", "start", "_parent", "_annotation")

    def __init__(self, tel: "Telemetry", name: str, attrs: Dict):
        self._tel = tel
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = self._tel._stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.attrs)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._tel._stack().pop()
        self._tel._record_span(self.name, self.start, end, self._parent,
                               self.attrs)
        return False


class Telemetry:
    """Span/counter/gauge registry with an optional JSONL sink.

    ``max_events`` bounds the in-memory records of each span name and
    the event list (oldest halved out), so a long-lived serve process
    cannot grow without bound; the JSONL sink, when given, keeps the
    full stream.
    """

    def __init__(self, enabled: bool = False,
                 jsonl_path: Optional[str] = None,
                 max_events: int = 65536):
        self.max_events = int(max_events)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: Dict[str, List[_Record]] = {}
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._events: List[Dict] = []
        self._file = None
        self.jsonl_path = None
        self.enabled = enabled
        if jsonl_path is not None:
            self.open_sink(jsonl_path)

    # -- sink ---------------------------------------------------------------
    def open_sink(self, path: str) -> None:
        """Attach (or switch) the JSONL sink.  Line-buffered: each record
        is one ``write()`` of one line, so a crash loses at most the
        record being written."""
        with self._lock:
            if self._file is not None:
                self._file.close()
            self._file = open(path, "a", buffering=1)
            self.jsonl_path = path

    def _emit(self, rec: Dict, critical: bool = False) -> None:
        if self._file is None:
            return
        self._file.write(json.dumps(rec) + "\n")
        if critical:
            self._file.flush()
            os.fsync(self._file.fileno())

    # -- spans --------------------------------------------------------------
    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, **attrs):
        """Context manager timing ``name``; the disabled path, and any
        span opened while jax traces, return a shared null object (no
        clock read, no annotation, no record)."""
        if not self.enabled or _tracing():
            return _NULL
        return _Span(self, name, attrs)

    def interval(self, name: str, start: float, end: float,
                 **attrs) -> None:
        """Record a finished interval ``[start, end]`` on
        ``time.perf_counter()``: a span that is no nested context, so it
        has no parent and no profiler annotation."""
        if not self.enabled:
            return
        self._record_span(name, start, end, None, attrs)

    def _record_span(self, name: str, start: float, end: float,
                     parent: Optional[str], attrs: Dict) -> None:
        with self._lock:
            recs = self._spans.setdefault(name, [])
            recs.append((start, end, parent, attrs))
            if len(recs) > self.max_events:
                del recs[:len(recs) // 2]
            rec = {"kind": "span", "name": name, "wall": time.time(),
                   "dur_s": end - start, "traced": False}
            if parent:
                rec["parent"] = parent
            if attrs:
                rec["attrs"] = attrs
            self._emit(rec)

    def records(self, name: Optional[str] = None) -> List[Dict]:
        """The span records held in memory (``name`` alone, or all), in
        start order: ``{name, start, end, parent, attrs}``."""
        with self._lock:
            out = [{"name": n, "start": s, "end": e, "parent": p,
                    "attrs": a}
                   for n, recs in self._spans.items()
                   if name is None or n == name
                   for s, e, p, a in recs]
        return sorted(out, key=lambda r: r["start"])

    # -- counters / gauges / events -----------------------------------------
    def count(self, name: str, n: float = 1) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n
            self._emit({"kind": "counter", "name": name, "wall": time.time(),
                        "n": n})

    def gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._gauges[name] = value
            self._emit({"kind": "gauge", "name": name, "wall": time.time(),
                        "value": value})

    def event(self, name: str, critical: bool = False, **attrs) -> None:
        """Point-in-time record.  ``critical=True`` (rollback,
        quarantine, crash) flushes and fsyncs the sink before returning:
        the fault trace must survive the process dying on the next
        instruction."""
        if not self.enabled:
            return
        with self._lock:
            rec = {"kind": "event", "name": name, "wall": time.time()}
            if attrs:
                rec["attrs"] = attrs
            if critical:
                rec["critical"] = True
            self._events.append(rec)
            if len(self._events) > self.max_events:
                del self._events[:len(self._events) // 2]
            self._emit(rec, critical=critical)

    # -- rollup -------------------------------------------------------------
    def summary(self) -> Dict:
        """Rollup of everything recorded so far: per span name
        ``{count, total_s, p50_s, p95_s (nearest rank), p99_s, max_s}``,
        counters, gauges, the number of events."""
        with self._lock:
            spans = {}
            for name, recs in self._spans.items():
                d = sorted(e - s for s, e, _, _ in recs)
                n = len(d)
                spans[name] = {
                    "count": n,
                    "total_s": sum(d),
                    "p50_s": d[(n - 1) // 2],
                    "p95_s": d[max(math.ceil(0.95 * n), 1) - 1],
                    "p99_s": d[min(n - 1, (99 * n) // 100)],
                    "max_s": d[-1],
                }
            return {"spans": spans,
                    "counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "events": len(self._events)}

    def events(self, name: Optional[str] = None) -> List[Dict]:
        with self._lock:
            return [e for e in self._events
                    if name is None or e["name"] == name]

    def flush(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.flush()
                os.fsync(self._file.fileno())

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()
            self._gauges.clear()
            self._events.clear()

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


# -- module default: what library instrumentation points bind to ------------
_default = Telemetry()


def default() -> Telemetry:
    return _default


def configure(enabled: bool = True,
              jsonl_path: Optional[str] = None) -> Telemetry:
    """Switch the module default on (or off) and optionally attach a
    JSONL sink; returns the default instance."""
    _default.enabled = enabled
    if jsonl_path is not None:
        _default.open_sink(jsonl_path)
    return _default


def span(name: str, **attrs):
    return _default.span(name, **attrs)


def count(name: str, n: float = 1) -> None:
    _default.count(name, n)


def gauge(name: str, value: float) -> None:
    _default.gauge(name, value)


def event(name: str, critical: bool = False, **attrs) -> None:
    _default.event(name, critical=critical, **attrs)


def summary() -> Dict:
    return _default.summary()

