"""The telemetry bus: spans, sinks, and the trace exporters.

One :class:`Telemetry` instance is the run's event stream.  Producers
(`repro.core.engine`, `repro.checkpoint.store`,
`repro.runtime.supervisor`) call :meth:`Telemetry.emit` with a kind
from the closed taxonomy of :mod:`repro.runtime.events` and wrap their
phase structure in :meth:`Telemetry.span`; consumers attach *sinks* —
an in-memory ring (:class:`RingSink`), a JSONL file
(:class:`JSONLSink`), or anything with a ``write(event)`` method.

Two contracts make it safe to leave on in production (pinned by
tests/test_telemetry.py):

* **off is a true no-op** — the disabled singleton
  (:data:`NULL_TELEMETRY`, what ``telemetry=None`` resolves to) is
  falsy, its ``emit`` returns before building any record, and its
  ``span`` hands back one reusable null context manager: no
  allocation, no lock, no clock read;
* **on is bit-identical** — telemetry only *observes* host values the
  engine already materializes at epoch boundaries (the per-epoch
  counters ride the jitted state whether or not anyone reads them), so
  enabling it changes neither the compiled computations nor the RNG
  stream on any lane.

Spans nest per thread (a thread-local stack supplies ``parent`` ids),
and emission is thread-safe — the async checkpoint publisher emits
from its background thread onto the same bus, distinguished by the
event's ``tid``.

While a ``jax.profiler`` trace is running, every span is also a
``jax.profiler.TraceAnnotation`` of the same name and fields, bus on or
off: the phases then sit on the trace's host plane, on the clock of the
device operations, and each closes with the compile and trace counters
it spent (below) as stats.  Every emitted event is likewise left as an
instant annotation of its kind.  With no trace running and the bus off,
nothing of this runs.

The compile and trace counter: :func:`host_counter_totals` reads
per-thread running totals of JAX's backend compiles, persistent-cache
loads and jaxpr traces and MLIR lowerings, kept by ``jax.monitoring``
listeners registered once per process on first use;
:func:`host_counter_delta` turns readings into the counts and seconds
in between.  ``run_adaptive`` reports them per phase
(``AdaptiveRunResult.host_counters``).

Exporter: :func:`chrome_trace` turns a stream into the Chrome/Perfetto
trace-event JSON (load at ``chrome://tracing`` or ui.perfetto.dev).
"""
from __future__ import annotations

import itertools
import json
import threading
import time

from jax import monitoring
from jax.profiler import TraceAnnotation

from .events import Event, to_json, validate_event

__all__ = ["Telemetry", "NULL_TELEMETRY", "RingSink", "JSONLSink",
           "NullSink", "resolve_telemetry", "chrome_trace",
           "write_chrome_trace", "HOST_COUNTER_KEYS", "host_counter_totals",
           "host_counter_delta"]

# A profiler trace is running: spans and events are mirrored into it.
_profiling = TraceAnnotation.is_enabled


class NullSink:
    """Swallows everything (the explicit no-op sink)."""

    def write(self, ev: Event):
        pass


class RingSink:
    """Keeps the newest ``capacity`` events in memory (0 = unbounded)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._events: list = []
        self._lock = threading.Lock()

    def write(self, ev: Event):
        with self._lock:
            self._events.append(ev)
            if self.capacity and len(self._events) > self.capacity:
                del self._events[: len(self._events) - self.capacity]

    @property
    def events(self) -> list:
        with self._lock:
            return list(self._events)


class JSONLSink:
    """Appends one JSON line per event to ``path`` (thread-safe; each
    line is flushed so a crashed run leaves a readable prefix)."""

    def __init__(self, path: str):
        self.path = str(path)
        self._lock = threading.Lock()
        self._f = open(self.path, "a")

    def write(self, ev: Event):
        line = to_json(ev)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self):
        with self._lock:
            if not self._f.closed:
                self._f.close()


# ---------------------------------------------------------------------------
# The compile and trace counter
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
_JAXPR_TRACE = _TRACE_LOWER[0]

# What host_counter_delta reports: backend compiles (count, seconds)
# net of persistent-cache loads, the loads themselves, and jaxpr traces
# (count) with the seconds of traces and MLIR lowerings together.
HOST_COUNTER_KEYS = ("compile_s", "compiles", "cache_load_s", "cache_hits",
                     "trace_lower_s", "traces")


class _Totals(threading.local):
    """Per-thread running totals, as JAX reports them: a persistent-cache
    hit passes through the backend-compile event with its load time."""

    def __init__(self):
        self.backend_s = 0.0
        self.backend_n = 0
        self.load_s = 0.0
        self.hits = 0
        self.trace_lower_s = 0.0
        self.traces = 0


_totals = _Totals()
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, duration: float, **_):
    t = _totals
    if event == _BACKEND_COMPILE:
        t.backend_s += duration
        t.backend_n += 1
    elif event == _CACHE_RETRIEVAL:
        t.load_s += duration
    elif event in _TRACE_LOWER:
        t.trace_lower_s += duration
        if event == _JAXPR_TRACE:
            t.traces += 1


def _on_event(event: str, **_):
    if event == _CACHE_HIT:
        _totals.hits += 1


def host_counter_totals() -> tuple:
    """This thread's running totals (an opaque reading for
    :func:`host_counter_delta`); the first call registers the
    ``jax.monitoring`` listeners, once per process."""
    global _listening
    if not _listening:
        with _listen_lock:
            if not _listening:
                monitoring.register_event_duration_secs_listener(
                    _on_duration)
                monitoring.register_event_listener(_on_event)
                _listening = True
    t = _totals
    return (t.backend_s, t.backend_n, t.load_s, t.hits, t.trace_lower_s,
            t.traces)


def host_counter_delta(*readings: tuple) -> dict:
    """The compiles, cache loads and traces spent between readings of
    :func:`host_counter_totals` taken as (start, end) pairs, summed over
    the pairs, keyed by :data:`HOST_COUNTER_KEYS`."""
    pairs = list(zip(readings[0::2], readings[1::2]))
    bs, bn, ls, hits, tls, tr = (sum(e[i] - b[i] for b, e in pairs)
                                 for i in range(6))
    return {"compile_s": bs - ls, "compiles": bn - hits,
            "cache_load_s": ls, "cache_hits": hits,
            "trace_lower_s": tls, "traces": tr}


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

class _NullSpan:
    """The reusable context manager disabled spans return."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **stats):
        pass


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    """One live span: emits begin on enter, end (with seconds, the
    stats :meth:`set` left, and an ``error`` field when exiting on an
    exception) on exit."""

    __slots__ = ("_tel", "name", "fields", "span_id", "_t0", "_stats")

    def __init__(self, tel: "Telemetry", name: str, fields: dict):
        self._tel = tel
        self.name = name
        self.fields = fields
        self.span_id = None
        self._t0 = 0.0
        self._stats = {}

    def set(self, **stats):
        """Numbers the phase counted, for its end event."""
        self._stats.update(stats)

    def __enter__(self):
        tel = self._tel
        self.span_id = next(tel._span_ids)
        stack = tel._span_stack()
        parent = stack[-1] if stack else None
        self._t0 = tel._clock()
        tel._push(Event("span.begin", self._t0,
                        {"name": self.name, **self.fields},
                        span=self.span_id, parent=parent,
                        tid=threading.get_ident()))
        stack.append(self.span_id)
        return self

    def __exit__(self, exc_type, exc, tb):
        tel = self._tel
        stack = tel._span_stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        t1 = tel._clock()
        fields = {**self._stats, "name": self.name, "seconds": t1 - self._t0}
        if exc_type is not None:
            fields["error"] = exc_type.__name__
        parent = stack[-1] if stack else None
        tel._push(Event("span.end", t1, fields, span=self.span_id,
                        parent=parent, tid=threading.get_ident()))
        return False


class _TracedSpan:
    """A span while a profiler trace runs: a ``TraceAnnotation`` of the
    span's name and fields, closed with the host counters it spent and
    the stats :meth:`set` left, around the bus span when the bus is on."""

    __slots__ = ("_ann", "_bus", "_c0", "_stats")

    def __init__(self, tel: "Telemetry", name: str, fields: dict):
        self._ann = TraceAnnotation(name, **fields)
        self._bus = _SpanCtx(tel, name, fields) if tel._enabled else None
        self._c0 = None
        self._stats = {}

    def set(self, **stats):
        """Numbers the phase counted, for its annotation's stats."""
        self._stats.update(stats)
        if self._bus is not None:
            self._bus.set(**stats)

    def __enter__(self):
        self._ann.__enter__()
        self._c0 = host_counter_totals()
        if self._bus is not None:
            self._bus.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._bus is not None:
            self._bus.__exit__(exc_type, exc, tb)
        self._ann.set_metadata(
            **host_counter_delta(self._c0, host_counter_totals()),
            **self._stats)
        self._ann.__exit__(exc_type, exc, tb)
        return False


class Telemetry:
    """The bus.  ``sinks`` is an iterable of objects with
    ``write(event)``; ``validate=True`` checks every emitted event
    against the taxonomy at the producer (tests and the CI smoke turn
    it on; production leaves it off — the taxonomy audit is static).
    """

    def __init__(self, sinks=(), *, enabled: bool = True,
                 validate: bool = False, clock=time.monotonic):
        self.sinks = list(sinks)
        self._enabled = bool(enabled)
        self._validate = bool(validate)
        self._clock = clock
        self._span_ids = itertools.count(1)
        self._local = threading.local()

    def __bool__(self) -> bool:
        return self._enabled

    def add_sink(self, sink) -> "Telemetry":
        self.sinks.append(sink)
        return self

    def _span_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, ev: Event):
        if self._validate:
            validate_event(ev)
        for s in self.sinks:
            s.write(ev)

    def emit(self, kind: str, **fields):
        """Emit one instant event (kind from the registered taxonomy);
        while a profiler trace runs, also an instant annotation of it."""
        if _profiling():
            with TraceAnnotation(kind, **fields):
                pass
        if not self._enabled:
            return
        stack = self._span_stack()
        self._push(Event(kind, self._clock(), fields,
                         parent=stack[-1] if stack else None,
                         tid=threading.get_ident()))

    def span(self, name: str, **fields):
        """Context manager timing a named phase; spans nest per thread
        (``parent`` ids), and the end event carries the duration.  While
        a profiler trace runs, the span is also a trace annotation.  The
        context's ``set(**stats)`` adds numbers the phase counted to its
        end event and its annotation (a no-op when both are off)."""
        if _profiling():
            return _TracedSpan(self, name, fields)
        if not self._enabled:
            return _NULL_SPAN
        return _SpanCtx(self, name, fields)

    def events(self) -> list:
        """The events of the first RingSink (convenience for tests)."""
        for s in self.sinks:
            if isinstance(s, RingSink):
                return s.events
        return []

    def close(self):
        for s in self.sinks:
            close = getattr(s, "close", None)
            if close is not None:
                close()


# The disabled singleton every telemetry=None call site resolves to.
NULL_TELEMETRY = Telemetry((), enabled=False)


def resolve_telemetry(arg) -> Telemetry:
    """Normalize a ``telemetry=`` argument: ``None`` -> the disabled
    singleton, a :class:`Telemetry` -> itself, a path string -> a fresh
    bus writing JSONL there, a sink object -> a bus wrapping it."""
    if arg is None:
        return NULL_TELEMETRY
    if isinstance(arg, Telemetry):
        return arg
    if isinstance(arg, (str, bytes)) or hasattr(arg, "__fspath__"):
        return Telemetry([JSONLSink(arg)])
    if hasattr(arg, "write"):
        return Telemetry([arg])
    raise TypeError(
        f"telemetry must be None, a Telemetry, a JSONL path or a sink "
        f"object with .write(event); got {type(arg).__name__}")


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def chrome_trace(events) -> dict:
    """Render an event stream (Events or parsed JSONL dicts) as
    Chrome/Perfetto trace-event JSON.

    Matched ``span.begin``/``span.end`` pairs become ``"ph": "X"``
    complete events (µs timestamps relative to the stream's first
    event, one track per emitting thread); instant events become
    ``"ph": "i"`` thread-scoped instants carrying their payload as
    ``args``.  Unmatched begins are closed at the stream's end so a
    truncated trace still loads.
    """
    from .events import from_json
    evs = [e if isinstance(e, Event) else from_json(e) for e in events]
    if not evs:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(e.t for e in evs)
    t_end = max(e.t for e in evs)
    us = lambda t: (t - t0) * 1e6  # noqa: E731
    open_spans: dict = {}
    rows = []
    for e in evs:
        if e.kind == "span.begin":
            open_spans[e.span] = e
        elif e.kind == "span.end":
            b = open_spans.pop(e.span, None)
            if b is None:
                continue
            rows.append({
                "name": b.fields.get("name", f"span{e.span}"),
                "ph": "X", "ts": us(b.t), "dur": max(0.0, us(e.t) - us(b.t)),
                "pid": 0, "tid": b.tid,
                "args": {k: v for k, v in {**b.fields, **e.fields}.items()
                         if k != "name"}})
        else:
            rows.append({"name": e.kind, "ph": "i", "s": "t",
                         "ts": us(e.t), "pid": 0, "tid": e.tid,
                         "args": dict(e.fields)})
    for b in open_spans.values():    # close truncated spans at stream end
        rows.append({"name": b.fields.get("name", f"span{b.span}"),
                     "ph": "X", "ts": us(b.t),
                     "dur": max(0.0, us(t_end) - us(b.t)),
                     "pid": 0, "tid": b.tid,
                     "args": {k: v for k, v in b.fields.items()
                              if k != "name"}})
    rows.sort(key=lambda r: r["ts"])
    return {"traceEvents": rows, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, events) -> str:
    """Write :func:`chrome_trace` JSON to ``path``; returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(events), f)
    return str(path)
