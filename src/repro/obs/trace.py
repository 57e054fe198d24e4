"""Low-overhead span tracer — the observability seam of the stack.

The paper argues through *observed* work/ordering trade-offs, and the
AGM superstep is the natural unit of observation; this module supplies
the wall-clock half of that observation: nested spans and point events
with monotonic timestamps, recorded by every layer of the stack
(``Solver.solve`` → partition → engine → repair loop, the
``repro.tune`` segment loop, the serving tier's admission → flush →
solve path).  Design constraints, in order:

* **near-zero cost when off** — no tracer installed and no profiler
  session means one module-global read and one check of the profiler
  per ``span()`` (a global read per ``event()``) and a shared no-op
  context manager; no allocation, no locking, no clock read.
* **thread-safe when on** — the serving tier may pump the router from
  a different thread than the one building landmark indexes; records
  append under a lock and the span *stack* (parent attribution) is
  thread-local.
* **testable time** — the clock is injected (``Tracer(clock=...)``),
  so tests assert exact durations instead of sleeping.
* **bounded** — a flight recorder must not OOM the process it
  observes; past ``max_records`` new records are dropped and counted.
* **on the profiler's clock** — while a ``jax.profiler`` session
  records, every span is also a ``jax.profiler.TraceAnnotation`` of
  the same name (its scalar attributes as the annotation's arguments),
  whether or not a :class:`Tracer` is installed, so the program's host
  spans sit on the device trace's timeline.

Usage::

    from repro.obs import trace as obs

    tracer = obs.Tracer()
    with obs.use_tracer(tracer):
        with obs.span("solve", spec="delta:5/sparse") as sp:
            obs.event("engine_cache_miss")
            sp.set(supersteps=17)
    tracer.spans[0].duration_s

Spans carry a ``span_id``/``parent_id`` so exporters can rebuild the
tree, and free-form ``attrs`` — the serving tier records the
query-id → flush → solve correlation key there, which is what lets a
p99 outlier be traced to the batch and spec that served it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Any, Callable, Iterator, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Event",
    "Span",
    "SpanHandle",
    "Tracer",
    "current_tracer",
    "event",
    "now",
    "set_tracer",
    "span",
    "use_tracer",
]


@dataclasses.dataclass
class Span:
    """One closed span: a named wall-clock interval with attributes."""

    name: str
    t0: float
    t1: float
    attrs: dict[str, Any]
    span_id: int
    parent_id: Optional[int]
    thread: str

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def as_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Event:
    """One point-in-time record, attributed to the enclosing span."""

    name: str
    t: float
    attrs: dict[str, Any]
    span_id: Optional[int]
    thread: str

    def as_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


class _Annotated:
    """The profiler half of a span: while a profiler session records,
    a ``TraceAnnotation`` of the span's name from enter to exit, with
    the scalar attributes known at enter as its arguments."""

    __slots__ = ("name", "attrs", "_annotation")

    def __init__(self, name: str, attrs: dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._annotation: Optional[TraceAnnotation] = None

    def set(self, **attrs: Any) -> "_Annotated":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Annotated":
        if TraceAnnotation.is_enabled():
            args = {k: v for k, v in self.attrs.items()
                    if isinstance(v, (bool, int, float, str))}
            self._annotation = TraceAnnotation(self.name, **args)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None


class SpanHandle(_Annotated):
    """Context manager for one open span.  ``set(**attrs)`` adds
    attributes any time before exit (the tune controller records its
    per-segment decision on the already-open segment span)."""

    __slots__ = ("_tracer", "t0", "span_id", "parent_id")

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        attrs: dict[str, Any],
        parent_id: Optional[int],
    ):
        super().__init__(name, attrs)
        self._tracer = tracer
        self.t0 = tracer.clock()
        self.span_id = tracer._next_id()
        self.parent_id = parent_id

    def __enter__(self) -> "SpanHandle":
        self._tracer._push(self)
        super().__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        super().__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._tracer._pop(self)


class _NoopSpan:
    """Shared do-nothing span for the tracer-off fast path."""

    __slots__ = ()

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


_NOOP = _NoopSpan()


class Tracer:
    """Thread-safe span/event recorder with an injectable monotonic
    clock and a bounded record buffer.

    ``registry`` (optional, a :class:`repro.obs.export.MetricsRegistry`)
    receives every closed span as a ``repro_span_seconds{span=...}``
    histogram observation and every event as a
    ``repro_events_total{event=...}`` counter increment — the live
    metrics surface is fed by the same instrumentation as the trace.
    """

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        registry: Optional[Any] = None,
        max_records: int = 200_000,
    ):
        if max_records <= 0:
            raise ValueError(f"max_records must be positive: {max_records}")
        self.clock = clock
        self.registry = registry
        self.max_records = int(max_records)
        self.spans: list[Span] = []
        self.events: list[Event] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    # -- record plumbing ----------------------------------------------

    def _next_id(self) -> int:
        return next(self._ids)

    def _stack(self) -> list[SpanHandle]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = []
            self._local.stack = st
        return st

    def current_span_id(self) -> Optional[int]:
        st = self._stack()
        return st[-1].span_id if st else None

    def _push(self, handle: SpanHandle) -> None:
        self._stack().append(handle)

    def _pop(self, handle: SpanHandle) -> None:
        t1 = self.clock()
        st = self._stack()
        if st and st[-1] is handle:
            st.pop()
        rec = Span(
            name=handle.name,
            t0=handle.t0,
            t1=t1,
            attrs=handle.attrs,
            span_id=handle.span_id,
            parent_id=handle.parent_id,
            thread=threading.current_thread().name,
        )
        with self._lock:
            if len(self.spans) + len(self.events) >= self.max_records:
                self.dropped += 1
            else:
                self.spans.append(rec)
        if self.registry is not None:
            self.registry.histogram(
                "repro_span_seconds",
                help="wall seconds per traced span",
                labels={"span": handle.name},
            ).observe(rec.duration_s)

    # -- public API ----------------------------------------------------

    def span(self, name: str, **attrs: Any) -> SpanHandle:
        return SpanHandle(self, name, attrs, self.current_span_id())

    def event(self, name: str, **attrs: Any) -> None:
        rec = Event(
            name=name,
            t=self.clock(),
            attrs=attrs,
            span_id=self.current_span_id(),
            thread=threading.current_thread().name,
        )
        with self._lock:
            if len(self.spans) + len(self.events) >= self.max_records:
                self.dropped += 1
            else:
                self.events.append(rec)
        if self.registry is not None:
            self.registry.counter(
                "repro_events_total",
                help="traced point events",
                labels={"event": name},
            ).inc()

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.events.clear()
            self.dropped = 0

    def find(self, name: str) -> list[Span]:
        """Closed spans with this name (test convenience)."""
        with self._lock:
            return [s for s in self.spans if s.name == name]

    def children_of(self, span_id: int) -> list[Span]:
        with self._lock:
            return [s for s in self.spans if s.parent_id == span_id]


# ---------------------------------------------------------------------
# module-level current tracer (the instrumentation call sites)
# ---------------------------------------------------------------------

_TRACER: Optional[Tracer] = None


def current_tracer() -> Optional[Tracer]:
    return _TRACER


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as the process-wide current tracer; returns
    the previous one.  ``None`` disables tracing (the fast path)."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


@contextlib.contextmanager
def use_tracer(tracer: Optional[Tracer]) -> Iterator[Optional[Tracer]]:
    """Scoped :func:`set_tracer` — restores the previous tracer on
    exit, so tests and CLIs never leak instrumentation state."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


def span(name: str, **attrs: Any):
    """Open a span on the current tracer; with none installed, only a
    profiler annotation while a profiler session records, else a
    no-op.  Usable as a context manager; the yielded handle accepts
    ``.set(**attrs)``."""
    t = _TRACER
    if t is not None:
        return t.span(name, **attrs)
    if TraceAnnotation.is_enabled():
        return _Annotated(name, attrs)
    return _NOOP


def event(name: str, **attrs: Any) -> None:
    """Record a point event on the current tracer (no-op when off)."""
    t = _TRACER
    if t is not None:
        t.event(name, **attrs)


def now() -> float:
    """The current tracer's clock (``time.perf_counter`` when tracing
    is off) — lets instrumented code stamp records consistently with
    the spans around them."""
    t = _TRACER
    return t.clock() if t is not None else time.perf_counter()
