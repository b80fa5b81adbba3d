"""Spans and counters of the served path, on the host's monotonic clock.

One process-wide ``TRACER``::

    with TRACER.span("helix.step"):          # a span on this thread
        ...
    TRACER.record(name, t0, t1, **attrs)     # a span another thread began
    TRACER.count("preemptions")              # a counter

A span is ``Span(name, t0, t1, id, parent, attrs)``: ``t0``/``t1`` are
``time.monotonic()`` seconds, ``parent`` is the id of the innermost span
open on the same thread when it began (``None`` for ``record``).  Every
``span`` also opens a ``jax.profiler.TraceAnnotation`` of the same name
carrying its int attrs, so a profiler trace shows it on the host plane, on
the device trace's clock; other attrs stay in memory only.  No attr may
hold a device array: readers run after the device buffers are freed.

Spans live in a bounded ring.  Once full, the oldest span is evicted;
``dropped`` counts evictions and ``evicted_t1`` holds the end time of the
newest evicted span, so a reader can tell whether its window is whole.
Per-name totals (count, seconds) and the counters are kept apart from the
ring and never evicted: ``summary()`` is the operator's view.

Each backend compile (or persistent-cache load) is counted as ``compiles``
and charged to the innermost span open on the compiling thread as
``compiles.<span name>``.  ``serving/README.md`` lists every span and
counter the serving path records.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax

# A benchmark run (set-up, pre-roll, a 51 s window, drain) records about
# 650 spans a second of serving; 2**18 holds several such runs whole.
CAPACITY = 1 << 18
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    id: int
    parent: Optional[int]
    attrs: Dict[str, Any]


_NO_ATTRS: Dict[str, Any] = {}
_Annotation = jax.profiler.TraceAnnotation


def _span(*fields) -> Span:
    """A ``Span`` without ``NamedTuple``'s argument handling: this runs
    once per span on the serving path."""
    return tuple.__new__(Span, fields)


class _Open:
    """One open span (the context manager ``Tracer.span`` returns)."""

    __slots__ = ("tracer", "name", "attrs", "id", "parent", "t0", "t1",
                 "_stack", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Open":
        tr = self.tracer
        stack = self._stack = tr._stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(tr._ids)
        stack.append(self)
        attrs = self.attrs
        self._ann = _Annotation(self.name, **{
            k: v for k, v in attrs.items() if type(v) is int}) \
            if attrs else _Annotation(self.name)
        self._ann.__enter__()
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc) -> None:
        tr = self.tracer
        self.t1 = tr.clock()
        self._ann.__exit__(*exc)
        self._stack.pop()
        tr._add(_span(self.name, self.t0, self.t1, self.id, self.parent,
                      self.attrs or _NO_ATTRS))


class Tracer:
    """Spans in a bounded ring, per-name totals and counters; thread-safe.
    ``clock`` is the time source (``time.monotonic``; tests pass a fake)."""

    def __init__(self, capacity: int = CAPACITY,
                 clock: Callable[[], float] = time.monotonic):
        self.clock = clock
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count()
        self._totals: Dict[str, List] = {}
        self.counters: Dict[str, int] = {}
        self.dropped = 0
        self.evicted_t1: Optional[float] = None

    def _stack(self) -> List[_Open]:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def _add(self, sp: Span) -> None:
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                old = ring[0]
                self.dropped += 1
                if self.evicted_t1 is None or old.t1 > self.evicted_t1:
                    self.evicted_t1 = old.t1
            ring.append(sp)
            tot = self._totals.get(sp.name)
            if tot is None:
                tot = self._totals[sp.name] = [0, 0.0]
            tot[0] += 1
            tot[1] += sp.t1 - sp.t0

    def span(self, name: str, **attrs) -> _Open:
        """A context manager recording ``name`` from entry to exit; its
        ``t0``/``t1`` are readable once it has exited."""
        return _Open(self, name, attrs)

    def record(self, name: str, t0: float, t1: float, **attrs) -> None:
        """A finished span whose start was stamped elsewhere (another
        thread, an earlier call); it has no parent."""
        self._add(_span(name, t0, t1, next(self._ids), None,
                        attrs or _NO_ATTRS))

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def innermost(self) -> Optional[str]:
        """Name of the innermost span open on this thread."""
        s = self._stack()
        return s[-1].name if s else None

    def spans(self) -> List[Span]:
        """The spans the ring holds, oldest first."""
        with self._lock:
            return list(self._ring)

    def summary(self) -> Dict[str, Any]:
        """Counters, and count and seconds per span name, since start."""
        with self._lock:
            return {"counters": dict(self.counters),
                    "spans": {k: {"count": c, "seconds": s}
                              for k, (c, s) in sorted(self._totals.items())},
                    "dropped": self.dropped}


TRACER = Tracer()


def _on_duration(event: str, secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        TRACER.count("compiles")
        inner = TRACER.innermost()
        if inner is not None:
            TRACER.count(f"compiles.{inner}")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
