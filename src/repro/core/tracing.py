"""The program's one tracer: spans and instants on the host.

Every record is stamped with ``time.perf_counter()``, the clock the
benchmark stamps tokens and its window with.  A span is also entered as
``jax.profiler.TraceAnnotation(name)``: in a profiler session it lands
on the host plane beside the device ops, on their clock.

- ``span(name, rid=None)`` is a context manager.  It records the name,
  ``t0``/``t1``, the parent (the span open on the same thread when it
  began), the request id, the thread and the engine index (given, or
  else the parent's, so replicas sharing a process stay apart).
- ``mark(name, rid, **data)`` records an instant.

Spans and marks share one ring of ``CAPACITY`` records, room for a few
minutes of serving steps; the oldest fall out.  Program builds are
spans under the span open on the building thread, fed by
``jax.monitoring`` at each phase's end: ``trace:<fun_name>`` (Python to
jaxpr), ``lower:<module>`` (jaxpr to MLIR) and ``build:<fun_name>``
(the backend compile, or a load from the persistent cache).  They are
not profiler annotations: XLA's own compile events stand for them there.

The process has one tracer, ``tracer()``, so a reader with no handle on
an engine (the benchmark, an operator's console) can get the records.
Recording is always on: a span costs a few microseconds.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16
# jax.monitoring duration events of a program build -> span name prefix
BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "build",
}

_now = time.perf_counter


class Span:
    """One timed region: a context manager while open, a record after."""
    __slots__ = ("name", "rid", "engine", "id", "parent", "thread", "t0",
                 "t1", "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, rid: Optional[int],
                 engine: Optional[int]):
        self.name, self.rid, self.engine = name, rid, engine
        self._tracer = tracer
        self.t1: Optional[float] = None

    def __enter__(self) -> "Span":
        tr = self._tracer
        tr._link(self)
        tr._local.stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = _now()
        self._ann.__exit__(None, None, None)
        tr = self._tracer
        stack = tr._local.stack
        if stack[-1] is self:
            stack.pop()
        else:
            stack.remove(self)
        self._tracer = self._ann = None
        tr._ring.append(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, rid={self.rid}, engine={self.engine}, "
                f"t0={self.t0}, t1={self.t1}, parent={self.parent})")


class Mark(NamedTuple):
    """One instant."""
    name: str
    t: float
    rid: Optional[int]
    engine: Optional[int]
    thread: int
    data: Dict[str, object]


class _Local(threading.local):
    def __init__(self):
        self.stack: List[Span] = []       # open spans, innermost last
        self.ident = threading.get_ident()


class Tracer:
    def __init__(self):
        self._ring: deque = deque(maxlen=CAPACITY)
        self._local = _Local()
        self._ids = itertools.count(1)
        self._engines = itertools.count()

    def span(self, name: str, rid: Optional[int] = None, *,
             engine: Optional[int] = None) -> Span:
        return Span(self, name, rid, engine)

    def mark(self, name: str, rid: Optional[int] = None, *,
             engine: Optional[int] = None, **data) -> None:
        local = self._local
        if engine is None and local.stack:
            engine = local.stack[-1].engine
        self._ring.append(Mark(name, _now(), rid, engine, local.ident, data))

    def new_engine(self) -> int:
        """A fresh engine index for the records of one engine."""
        return next(self._engines)

    def spans(self) -> List[Span]:
        return [r for r in list(self._ring) if isinstance(r, Span)]

    def marks(self) -> List[Mark]:
        return [r for r in list(self._ring) if isinstance(r, Mark)]

    def _link(self, sp: Span) -> None:
        """Give ``sp`` its id, its thread and its parent, the span open on
        this thread; an ``sp`` with no engine takes the parent's."""
        local = self._local
        parent = local.stack[-1] if local.stack else None
        sp.parent = None if parent is None else parent.id
        if sp.engine is None and parent is not None:
            sp.engine = parent.engine
        sp.thread = local.ident
        sp.id = next(self._ids)

    def _build(self, event: str, duration: float, fun_name="",
               **_) -> None:
        """A ``jax.monitoring`` duration listener: a build phase that has
        just ended on this thread becomes a finished span."""
        kind = BUILD_EVENTS.get(event)
        if kind is None:
            return
        sp = Span(self, f"{kind}:{fun_name}", None, None)
        self._link(sp)
        sp.t1 = _now()
        sp.t0 = sp.t1 - duration
        sp._tracer = sp._ann = None
        self._ring.append(sp)


_TRACER: Optional[Tracer] = None
_INIT = threading.Lock()


def tracer() -> Tracer:
    """The process's tracer (made, and hooked to ``jax.monitoring``, on
    first use)."""
    global _TRACER
    if _TRACER is None:
        with _INIT:
            if _TRACER is None:
                t = Tracer()
                jax.monitoring.register_event_duration_secs_listener(
                    t._build)
                _TRACER = t
    return _TRACER


def traced(name: str):
    """Method decorator: run each call inside span ``name``, with the
    request id taken from the call's first argument."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(self, rid, *args, **kwargs):
            with tracer().span(name, rid):
                return fn(self, rid, *args, **kwargs)
        return call
    return wrap


class TracedLock:
    """A ``threading.Lock`` that records how long an acquirer waited for
    it, when it had to wait, as the span ``<name>.wait``.  The hold is
    not recorded."""
    __slots__ = ("_lock", "_wait")

    def __init__(self, name: str):
        self._lock = threading.Lock()
        self._wait = f"{name}.wait"

    def __enter__(self) -> "TracedLock":
        if not self._lock.acquire(blocking=False):
            with tracer().span(self._wait):
                self._lock.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self._lock.release()
