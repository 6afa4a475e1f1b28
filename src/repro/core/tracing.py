"""Spans and events recorded inside the serving engine, on the host clock.

A `Tracer` records what the engine does: spans (a name, a start and an end
on `time.perf_counter()`, the enclosing span, the request `(cid, turn)` the
span serves, a few attributes) and timestamped events (compiles). Records
go to a bounded in-memory ring, `Tracer.records`; nothing is written
anywhere and nothing is exported.

Two kinds of span:

* lexical spans (`span()`, or the `traced` method decorator) around one
  call. They nest on the tracer's stack: a span's parent is the innermost
  span open when it starts, and it inherits that span's request. Each also
  enters `jax.profiler.TraceAnnotation(name)`, so while a profile is being
  taken the same span lies in it, on the device trace's clock. Where the
  code already times a call, `timed(t0, dt)` gives the span those same
  readings;
* request spans (`begin()` / `end()`), which open in one event of the
  serving loop and close in a later one: `engine.turn` (the turn became
  runnable -> its opening token) and `engine.admission` (parked ->
  admitted, a child of the turn). They are records only, never
  annotations. `resume(cid, turn)` makes the open turn span the parent of
  the lexical spans opened inside it, and lends them its request.

Events: `event(name, **attrs)`; every enabled tracer receives an
`engine.compile` event for each backend compile or persistent-cache load
JAX reports (one process-wide `jax.monitoring` listener), naming the
program and the span open at the time.

Off (the default) is the off path: `span()` and `resume()` return one
shared no-op context, `begin`/`end`/`event`/`timed`/`note` return at once.
Nothing is recorded, no annotation is entered, no clock is read.

The runtime contract (`core.runtime.Runtime`) holds `NULL_TRACER`, so a
backend that never enables tracing (the simulator) pays one attribute
check per admission."""
from __future__ import annotations

import collections
import functools
import itertools
import time
import weakref
from typing import Any, Deque, Dict, List, Optional, Tuple

TURN = "engine.turn"
ADMISSION = "engine.admission"
COMPILE = "engine.compile"

RING = 1 << 16  # records kept; the oldest fall out first


class Record:
    """One finished span (t1 > t0 or equal) or event (t1 == t0)."""
    __slots__ = ("id", "parent", "name", "t0", "t1", "cid", "turn", "attrs")

    def __init__(self, id_, parent, name, t0, t1, cid, turn, attrs):
        self.id = id_
        self.parent = parent
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.cid = cid
        self.turn = turn
        self.attrs = attrs

    def __repr__(self):
        return (f"Record({self.id}, parent={self.parent}, {self.name!r}, "
                f"cid={self.cid}, turn={self.turn}, {self.attrs})")


class _NoSpan:
    """The shared no-op context every call returns while tracing is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("tracer", "rec", "ann", "fixed")

    def __init__(self, tracer: "Tracer", rec: Record):
        self.tracer = tracer
        self.rec = rec
        self.fixed = False

    def __enter__(self):
        import jax
        tr, rec = self.tracer, self.rec
        if tr._stack:
            top = tr._stack[-1].rec
            rec.parent = top.id
            if rec.cid is None:
                rec.cid, rec.turn = top.cid, top.turn
        self.ann = jax.profiler.TraceAnnotation(rec.name)
        self.ann.__enter__()
        tr._stack.append(self)
        rec.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t = time.perf_counter()
        tr, rec = self.tracer, self.rec
        tr._stack.pop()
        self.ann.__exit__(*exc)
        if not self.fixed:
            rec.t1 = t
        tr.records.append(rec)
        return False


class _Resume:
    """Stack frame of a request: spans opened inside take its open turn
    span as their parent (none when the turn is not open) and its
    (cid, turn)."""
    __slots__ = ("tracer", "rec")

    def __init__(self, tracer: "Tracer", rec: Record):
        self.tracer = tracer
        self.rec = rec

    def __enter__(self):
        self.tracer._stack.append(self)
        return self

    def __exit__(self, *exc):
        self.tracer._stack.pop()
        return False


class Tracer:
    """Spans and events of one runtime, kept in `records`; see the module
    docstring."""

    def __init__(self, enabled: bool = False, capacity: int = RING):
        self.enabled = False
        self.records: Deque[Record] = collections.deque(maxlen=capacity)
        self._stack: List[Any] = []          # open _Span / _Resume frames
        self._open: Dict[Tuple[str, Any, Any], Record] = {}  # request spans
        self._ids = itertools.count(1)
        if enabled:
            self.enable()

    def enable(self) -> "Tracer":
        if self is NULL_TRACER:
            raise RuntimeError("NULL_TRACER is shared and stays off; give the "
                               "runtime a Tracer of its own")
        self.enabled = True
        _listen(self)
        return self

    # ----- lexical spans ------------------------------------------------------
    def span(self, name: str, cid=None, turn=None, **attrs):
        """A context around one call; see the module docstring."""
        if not self.enabled:
            return NO_SPAN
        return _Span(self, Record(next(self._ids), None, name, None, None,
                                  cid, turn, attrs))

    def timed(self, t0: float, dt: float, **attrs):
        """Give the innermost open span the caller's own readings of the
        call (start, duration), and attributes."""
        if self.enabled and self._stack and isinstance(self._stack[-1],
                                                       _Span):
            sp = self._stack[-1]
            sp.rec.t0, sp.rec.t1, sp.fixed = t0, t0 + dt, True
            sp.rec.attrs.update(attrs)

    def note(self, **attrs):
        """Add attributes to the innermost open span."""
        if self.enabled and self._stack and isinstance(self._stack[-1],
                                                       _Span):
            self._stack[-1].rec.attrs.update(attrs)

    def resume(self, cid, turn):
        """A context in which new lexical spans belong to request
        (cid, turn), as children of its open turn span."""
        if not self.enabled:
            return NO_SPAN
        rec = self._open.get((TURN, cid, turn))
        if rec is None:
            rec = Record(None, None, TURN, None, None, cid, turn, {})
        return _Resume(self, rec)

    # ----- request spans ------------------------------------------------------
    def begin(self, name: str, cid, turn, **attrs):
        """Open the request span (name, cid, turn) unless it is open
        already. A span other than the turn is the open turn's child."""
        if not self.enabled:
            return
        key = (name, cid, turn)
        if key in self._open:
            return
        parent = None if name == TURN else self._open.get((TURN, cid, turn))
        self._open[key] = Record(next(self._ids),
                                 None if parent is None else parent.id,
                                 name, time.perf_counter(), None, cid, turn,
                                 attrs)

    def end(self, name: str, cid, turn):
        """Close the request span (name, cid, turn); no-op when not open."""
        if not self.enabled:
            return
        rec = self._open.pop((name, cid, turn), None)
        if rec is not None:
            rec.t1 = time.perf_counter()
            self.records.append(rec)

    # ----- events -------------------------------------------------------------
    def event(self, name: str, **attrs):
        """A timestamped point; `span` names the innermost open span."""
        if not self.enabled:
            return
        top = self._stack[-1].rec if self._stack else None
        t = time.perf_counter()
        attrs.setdefault("span", None if top is None else top.name)
        self.records.append(Record(
            next(self._ids), None if top is None else top.id, name, t, t,
            None if top is None else top.cid,
            None if top is None else top.turn, attrs))


NULL_TRACER = Tracer()


def traced(name: str):
    """Method decorator: the call runs inside `self.tracer.span(name)`."""
    def wrap(method):
        @functools.wraps(method)
        def call(self, *args, **kwargs):
            with self.tracer.span(name):
                return method(self, *args, **kwargs)
        return call
    return wrap


# ----- compile attribution ----------------------------------------------------
_LIVE: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False


def _on_duration(event: str, duration: float, **kwargs):
    from jax._src import dispatch
    if event == dispatch.BACKEND_COMPILE_EVENT:
        for tr in list(_LIVE):
            tr.event(COMPILE, program=kwargs.get("fun_name"),
                     seconds=duration)


def _listen(tracer: Tracer):
    global _listening
    _LIVE.add(tracer)
    if not _listening:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True

