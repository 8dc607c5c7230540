"""Spans and first-call counters of the serving program.

A :class:`Tracer` times the serving engine's layers where the work
happens. Every span takes a ``time.perf_counter`` pair whatever the
tracer's state, and the tracer keeps a running total per span name:
``RoundStats.t_*`` are per-round sums of spans. An enabled tracer also

* keeps a :class:`SpanRecord` of each span (name, times, span id, parent
  id, round, gather group, attributes), handed out by :meth:`drain`;
* enters ``jax.profiler.TraceAnnotation("td:<name>")``, so a profiler
  trace shows the span on its host plane, on the device trace's clock.

A span never waits for the device: device time comes from the trace.

:class:`JitCache` is the one place that makes the engine's jitted
programs. It names each program (the trace reads ``jit_<name>``), counts
the keys the process had not built (new programs) and runs the first
call of each inside a ``jit:<name>`` span: trace, lower, compile or load
from the persistent cache, and dispatch. Built programs live in one
table per process, so a fresh engine runs what an earlier one built
without tracing it again. The table holds at most ``MAX_PROGRAMS`` and
drops the least recently used first, so a conversation whose prompts
keep growing into new buckets does not keep every executable loaded;
:func:`clear_programs` empties it.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

SPAN_PREFIX = "td:"


@dataclass
class SpanRecord:
    name: str
    t0: float                     # time.perf_counter seconds
    t1: float
    id: int
    parent: Optional[int]         # id of the enclosing span, if any
    round: Optional[int]          # serving round (inherited from the parent)
    gid: Optional[str]            # gather group (inherited from the parent)
    attrs: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class Span:
    """One timed region; ``dt`` holds its seconds once it has closed."""

    __slots__ = ("tracer", "name", "round", "gid", "attrs", "t0", "dt",
                 "_rec", "_ann")

    def __init__(self, tracer: "Tracer", name: str, round_, gid, attrs):
        self.tracer = tracer
        self.name = name
        self.round = round_
        self.gid = gid
        self.attrs = attrs
        self.dt = 0.0

    def __enter__(self) -> "Span":
        if self.tracer.enabled:
            self.tracer._open(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.dt = t1 - self.t0
        tr = self.tracer
        tr.totals[self.name] = tr.totals.get(self.name, 0.0) + self.dt
        if tr.enabled:
            tr._close(self, t1)


class Tracer:
    """Span facility of the serving engine (see the module docstring).

    ``Tracer()`` records; ``Tracer(enabled=False)`` only times, which is
    what ``ServingEngine(tracer=None)`` uses."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        #: seconds per span name since the tracer was made
        self.totals: Dict[str, float] = {}
        self._records: List[SpanRecord] = []
        self._stack: List[SpanRecord] = []
        self._next_id = 0

    def span(self, name: str, *, round: Optional[int] = None,
             gid: Optional[str] = None, **attrs) -> Span:
        """Context manager timing ``name``. ``round`` and ``gid`` default
        to the enclosing span's."""
        return Span(self, name, round, gid, attrs)

    def total(self, name: str) -> float:
        return self.totals.get(name, 0.0)

    def drain(self) -> List[SpanRecord]:
        """The records of the spans closed since the last drain, in the
        order they opened."""
        out = sorted(self._records, key=lambda r: r.id)
        self._records = []
        return out

    def _open(self, span: Span) -> None:
        parent = self._stack[-1] if self._stack else None
        rec = SpanRecord(
            span.name, 0.0, 0.0, self._next_id,
            parent.id if parent else None,
            span.round if span.round is not None
            else (parent.round if parent else None),
            span.gid if span.gid is not None
            else (parent.gid if parent else None),
            span.attrs)
        self._next_id += 1
        self._stack.append(rec)
        span._rec = rec
        span._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + span.name)
        span._ann.__enter__()

    def _close(self, span: Span, t1: float) -> None:
        span._ann.__exit__(None, None, None)
        rec = span._rec
        rec.t0, rec.t1 = span.t0, t1
        # spans close in the order they opened, innermost first
        assert self._stack and self._stack[-1] is rec, rec.name
        self._stack.pop()
        self._records.append(rec)


#: the programs JitCaches built in this process, by (name, key), least
#: recently used first. The key holds the shapes and every static the
#: builder closes over, the model function it traces included; a builder
#: closes over no engine, collector or device array (weights are
#: arguments), so the table keeps nothing of an engine alive.
_PROGRAMS: "OrderedDict[tuple, Callable]" = OrderedDict()

#: how many programs the table keeps: several sessions' working sets (a
#: session builds one recovery and one decode program per bucket)
MAX_PROGRAMS = 64


def clear_programs() -> None:
    """Forget every program built in this process."""
    _PROGRAMS.clear()


class JitCache:
    """One engine's view of the process's jitted programs.

    ``get_jit(name, key, make)`` returns the program for ``(name,
    key)``. A program the table does not hold is built from ``make()``,
    the function to jit, renamed ``name``; that counts one new program
    under ``name`` (:meth:`take_new_programs` hands the counts out) and
    its first call runs inside a ``jit:<name>`` span. A program some
    engine of the process built already is neither counted nor timed."""

    def __init__(self, tracer: Optional[Tracer] = None):
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        #: programs this cache built whose first call has not run yet
        self._first: Dict[tuple, _FirstCall] = {}
        self._new: Dict[str, int] = {}

    def get_jit(self, name: str, key: tuple,
                make: Callable[[], Callable]) -> Callable:
        k = (name, key)
        first = self._first.get(k)
        if first is not None and not first.called:
            return first
        prog = _PROGRAMS.get(k)
        if prog is not None:
            _PROGRAMS.move_to_end(k)
            return prog
        fn = make()
        fn.__name__ = fn.__qualname__ = name
        _PROGRAMS[k] = prog = jax.jit(fn)
        while len(_PROGRAMS) > MAX_PROGRAMS:
            _PROGRAMS.popitem(last=False)
        self._new[name] = self._new.get(name, 0) + 1
        self._first = {k_: f for k_, f in self._first.items()
                       if not f.called}
        self._first[k] = first = _FirstCall(self.tracer, name, prog)
        return first

    def take_new_programs(self) -> Dict[str, int]:
        """New programs per name since the last call."""
        new, self._new = self._new, {}
        return new


class _FirstCall:
    """A jitted program whose first call is timed as ``jit:<name>``."""

    __slots__ = ("tracer", "name", "fn", "called")

    def __init__(self, tracer: Tracer, name: str, fn: Callable):
        self.tracer, self.name, self.fn = tracer, name, fn
        self.called = False

    def __call__(self, *args):
        if self.called:
            return self.fn(*args)
        self.called = True
        with self.tracer.span("jit:" + self.name):
            return self.fn(*args)
