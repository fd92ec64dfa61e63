"""Host-clock spans of the serving path, on the profiler's clock too.

A span keeps its name, its start and end from ``time.perf_counter_ns()``
(the clock a benchmark window is timed with), the id of the span it was
opened inside, and host-side attributes: tenant, rid, step, token and byte
counts. A span timed with ``span`` is also entered as a
``jax.profiler.TraceAnnotation`` over its extent, with the same attributes
(``StepTraceAnnotation`` where a ``step`` is given), so under
``jax.profiler`` it lands in the trace on the device's clock with its
attributes as stats. An ``instant``, and a span that crosses engine steps
(a request's wait, opened with ``begin`` and recorded once, at ``end``,
with its start), enter the trace as a zero-length annotation at the moment
they are recorded; a crossing span's carries ``dur_ns``, how long before
that moment it started.

Recording is always on. Spans go to ``RECORDER``, a ring of fixed capacity
that counts what it drops; ``Recorder.window`` refuses a window from which
spans were dropped.

Attributes are host values only: an attribute that read a device array
would add a sync to the path it times.
"""
from __future__ import annotations

import itertools
from collections import deque
from time import perf_counter_ns
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

CAPACITY = 1 << 17


def _mark(name: str, attrs: Dict[str, Any]) -> None:
    """A zero-length annotation: a moment in the profiler trace."""
    with TraceAnnotation(name, **attrs):
        pass


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    sid: int
    parent: int                 # sid of the enclosing span, 0 at the top
    attrs: Dict[str, Any]


class _Open:
    """A span being timed (the ``with`` target of ``Recorder.span``)."""
    __slots__ = ("rec", "name", "attrs", "ann", "sid", "parent", "start")

    def __init__(self, rec: "Recorder", name: str, step: Optional[int],
                 attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs
        if step is None:
            self.ann = TraceAnnotation(name, **attrs)
        else:
            self.ann = StepTraceAnnotation(name, step_num=step, **attrs)
            attrs["step"] = step

    def __enter__(self) -> "_Open":
        stack = self.rec._stack
        self.sid = next(self.rec._ids)
        self.parent = stack[-1] if stack else 0
        stack.append(self.sid)
        self.ann.__enter__()
        self.start = perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only once the work has run."""
        self.attrs.update(attrs)
        self.ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        end = perf_counter_ns()
        self.ann.__exit__(*exc)
        self.rec._stack.pop()
        self.rec._keep(Span(self.name, self.start, end, self.sid,
                            self.parent, self.attrs))


class Recorder:
    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.spans: deque[Span] = deque(maxlen=capacity)
        self.dropped = 0
        self.dropped_until_ns = -1      # newest end among dropped spans
        self._stack: List[int] = []
        self._ids = itertools.count(1)
        self._open: Dict[Tuple[str, Any], Tuple[int, Dict[str, Any]]] = {}

    def _keep(self, span: Span) -> None:
        if len(self.spans) == self.capacity:
            # spans arrive in order of their end: the oldest end falls off
            self.dropped += 1
            self.dropped_until_ns = self.spans[0].end_ns
        self.spans.append(span)

    def span(self, name: str, step: Optional[int] = None, **attrs) -> _Open:
        """``with rec.span(name, **attrs) as s:`` times the block."""
        return _Open(self, name, step, attrs)

    def instant(self, name: str, **attrs) -> None:
        _mark(name, attrs)
        t = perf_counter_ns()
        self._keep(Span(name, t, t, next(self._ids),
                        self._stack[-1] if self._stack else 0, attrs))

    # ------------------------------------------- spans that cross steps
    def begin(self, name: str, key, **attrs) -> None:
        """Open ``name`` for ``key`` (a rid) now; a second ``begin`` of the
        same pair restarts it."""
        self._open.pop((name, key), None)
        self._open[(name, key)] = (perf_counter_ns(), attrs)
        if len(self._open) > self.capacity:     # never ended: forget
            self._open.pop(next(iter(self._open)))

    def end(self, name: str, key, **attrs) -> None:
        """Record the span ``begin`` opened for ``key``, if one is open."""
        got = self._open.pop((name, key), None)
        if got is None:
            return
        start, a = got
        a.update(attrs, rid=key)
        t = perf_counter_ns()
        _mark(name, dict(a, dur_ns=t - start))
        self._keep(Span(name, start, t, next(self._ids), 0, a))

    def discard(self, name: str, key) -> None:
        self._open.pop((name, key), None)

    def opened(self, name: str) -> Dict[Any, int]:
        """Start of every ``name`` span still open, by key."""
        return {k: s for (n, k), (s, _a) in self._open.items() if n == name}

    # ---------------------------------------------------------- reading
    def window(self, lo_ns: int, hi_ns: int) -> Optional[List[Span]]:
        """Spans that start and end inside ``[lo_ns, hi_ns]``, or None when
        the ring has dropped spans that ended inside it."""
        if self.dropped_until_ns >= lo_ns:
            return None
        return [s for s in self.spans
                if s.start_ns >= lo_ns and s.end_ns <= hi_ns]


RECORDER = Recorder()
span = RECORDER.span
instant = RECORDER.instant
begin = RECORDER.begin
end = RECORDER.end
discard = RECORDER.discard
