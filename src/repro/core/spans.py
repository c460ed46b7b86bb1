"""Host spans of the serving path, on the profiler's clock.

``span(name, **attrs)`` times a block of host code.  While a profiler
session runs it also enters a ``TraceMe`` of the same name (the class
behind ``jax.profiler.TraceAnnotation``), so that the block shows on the
trace's host plane, on the same clock as the device's ops.
``mark(name, start, end, **attrs)`` records an interval whose ends are
known only afterwards, such as a request's wait in a queue; marks are
kept in memory only.

Records go to a bounded in-memory ring on ``time.perf_counter``, and only
while they can be read: while a profiler session is active, or after
``enable()``.  Otherwise a span costs one check.  Names are static;
request ids and sizes go in ``attrs``.  Each record holds its parent, the
innermost span open on its thread when it began, and the spans of one
request carry its id as ``req``.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

from jaxlib._profiler import TraceMe

#: records kept; the oldest go first
CAPACITY = 1 << 20

now = time.perf_counter


class Record(NamedTuple):
    id: int
    name: str
    start: float
    end: float
    parent: Optional[int]
    attrs: dict


_ring: deque = deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_forced = False


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def enable(on: bool = True) -> None:
    """Keep spans whether or not a profiler session is active."""
    global _forced
    _forced = on


def snapshot() -> list:
    """Every record in the ring, in the order the spans ended."""
    return list(_ring)


def clear() -> None:
    _ring.clear()


class span:
    """Context manager: one host span (see the module's docstring)."""

    __slots__ = ("name", "attrs", "_trace", "_id", "_parent", "_start")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._id = None

    def __enter__(self) -> "span":
        # a TraceMe made with no session active records nothing, even if
        # one starts before it ends: make none then
        traced = TraceMe.is_enabled()
        self._trace = TraceMe(self.name) if traced else None
        if traced:
            self._trace.__enter__()
        if traced or _forced:
            stack = _stack()
            self._parent = stack[-1] if stack else None
            self._id = next(_ids)
            stack.append(self._id)
            self._start = now()
        return self

    def __exit__(self, *exc) -> bool:
        if self._id is not None:
            end = now()
            _stack().pop()
            _ring.append(Record(self._id, self.name, self._start, end,
                                self._parent, self.attrs))
        if self._trace is not None:
            self._trace.__exit__(*exc)
        return False


def mark(name: str, start: float, end: float, **attrs) -> None:
    """Record a span whose ends are known afterwards, under the span open
    now (``start``/``end`` on ``time.perf_counter``)."""
    if _forced or TraceMe.is_enabled():
        stack = _stack()
        _ring.append(Record(next(_ids), name, start, end,
                            stack[-1] if stack else None, attrs))

