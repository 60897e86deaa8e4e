"""Program spans and counters, recorded where the work happens.

``span(name, **attrs)`` times a block of host code twice over: it
enters ``jax.profiler.TraceAnnotation``, so the block lands in any
running profile on the clock of the device trace (and names the device's
idle gaps there), and it appends one :class:`Record` to an in-memory
ring, timed by ``time.perf_counter_ns()``.  ``count(name, n, **attrs)``
appends a zero-length record to the same ring, so a counter's
increments can be cut to a window like spans can.  Spans of one request
share a ``rid`` attribute.

Both are always on: with no profiler running a span costs a few
microseconds of host time.  The ring keeps the newest :data:`RING`
records.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from dataclasses import dataclass

import jax

RING = 65_536

_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_local = threading.local()


@dataclass(slots=True)
class Record:
    id: int
    parent_id: int | None        # the span open around this one
    name: str
    start_ns: int
    end_ns: int
    attrs: dict                  # a counter's holds its increment, "n"


def _open() -> list:
    """This thread's stack of open spans."""
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """Record the ``with`` block as a span.  Entering yields its
    :class:`Record`, whose ``end_ns`` is set when the block exits (by
    return or raise)."""

    __slots__ = ("record", "_note", "_stack")

    def __init__(self, name: str, **attrs):
        self.record = Record(next(_ids), None, name, 0, 0, attrs)
        self._note = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> Record:
        rec, self._stack = self.record, _open()
        rec.parent_id = self._stack[-1].id if self._stack else None
        self._stack.append(rec)
        self._note.__enter__()
        rec.start_ns = time.perf_counter_ns()
        return rec

    def __exit__(self, *exc) -> None:
        rec = self.record
        rec.end_ns = time.perf_counter_ns()
        self._note.__exit__(*exc)
        self._stack.pop()
        _ring.append(rec)


def count(name: str, n: int = 1, **attrs) -> None:
    """Record ``n`` increments of the counter ``name`` now."""
    stack = _open()
    t = time.perf_counter_ns()
    _ring.append(Record(next(_ids), stack[-1].id if stack else None, name,
                        t, t, dict(attrs, n=n)))


def records(start_ns: int | None = None,
            end_ns: int | None = None) -> list[Record]:
    """The ring's records, oldest first; with bounds, those that lie
    wholly inside ``[start_ns, end_ns]``."""
    out = list(_ring)
    if start_ns is not None:
        out = [r for r in out if r.start_ns >= start_ns]
    if end_ns is not None:
        out = [r for r in out if r.end_ns <= end_ns]
    return out
