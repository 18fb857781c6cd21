"""Spans and marks of the program's own layers, on the profiler's clock.

Recording is on exactly while a JAX profiler session records
(``jax.profiler.TraceAnnotation.is_enabled()``): the profiler is the one
switch, and this module adds none.  While it is on, :func:`span` does two
things:

* it opens a ``jax.profiler.TraceAnnotation`` under the span's bare name,
  so the span sits in the profiler's ``.xplane.pb`` on the same clock as
  the device ops;
* it keeps one :class:`Record` in memory, read back in the same process
  with :func:`records` once the traced window has ended.

While it is off, :func:`span` returns one shared no-op context, so a span
on the hot path costs an ``is_enabled()`` check and an empty ``with``.

Parents are tracked per thread: the service evaluates on
``asyncio.to_thread`` workers, and a span opened there takes the span open
on that thread as its parent (or none).  Spans on one thread must nest, so
none is held across an ``await``.

>>> with span("repro.example"):  # no profiler session: nothing is kept
...     pass
>>> [r for r in records() if r.name == "repro.example"]
[]
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Record", "span", "mark", "records", "dropped", "clear"]

#: records kept at most; spans past it are counted by :func:`dropped`
CAPACITY = 1 << 20


class Record(NamedTuple):
    """One span (or a zero-length mark) while the profiler recorded."""

    name: str
    parent: Optional[int]  # index of the span open on this thread, or None
    start_ns: int  # time.perf_counter_ns()
    end_ns: Optional[int]  # None while the span is open
    thread_id: int


_is_enabled = TraceAnnotation.is_enabled
_lock = threading.Lock()
_records: List[Record] = []
_dropped = 0
_local = threading.local()


#: the context every span returns while the profiler is off
_OFF = contextlib.nullcontext()


def _stack() -> List[Optional[int]]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _keep(name: str, parent: Optional[int], start_ns: int,
          end_ns: Optional[int]) -> Optional[int]:
    """Append one record; its index, or None once :data:`CAPACITY` records
    are kept."""
    global _dropped
    rec = Record(name, parent, start_ns, end_ns, threading.get_ident())
    with _lock:
        if len(_records) >= CAPACITY:
            _dropped += 1
            return None
        _records.append(rec)
        return len(_records) - 1


class _Span:
    __slots__ = ("name", "annotation", "stack", "parent", "start", "index")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.stack = stack = _stack()
        self.parent = stack[-1] if stack else None
        self.start = time.perf_counter_ns()
        self.index = _keep(self.name, self.parent, self.start, None)
        stack.append(self.index)
        return None

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.stack.pop()
        if self.index is not None:
            rec = Record(self.name, self.parent, self.start, end,
                         threading.get_ident())
            with _lock:
                if self.index < len(_records):  # else cleared meanwhile
                    _records[self.index] = rec
        return self.annotation.__exit__(*exc)


def span(name: str):
    """Context manager: one span named ``name`` while the profiler records,
    a shared no-op otherwise."""
    if not _is_enabled():
        return _OFF
    return _Span(name)


def mark(name: str) -> None:
    """A zero-length record under the span open on this thread, while the
    profiler records (``repro.compile.<engine>`` at each retrace)."""
    if _is_enabled():
        stack = _stack()
        now = time.perf_counter_ns()
        _keep(name, stack[-1] if stack else None, now, now)


def records() -> List[Record]:
    """Every record kept since the last :func:`clear`, in opening order (a
    parent before its children)."""
    with _lock:
        return list(_records)


def dropped() -> int:
    """Spans and marks not kept because :data:`CAPACITY` was reached."""
    return _dropped


def clear() -> None:
    """Forget every record and the dropped count; call it with no span
    open."""
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0
