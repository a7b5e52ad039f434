"""Spans and counters at the program's layer boundaries.

Three functions:

* :func:`span` — a context manager round one layer's work. It opens a
  ``jax.profiler.TraceAnnotation`` of the same name (so a profiler trace
  shows the span on the host beside the device ops it caused), and
  records ``(name, call, parent, start_ns, end_ns, ids, counters)`` in a
  bounded in-memory ring. A span opened while another is open on the same
  thread is its child; one opened with none open is the root of a new
  *call*, and the ring keeps the last :data:`RING_CALLS` finished calls.
* :func:`count` — adds to a counter of the innermost open span.
* :func:`recent` — the spans of the last ``n`` finished calls whose root
  has a given name, for operators and the benchmark's readers.

Spans open once per API call, per serving window or per compile, never
per simulated step. With no profiler running a span costs a few
microseconds, and it never waits for the device: it adds no
``block_until_ready`` or transfer and changes no dispatch order. A span's
self time is its duration less the time its children cover.

Span names are fixed strings (``memsim.<layer>.<phase>``); ``ids`` carry
what varies, such as the serving window's index.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax

#: finished root calls the ring keeps
RING_CALLS = 256

_ring: deque = deque(maxlen=RING_CALLS)   # (root name, [Span, ...])
_ring_lock = threading.Lock()
_call_ids = itertools.count(1)
_local = threading.local()


class Span:
    """One recorded span. ``call`` is the id of the root call it belongs
    to, ``parent`` the index of its parent in that call's span list (None
    for the root), times are ``time.perf_counter_ns()``; ``end_ns`` is None
    while the span is open."""

    __slots__ = ("name", "call", "parent", "start_ns", "end_ns", "ids",
                 "counters", "_spans", "_index", "_annotation")

    def __init__(self, name: str, ids: Dict):
        self.name = name
        self.ids = ids
        self.counters: Dict[str, int] = {}
        self.end_ns: Optional[int] = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __enter__(self) -> "Span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        if stack:
            top = stack[-1]
            self.call, self._spans, self.parent = (top.call, top._spans,
                                                   top._index)
        else:
            self.call, self._spans, self.parent = next(_call_ids), [], None
        self._index = len(self._spans)
        self._spans.append(self)
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.ids)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _local.stack.pop()
        if self.parent is None:
            with _ring_lock:
                _ring.append((self.name, self._spans))


def span(name: str, **ids) -> Span:
    """A context manager that records one span named ``name`` (see the
    module docstring); ``ids`` go to the profiler annotation and the
    record. ``with span(...) as s`` gives the :class:`Span`, whose
    ``seconds`` hold its duration once it has closed."""
    return Span(name, ids)


def count(key: str, n: int = 1) -> None:
    """Add ``n`` to counter ``key`` of the innermost span open on this
    thread; with none open, do nothing."""
    stack = getattr(_local, "stack", None)
    if stack:
        c = stack[-1].counters
        c[key] = c.get(key, 0) + n


def recent(n: int, root: str) -> List[List[Span]]:
    """The last ``n`` finished calls whose root span is named ``root``,
    oldest first; each is its list of spans in the order they opened, the
    root first (a span's ``parent`` indexes this list)."""
    if n <= 0:
        return []
    with _ring_lock:
        calls = [spans for name, spans in _ring if name == root]
    return calls[-n:]
