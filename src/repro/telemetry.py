"""In-program spans and counters of the serving path.

A *request* is one call into the server (``GraphBatchServer.advance`` or
``tick``).  Its root span and every span opened inside it share the
request's id; each span records its name, start, end (``time.perf_counter``
seconds) and the id of the span that was open when it began.  Counters
add up per request.  When the root span closes, the request's
:class:`Record` goes into a bounded ring of the last :data:`RING_REQUESTS`
requests, marked ``ok`` or failed; :func:`recent` reads it.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name, so
a profiler trace shows it on the device trace's clock.  Only the root span
carries the request id, as trace metadata (child names stay the same from
run to run).  The recorder is always on; with the profiler stopped the
annotations cost next to nothing.

Spans and counters outside a request (a direct ``serve_batch`` call) are
written to the trace only, not recorded.  The active request is kept per
thread.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

#: completed requests kept in memory
RING_REQUESTS = 4096

_clock = time.perf_counter
_ring: "collections.deque[Record]" = collections.deque(maxlen=RING_REQUESTS)
_ring_lock = threading.Lock()
_request_ids = itertools.count(1)


class _Thread(threading.local):
    """This thread's open spans (innermost last) and open request."""

    def __init__(self):
        self.stack: List[Span] = []
        self.record: Optional[Record] = None


_local = _Thread()


class Span:
    """One timed interval of a request.  ``id`` is the span's index in its
    record (the root is 0), ``parent`` the id of the span it was opened in
    (None for the root).  ``end`` is None while the span is open."""

    __slots__ = ("name", "id", "parent", "request", "start", "end",
                 "_annotation")

    def __init__(self, name: str, id: int, parent: Optional[int],
                 request: Optional[int], annotation: TraceAnnotation):
        self.name = name
        self.id = id
        self.parent = parent
        self.request = request
        self._annotation = annotation
        self.end: Optional[float] = None
        self.start = _clock()

    @property
    def duration(self) -> float:
        return (self.end if self.end is not None else _clock()) - self.start

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"request={self.request}, duration={self.duration:.6f})")


class Record:
    """The spans and counters of one request."""

    __slots__ = ("request", "spans", "counters", "ok")

    def __init__(self, request: int):
        self.request = request
        self.spans: List[Span] = []
        self.counters: Dict[str, float] = {}
        self.ok = True

    @property
    def root(self) -> Span:
        return self.spans[0]

    @property
    def name(self) -> str:
        return self.spans[0].name

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def durations(self, name: str) -> List[float]:
        return [s.duration for s in self.spans if s.name == name]

    def total(self, name: str) -> float:
        """Seconds spent in spans called ``name`` (summed)."""
        return sum(self.durations(name))

    def children(self, span: Span) -> List[Span]:
        return [s for s in self.spans if s.parent == span.id]

    def __repr__(self) -> str:
        return (f"Record(request={self.request}, ok={self.ok}, "
                f"spans={[s.name for s in self.spans]}, "
                f"counters={self.counters})")


def _annotate(name: str, **metadata) -> Optional[TraceAnnotation]:
    if not TraceAnnotation.is_enabled():    # the profiler is not running
        return None
    annotation = TraceAnnotation(name, **metadata)
    annotation.__enter__()
    return annotation


def begin(name: str) -> Span:
    """Open a span; close it with :func:`end` (or use :class:`span`).  It
    is a child of the innermost open span, and is recorded when a request
    is open; outside one it is written to the profiler's trace only."""
    stack, rec = _local.stack, _local.record
    if rec is None:
        s = Span(name, -1, None, None, _annotate(name))
    else:
        s = Span(name, len(rec.spans), stack[-1].id, rec.request,
                 _annotate(name))
        rec.spans.append(s)
    stack.append(s)
    return s


def end(name_or_span) -> None:
    """Close an open span: the one given, or the innermost open span of
    that name, with every span opened inside it.  A span that is not open
    is left alone, so a phase may be ended early where it finishes and
    again, harmlessly, on every way out."""
    now = _clock()
    stack = _local.stack
    for i in range(len(stack) - 1, -1, -1):
        s = stack[i]
        if s is name_or_span or s.name == name_or_span:
            for inner in stack[i:]:
                inner.end = now
                if inner._annotation is not None:
                    inner._annotation.__exit__(None, None, None)
                    inner._annotation = None
            del stack[i:]
            return


class span(contextlib.ContextDecorator):
    """A span around the enclosed block; as a decorator, around each call
    of the function."""

    def __init__(self, name: str):
        self.name = name
        self._span: Optional[Span] = None

    def _recreate_cm(self):
        return type(self)(self.name)

    def __enter__(self) -> Span:
        self._span = begin(self.name)
        return self._span

    def __exit__(self, *exc) -> bool:
        end(self._span)
        return False


class request(span):
    """The root span of a new request around the enclosed block (or each
    call); inside an open request it is an ordinary child span.  A block
    that raises records the request with ``ok=False``."""

    def __enter__(self) -> Span:
        if _local.record is not None:
            return super().__enter__()
        rec = _local.record = Record(next(_request_ids))
        root = self._span = Span(self.name, 0, None, rec.request,
                                 _annotate(self.name, request=rec.request))
        rec.spans.append(root)
        _local.stack.append(root)
        return root

    def __exit__(self, exc_type, *exc) -> bool:
        root = self._span
        end(root)
        if root.id == 0:
            rec = _local.record
            rec.ok = exc_type is None
            _local.record = None
            with _ring_lock:
                _ring.append(rec)
        return False


def count(name: str, value: float) -> None:
    """Add ``value`` to the current request's counter ``name``."""
    rec = _local.record
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + value


def recent(n: int) -> List[Record]:
    """The last ``n`` completed requests that succeeded, oldest first;
    fewer when fewer are kept."""
    out: List[Record] = []
    if n <= 0:
        return out
    with _ring_lock:
        for rec in reversed(_ring):
            if rec.ok:
                out.append(rec)
                if len(out) == n:
                    break
    out.reverse()
    return out


__all__ = ["RING_REQUESTS", "Record", "Span", "begin", "count", "end",
           "recent", "request", "span"]
