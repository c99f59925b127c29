"""Spans: where the service's threads spend their time, kept in memory.

``span(name, **attrs)`` marks one piece of work on the calling thread.
Off (the default) it returns the one shared ``NULL`` context, which
records nothing: an instrumented site costs one read of a module global.
``enable()`` turns recording on and ``drain()`` returns and clears what
was recorded, one tuple per span::

    (span_id, parent_id, request_id, name, thread_id, start_ns, end_ns, attrs)

timed with ``time.monotonic_ns()`` (the clock of ``time.monotonic()``).
The parent is the span open on the same thread when this one opened (0
for none).  ``request_span`` opens a span whose id becomes the
``request_id`` of every span under it on that thread (the HTTP
handler's); work done for other requests names their ids in its
``attrs`` (a served miss lists the requests it answered).

A span whose name ends in ``_wait`` is time spent waiting; every other
span is work.  Records are kept up to ``CAP``; past it new spans are
counted by ``dropped()`` and not kept.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List

CAP = 1 << 19

_on = False
_records: collections.deque = collections.deque()
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count(1)
_tls = threading.local()


class _Null:
    """The context every site gets while recording is off."""
    __slots__ = ()
    request_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NULL = _Null()


class _Span:
    __slots__ = ("name", "attrs", "new_request", "id", "parent",
                 "request_id", "start", "_outer_request")

    def __init__(self, name: str, attrs: dict, new_request: bool = False):
        self.name = name
        self.attrs = attrs
        self.new_request = new_request

    def __enter__(self):
        stack = _stack()
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else 0
        self._outer_request = getattr(_tls, "request", None)
        self.request_id = self.id if self.new_request else self._outer_request
        _tls.request = self.request_id
        stack.append(self)
        self.start = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        end = time.monotonic_ns()
        _stack().pop()
        _tls.request = self._outer_request
        _keep((self.id, self.parent, self.request_id, self.name,
               threading.get_ident(), self.start, end, self.attrs or None))
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only once the work has run."""
        self.attrs.update(attrs)


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _keep(rec: tuple) -> None:
    global _dropped
    with _lock:
        if len(_records) < CAP:
            _records.append(rec)
        else:
            _dropped += 1


def span(name: str, **attrs):
    """A context manager that records ``name`` on this thread while
    recording is on, and the shared ``NULL`` otherwise."""
    if not _on:
        return NULL
    return _Span(name, attrs)


def request_span(name: str, **attrs):
    """``span``, whose id is also the request id of the spans under it."""
    if not _on:
        return NULL
    return _Span(name, attrs, new_request=True)


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """Record an interval measured elsewhere (a job's time in a queue)
    as a span under the one open on this thread."""
    if not _on:
        return
    stack = _stack()
    _keep((next(_ids), stack[-1].id if stack else 0,
           getattr(_tls, "request", None), name, threading.get_ident(),
           int(start_ns), int(end_ns), attrs or None))


def annotate(**attrs) -> None:
    """Add attributes to the innermost span open on this thread, from
    code that does not hold it (a GP program naming the bytes it sends
    to the device)."""
    if not _on:
        return
    stack = _stack()
    if stack:
        stack[-1].attrs.update(attrs)


def enable() -> None:
    """Start recording; clears what an earlier session left."""
    global _on, _dropped
    with _lock:
        _records.clear()
        _dropped = 0
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> List[tuple]:
    """The spans recorded since the last drain, in the order they
    ended; clears them."""
    with _lock:
        out = list(_records)
        _records.clear()
    return out


def dropped() -> int:
    """Spans not kept since ``enable()`` because ``CAP`` was reached."""
    return _dropped
