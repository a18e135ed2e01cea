"""Spans and counters inside the program, kept in memory.

``span(name)`` times a stretch of host code and ``count(name, value)`` adds
to a counter of the innermost open span::

    from repro import tracing

    with tracing.enable():
        rep = simulate(net, xs, profile)
    spans, dropped = tracing.drain()

Recording is on while :func:`enable` is in effect, or while a profiler
trace runs (``jax.profiler.start_trace``).  Off, ``span`` returns one
shared object that does nothing, and ``count`` returns at once.  On, a
span also opens a ``jax.profiler.TraceAnnotation`` of its name, so it lands
in the profiler's trace on the device trace's clock, and on exit appends a
:class:`Span` to a buffer that :func:`drain` empties.

A span opened with no span open on its thread is a root: it starts a new
request id, which every span under it shares.  A listener on JAX's compile
events adds each backend compile to the innermost open span, as
``compiles`` and ``compile_s``.  Spans go in host code only, never in a
function that ``jax.jit``, ``vmap`` or ``scan`` traces: there they would
time the tracing, once.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
import types
from typing import NamedTuple

import jax

#: spans the buffer holds between drains; later ones are counted as dropped.
#: A traced 45 s benchmark window closes about 26,000.
MAX_SPANS = 1 << 17

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    """One closed span.  Times are ``time.perf_counter()`` seconds."""

    name: str
    start: float
    end: float
    parent: int        # ``index`` of the enclosing span; -1 for a root
    request: int       # shared by a root and every span under it
    counts: dict       # counter name -> total added while innermost
    index: int         # order of entry, unique in the process


class Drained(NamedTuple):
    spans: list        # of Span, in order of exit
    dropped: int       # spans closed while the buffer was full


_local = threading.local()
_lock = threading.Lock()
_buffer: list = []
_dropped = 0
_enabled = 0
_indices = itertools.count()
_requests = itertools.count()
_profiling = jax.profiler.TraceAnnotation.is_enabled


class _Off:
    """The span returned while recording is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()
_NO_COUNTS = types.MappingProxyType({})   # the counts of a span never counted


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Open:
    """A span being recorded."""

    __slots__ = ("name", "annotation", "start", "parent", "request",
                 "counts", "index")

    def __init__(self, name: str):
        self.name = name
        self.counts = None          # a dict from the first count()

    def __enter__(self):
        stack = _stack()
        if stack:
            self.parent, self.request = stack[-1].index, stack[-1].request
        else:
            self.parent, self.request = -1, next(_requests)
        self.index = next(_indices)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _dropped
        end = time.perf_counter()
        _stack().pop()
        self.annotation.__exit__(*exc)
        rec = Span(self.name, self.start, end, self.parent, self.request,
                   self.counts or _NO_COUNTS, self.index)
        with _lock:
            if len(_buffer) < MAX_SPANS:
                _buffer.append(rec)
            else:
                _dropped += 1
        return False


def span(name: str):
    """A context manager that records ``name`` while recording is on."""
    if _enabled or _profiling():
        return _Open(name)
    return _OFF


def count(name: str, value) -> None:
    """Add ``value`` to counter ``name`` of the innermost open span; no-op
    where no span is open (as whenever recording is off)."""
    stack = getattr(_local, "stack", None)
    if stack:
        top = stack[-1]
        if top.counts is None:
            top.counts = {}
        top.counts[name] = top.counts.get(name, 0) + value


@contextlib.contextmanager
def enable():
    """Record spans inside the ``with`` block, with or without a profiler
    trace.  Nests."""
    global _enabled
    with _lock:
        _enabled += 1
    try:
        yield
    finally:
        with _lock:
            _enabled -= 1


def drain() -> Drained:
    """The spans recorded since the last drain, and how many were dropped;
    empties the buffer."""
    global _buffer, _dropped
    with _lock:
        out = Drained(_buffer, _dropped)
        _buffer, _dropped = [], 0
    return out


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        count("compiles", 1)
        count("compile_s", duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
