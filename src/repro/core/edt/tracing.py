"""Spans and counters of the solve path, recorded only when asked.

A span marks where one step of a request's work happens::

    with tracing.span("dispatch"):
        out = sweep(indeg, u)

While recording is on, it is a ``jax.profiler.TraceAnnotation`` named
``edt:<name>`` (so a profiler trace shows it on the device trace's clock)
and a :class:`Span` kept in memory: its name, host-clock start and end,
the enclosing span and the request it belongs to.  A counter adds up a
count where the work is done (``tracing.count("sweep.tasks", n)``).

Recording is on inside :func:`recording`, for the thread that entered it;
:func:`request` groups the spans and counters of one request and hands
them back when it closes.  Nothing is written to disk.  While recording is
off, :func:`span` returns one shared no-op context and :func:`count`
returns at once, after one module-level check each, and this module does
not import ``jax``: the scan path, and the process-pool workers that
import it, stay jax-free.

The names the program records (``docs/device_exec.md``, "Tracing"):

* spans: ``scan`` (a graph-cache miss), ``scan.count``, ``scan.tiles`` and
  ``scan.edges`` (the sharded scan's three pool rounds), ``level``,
  ``pack``, and in a fused sweep ``build``, ``upload``, ``dispatch``,
  ``wait`` and ``readback``;
* counters: ``sweep.tasks``, ``sweep.task_lanes``, ``sweep.edges``,
  ``sweep.edge_lanes`` and ``sweep.builds``.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Iterator, Optional

#: Prefix of every span's name in a profiler trace.
PREFIX = "edt:"


@dataclass
class Span:
    """One recorded span; ``parent`` indexes the enclosing span in the
    same :class:`Records` (``-1`` for none), ``end`` is ``None`` while it
    is open."""

    name: str
    start: float
    end: Optional[float]
    parent: int
    request: Optional[int]

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Records:
    """The spans and counters of one request (``request`` is ``None`` for
    those recorded outside any request)."""

    request: Optional[int]
    spans: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    _open: list = field(default_factory=list, repr=False)


class _Recorder:
    def __init__(self):
        from jax.profiler import TraceAnnotation

        self.annotation = TraceAnnotation
        self.thread = threading.get_ident()
        self.sinks = [Records(None)]

    def span(self, name: str):
        if threading.get_ident() != self.thread:
            return _NOOP
        return self._span(name)

    @contextmanager
    def _span(self, name: str):
        rec = self.sinks[-1]
        s = Span(name, time.perf_counter(), None,
                 rec._open[-1] if rec._open else -1, rec.request)
        rec._open.append(len(rec.spans))
        rec.spans.append(s)
        try:
            with self.annotation(PREFIX + name):
                yield s
        finally:
            s.end = time.perf_counter()
            rec._open.pop()

    def count(self, name: str, n: int) -> None:
        if threading.get_ident() == self.thread:
            c = self.sinks[-1].counters
            c[name] = c.get(name, 0) + int(n)


_NOOP = nullcontext()
_recorder: Optional[_Recorder] = None


def span(name: str):
    """A context that records one span while recording is on."""
    if _recorder is None:
        return _NOOP
    return _recorder.span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to a counter while recording is on."""
    if _recorder is None:
        return
    _recorder.count(name, n)


@contextmanager
def recording() -> Iterator[Records]:
    """Turn recording on for the calling thread; yields the records of
    spans and counters made outside any :func:`request`."""
    global _recorder
    outer, _recorder = _recorder, _Recorder()
    try:
        yield _recorder.sinks[0]
    finally:
        _recorder = outer


@contextmanager
def request(index: int) -> Iterator[Records]:
    """Group the spans and counters recorded inside under request
    ``index``; the yielded :class:`Records` is complete when the context
    closes (and stays empty while recording is off)."""
    rec = Records(index)
    r = _recorder
    if r is None or threading.get_ident() != r.thread:
        yield rec
        return
    r.sinks.append(rec)
    try:
        yield rec
    finally:
        r.sinks.pop()
