"""Request-stage spans, kept in memory and always on.

One process-wide recorder holds a bounded ring of spans. The server marks
every stage of a plain-RCB request with one (DESIGN.md §15): ``aeg.recv``,
``aeg.unpack``, ``aeg.wait``, ``aeg.dispatch`` with its children
``aeg.issue`` and ``aeg.readback``, and ``aeg.reply``. Spans of one request
share its ``req`` id. Times come from ``time.perf_counter_ns()``, so a
caller in the same process can lay them beside its own ``perf_counter``
stamps; nothing here talks to a profiler.

Recording appends one tuple to a ``deque``: no lock beyond the interpreter
lock that the append already holds. The ring keeps the newest
``CAPACITY`` spans, enough for every span of a one-minute run at a few
hundred requests a second; older ones fall off the far end.

Read it with ``spans(since_ns, until_ns)`` or ``stage_summary()``; the
server's TELEMETRY reply carries the latter under ``"stages"``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import numpy as np

#: Spans the process-wide ring holds. Full, it takes 28 MB of spans
#: without stats to 52 MB of spans with three (212 and 396 bytes a span,
#: CPython 3.12; tests/test_tracing.py holds it under 64 MB).
CAPACITY = 1 << 17


class Span(NamedTuple):
    name: str
    start_ns: int              # time.perf_counter_ns()
    end_ns: int
    id: int                    # unique in the process
    parent: int                # id of the enclosing span, 0 for none
    req: int                   # request id, 0 for none
    stats: Optional[dict]      # a few ints or short strings, or None


class Recorder:
    """A bounded ring of spans and the ids they carry."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._local = threading.local()     # per-thread stack of open ids

    def new_id(self) -> int:
        """A fresh id, for a span or a request."""
        return next(self._ids)

    def record(self, name: str, start_ns: int, end_ns: int, req: int = 0,
               parent: int = 0, **stats) -> int:
        """Record a span whose times the caller took; returns its id."""
        sid = next(self._ids)
        self._ring.append(Span(name, start_ns, end_ns, sid, parent, req,
                               stats or None))
        return sid

    def span(self, name: str, req: int = 0, **stats) -> "_Open":
        """``with rec.span(name, req=...) as s:`` records the block's
        duration; a span opened inside it on the same thread takes ``s.id``
        as its parent. ``s.stats`` may be filled in before the block
        ends."""
        return _Open(self, name, req, stats)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def spans(self, since_ns: Optional[int] = None,
              until_ns: Optional[int] = None) -> list:
        """The recorded spans that start in ``[since_ns, until_ns)``, in the
        order they ended."""
        while True:
            try:
                snap = list(self._ring)
                break
            except RuntimeError:            # appended to while copied
                continue
        lo = -1 if since_ns is None else since_ns
        hi = None if until_ns is None else until_ns
        return [s for s in snap
                if s.start_ns >= lo and (hi is None or s.start_ns < hi)]

    def stage_summary(self, since_ns: Optional[int] = None,
                      until_ns: Optional[int] = None) -> dict:
        """``{name: {n, mean, p50, p95, p99}}`` of span durations in
        seconds, over the spans ``spans(since_ns, until_ns)`` returns."""
        by: dict = collections.defaultdict(list)
        for s in self.spans(since_ns, until_ns):
            by[s.name].append(s.end_ns - s.start_ns)
        out = {}
        for name, ns in sorted(by.items()):
            x = np.asarray(ns, np.float64) / 1e9
            p50, p95, p99 = np.percentile(x, [50, 95, 99])
            out[name] = {"n": len(ns), "mean": float(x.mean()),
                         "p50": float(p50), "p95": float(p95),
                         "p99": float(p99)}
        return out


class _Open:
    __slots__ = ("_rec", "_stack", "name", "req", "stats", "id", "parent",
                 "start_ns")

    def __init__(self, rec: Recorder, name: str, req: int, stats: dict):
        self._rec, self.name, self.req, self.stats = rec, name, req, stats

    def __enter__(self) -> "_Open":
        self._stack = stack = self._rec._stack()
        self.parent = stack[-1] if stack else 0
        self.id = next(self._rec._ids)
        stack.append(self.id)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._stack.pop()
        self._rec._ring.append(Span(self.name, self.start_ns, end, self.id,
                                    self.parent, self.req,
                                    self.stats or None))


_RECORDER = Recorder()
new_id = _RECORDER.new_id
record = _RECORDER.record
span = _RECORDER.span
spans = _RECORDER.spans
stage_summary = _RECORDER.stage_summary
