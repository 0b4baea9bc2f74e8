"""The program's own request-stage spans, read after a run.

The server records every stage of a plain-RCB request in an in-memory ring
(``repro.core.tracing``: ``aeg.recv``, ``aeg.unpack``, ``aeg.wait``,
``aeg.dispatch`` with children ``aeg.issue`` and ``aeg.readback``,
``aeg.reply``), with the profiler on or off. The server runs in the
benchmark's process, so the ring is read here once the run is over.

``of_run`` groups the spans by request and keeps the requests whose
header arrived inside the run's window (start of ``aeg.recv``): the
requests ``resnet_p95_ms`` counts are those due in the window, and a
request is sent when due. Keeping those whose reply ends in the window
instead also keeps a backlog left from the ramp, whose residences the
client's percentile never sees.

``fit`` finds the offset that puts a span onto the profiler's clock, from
the harness's own ``bench.send`` spans matched in order to each record's
``sent`` stamp (one sender thread stamps both), and ``held_idle`` uses it.
A program without the ring, as older commits are, reads as ``None``
throughout.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Optional

from harness import trace

MIN_SENDS = 50                  # matched sends a fit needs
MAX_MAD_NS = 100_000            # residuals' median absolute deviation
SEND_SPAN = "bench.send"


@dataclass
class Request:
    """The spans of one request: its stages by name, and the dispatch
    that served it with that dispatch's children."""
    req: int
    stages: dict = field(default_factory=dict)      # name -> span
    dispatch: Optional[object] = None
    children: list = field(default_factory=list)    # the dispatch's

    def dur(self, name: str) -> Optional[int]:
        s = self.stages.get(name)
        return None if s is None else s.end_ns - s.start_ns

    @property
    def start_ns(self) -> int:
        return self.stages["aeg.recv"].start_ns

    @property
    def end_ns(self) -> int:
        return self.stages["aeg.reply"].end_ns


def ring() -> Optional[list]:
    """Every span the program's ring holds, or None without a ring."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.spans()


def group(spans: list) -> list:
    """The requests that have both an ``aeg.recv`` and an ``aeg.reply``,
    in the order their replies ended."""
    reqs: dict = {}
    kids: dict = {}
    dispatches = []
    for s in spans:
        if s.name == "aeg.dispatch":
            dispatches.append(s)
        elif s.parent:
            kids.setdefault(s.parent, []).append(s)
        if s.req and s.name != "aeg.dispatch":
            r = reqs.setdefault(s.req, Request(s.req))
            r.stages[s.name] = s
    for d in dispatches:             # a failed batch is redone solo: the
        for q in d.stats["reqs"]:    # dispatch that ended last served it
            r = reqs.get(q)
            if r is not None and (r.dispatch is None
                                  or d.end_ns > r.dispatch.end_ns):
                r.dispatch, r.children = d, kids.get(d.id, [])
    done = [r for r in reqs.values()
            if "aeg.recv" in r.stages and "aeg.reply" in r.stages]
    return sorted(done, key=lambda r: r.end_ns)


def in_window(reqs: list, lo_ns: int, hi_ns: int) -> list:
    return [r for r in reqs if lo_ns <= r.start_ns < hi_ns]


def of_run(run) -> Optional[list]:
    """The run's requests whose header arrived inside its window."""
    spans = ring()
    if spans is None:
        return None
    lo, hi = (int(round(t * 1e9)) for t in run.window)
    return in_window(group(spans), lo, hi)


# ------------------------------------------------------------ per request
def ingress_ns(r: Request) -> Optional[int]:
    a, b = r.dur("aeg.recv"), r.dur("aeg.unpack")
    return None if a is None or b is None else a + b


def residence_ns(r: Request) -> int:
    return r.end_ns - r.start_ns


def solo_issue_ns(reqs: list) -> list:
    """Host issue time of each solo dispatch among ``reqs``."""
    return [sum(c.end_ns - c.start_ns for c in r.children
                if c.name == "aeg.issue")
            for r in reqs
            if r.dispatch is not None and r.dispatch.stats["mode"] == "solo"]


# ----------------------------------------------------------- device clock
def fit(flat: dict, records: list) -> Optional[tuple]:
    """``(offset_ns, mad_ns)`` such that a ``perf_counter_ns`` stamp plus
    ``offset_ns`` is the profiler's time: the median, over the sends
    matched in order, of (``bench.send`` start - ``Record.sent``), and the
    median absolute deviation of those differences. None when fewer than
    ``MIN_SENDS`` match or the deviation exceeds ``MAX_MAD_NS``."""
    starts = sorted(s[1] for s in flat["spans"] if s[0] == SEND_SPAN)
    sent = sorted(r.sent for r in records if r.sent)
    n = min(len(starts), len(sent))
    if n < MIN_SENDS:
        return None
    d = [a - int(round(b * 1e9)) for a, b in zip(starts[:n], sent[:n])]
    off = statistics.median(d)
    mad = statistics.median(abs(x - off) for x in d)
    if mad > MAX_MAD_NS:
        return None
    return off, mad


def idle(flat: dict) -> Optional[list]:
    """The stretches of the traced window in which no op ran on the first
    device, as ``[start, end]`` on the profiler's clock."""
    w = trace.window(flat)
    devs = trace.devices(flat)
    if w is None or not devs:
        return None
    ev = trace.clip(flat["ops"] or flat["modules"], *w)
    busy = trace.merge((s, e) for d, _, s, e in ev if d == devs[0])
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append([t, a])
        t = max(t, b)
    if w[1] > t:
        gaps.append([t, w[1]])
    return gaps


def held_idle(flat: dict, records: list, reqs: list) -> Optional[float]:
    """Percent of the first device's idle time in the traced window that
    lies inside at least one request's [start of ``aeg.recv``, end of
    ``aeg.reply``]: the device waits while the host holds a request."""
    gaps = idle(flat)
    f = fit(flat, records)
    if not gaps or f is None:
        return None
    off = f[0]
    held = trace.merge((r.start_ns + off, r.end_ns + off) for r in reqs)
    total = sum(b - a for a, b in gaps)
    inside, j = 0, 0
    for a, b in gaps:                # both lists sorted and disjoint
        while j < len(held) and held[j][1] <= a:
            j += 1
        k = j
        while k < len(held) and held[k][0] < b:
            inside += min(b, held[k][1]) - max(a, held[k][0])
            k += 1
    return 100.0 * inside / total if total else None
