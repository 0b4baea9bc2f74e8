"""Profiler capture and its reduction to device times.

``run.py`` records a JAX profiler trace that covers the whole window;
``flatten`` reads the ``.xplane.pb`` it wrote into plain lists, which is also
the form of the small trace kept under ``bench/data`` for the tests; the
other functions reduce those lists to the numbers the metric readers use.

Device events come from the planes whose name starts with ``/device:``: the
``XLA Ops`` line (one event per operation) and the ``XLA Modules`` line (one
event per executed program). Host events are the harness's own spans, the
``bench.*`` names of ``jax.profiler.TraceAnnotation``. All times are in
nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Callable, Iterable, Optional

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def flatten(path: str) -> dict:
    """Read an ``.xplane.pb`` into ``{"ops": [...], "modules": [...],
    "spans": [...]}``; each device event is ``[device, name, start_ns,
    dur_ns]`` and each span ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: dict = {"ops": [], "modules": [], "spans": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    out[key].append([plane.name, ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out["spans"].append([ev.name, int(ev.start_ns),
                                             int(ev.duration_ns)])
    return out


def latest_xplane(logdir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return found[-1] if found else None


def save(flat: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(flat, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------------------------------------------ reduce
def window(flat: dict) -> Optional[tuple]:
    """(start_ns, end_ns) of the traced window: the harness's window span."""
    spans = [s for s in flat["spans"] if s[0] == WINDOW_SPAN]
    if not spans:
        return None
    s = max(spans, key=lambda s: s[2])
    return s[1], s[1] + s[2]


def merge(intervals: Iterable) -> list:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(events: list, lo: int, hi: int) -> list:
    """(device, name, start, end) of events, cut to [lo, hi]."""
    out = []
    for dev, name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((dev, name, a, b))
    return out


def devices(flat: dict) -> list:
    return sorted({e[0] for e in flat["ops"]} | {e[0] for e in flat["modules"]})


def busy_ns(flat: dict) -> Optional[float]:
    """Mean over devices of the union of device-op intervals in the window."""
    w = window(flat)
    devs = devices(flat)
    if w is None or not devs:
        return None
    ev = clip(flat["ops"] or flat["modules"], *w)
    per = []
    for d in devs:
        per.append(sum(b - a for a, b in merge((s, e) for dv, _, s, e in ev
                                              if dv == d)))
    return sum(per) / len(per)


def module_ns(flat: dict, pattern: str) -> float:
    """Device time, summed over devices, of programs whose name matches."""
    w = window(flat)
    if w is None:
        return 0.0
    rx = re.compile(pattern)
    return float(sum(e - s for _, n, s, e in clip(flat["modules"], *w)
                     if rx.search(n)))


def module_count(flat: dict, pattern: str) -> int:
    """Executions of programs whose name matches that start in the window."""
    w = window(flat)
    if w is None:
        return 0
    rx = re.compile(pattern)
    return sum(1 for _, n, s, _ in flat["modules"]
               if w[0] <= s < w[1] and rx.search(n))


def op_ns(flat: dict, pred: Callable) -> float:
    """Device time of the ops whose name ``pred`` accepts."""
    w = window(flat)
    if w is None:
        return 0.0
    return float(sum(e - s for _, n, s, e in clip(flat["ops"], *w)
                     if pred(n)))


def top_ops(flat: dict, k: int = 10) -> list:
    """The k op names with the most device time, ``[[name, seconds]]``."""
    w = window(flat)
    if w is None:
        return []
    tot: dict = {}
    for _, n, s, e in clip(flat["ops"] or flat["modules"], *w):
        tot[n] = tot.get(n, 0) + (e - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]


def idle_gaps(flat: dict, k: int = 10) -> list:
    """The k longest stretches of the window in which no op ran on the
    first device, each labelled by the harness span that covers its middle
    (``unattributed`` where none does): ``[[label, seconds]]``."""
    w = window(flat)
    devs = devices(flat)
    if w is None or not devs:
        return []
    ev = clip(flat["ops"] or flat["modules"], *w)
    busy = merge((s, e) for d, _, s, e in ev if d == devs[0])
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w[1] > t:
        gaps.append((t, w[1]))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    spans = [s for s in flat["spans"] if s[0] != WINDOW_SPAN]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        label = min(cover, key=lambda s: s[2])[0] if cover else "unattributed"
        out.append([label, (b - a) / 1e9])
    return out
