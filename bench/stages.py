"""Run one cell as ``run.py`` does, then print what the program reports of
its own request stages.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The first JSON line is ``run.py``'s result line. The second holds:

* ``telemetry``: the ``stages`` of the server's TELEMETRY reply for the
  window, asked for just before the server stops;
* ``per_layer`` and ``end_to_end``: every reader of the cell on this run,
  traced or not (per-layer ones that need the trace read nothing
  untraced);
* ``window``: from the span ring, over the requests whose reply ended in
  the window, each stage's mean and p95 in ms, the mean of ingress + wait +
  dispatch + reply against the mean server residence, and the share of
  requests served solo;
* ``longest_waits``: the ten longest ``aeg.wait`` of the run, ramp
  included, each with its header's arrival in seconds from the window's
  start;
* ``recorder_us`` and ``clock_ns``: what recording a request's spans, and
  one read of ``perf_counter_ns``, cost this host (on a ring of its own);
* with ``--trace 1`` also ``fit`` (the clock offset and its residual),
  device time by program name (linked handlers read ``jit_rcb_<op>``), and
  the longest idle gaps of the first device, each labelled by the
  innermost program span around its middle.
"""
from __future__ import annotations

import argparse
import collections
import json
import re
import statistics
import sys
import time

import run as bench_run                 # noqa: E402  (sets the paths)
from harness import program_spans, registry, trace  # noqa: E402


class _Capture:
    """A reader that keeps the run it is handed and reports nothing."""
    run = None

    def read(self, run):
        _Capture.run = run


def _stage_cell(cell: registry.Cell) -> tuple:
    """``cell`` with a session that asks the server for its stages before
    it stops, and a reader that keeps the run."""
    base = cell.family.Session

    class Session(base):
        telemetry: dict = {}

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.marks: list = []

        def counters(self):
            # run.py reads the counters when the window opens and closes
            self.marks.append(time.perf_counter_ns())
            return super().counters()

        def stop(self):
            if self.client is not None and len(self.marks) >= 2:
                Session.telemetry = self.client.telemetry(
                    since_ns=self.marks[0], until_ns=self.marks[1])
            super().stop()

    class Cell(registry.Cell):
        def readers(self, per_layer):
            return {**super().readers(per_layer),
                    "_capture": ({"unit": ""}, _Capture())}

    fam = type("family", (), {"Session": Session})
    return Cell(name=cell.name, chips=cell.chips, cfg=cell.cfg, ref=cell.ref,
                family=fam, mix=cell.mix, metrics=cell.metrics), Session


def recorder_us(n: int = 20000) -> float:
    """Microseconds to record one request's spans as the server does."""
    from repro.core import tracing
    rec = tracing.Recorder()
    t0 = time.perf_counter()
    for i in range(n):
        req = rec.new_id()
        a = time.perf_counter_ns()
        rec.record("aeg.recv", a, a, req=req, rid=i, bytes=2408770)
        with rec.span("aeg.unpack", req=req):
            pass
        rec.record("aeg.wait", a, a, req=req)
        with rec.span("aeg.dispatch", req=req, mode="solo", n=1,
                      reqs=(req,)):
            with rec.span("aeg.issue", thunks=52):
                pass
            with rec.span("aeg.readback"):
                pass
        with rec.span("aeg.reply", req=req):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def clock_ns(n: int = 200000) -> float:
    """Nanoseconds a ``time.perf_counter_ns()`` read takes."""
    t0 = time.perf_counter()
    for _ in range(n):
        time.perf_counter_ns()
    return (time.perf_counter() - t0) / n * 1e9


def window_stages(reqs: list) -> dict:
    def ms(ns):
        return {"n": len(ns), "mean": statistics.fmean(ns) / 1e6,
                "p95": float(sorted(ns)[int(0.95 * (len(ns) - 1))]) / 1e6}
    out = {}
    for name in ("aeg.recv", "aeg.unpack", "aeg.wait", "aeg.reply"):
        ns = [r.dur(name) for r in reqs if r.dur(name) is not None]
        if ns:
            out[name] = ms(ns)
    served = [r for r in reqs if r.dispatch is not None]
    for mode in ("solo", "batched"):
        ns = [r.dispatch.end_ns - r.dispatch.start_ns for r in served
              if r.dispatch.stats["mode"] == mode]
        if ns:
            out[f"aeg.dispatch.{mode}"] = ms(ns)
        for kid in ("aeg.issue", "aeg.readback"):
            ns = [sum(c.end_ns - c.start_ns for c in r.children
                      if c.name == kid) for r in served
                  if r.dispatch.stats["mode"] == mode]
            if ns:
                out[f"{kid}.{mode}"] = ms(ns)
    res = [program_spans.residence_ns(r) for r in served]
    parts = [program_spans.ingress_ns(r) + r.dur("aeg.wait")
             + (r.dispatch.end_ns - r.dispatch.start_ns) + r.dur("aeg.reply")
             for r in served]
    if res:
        out["residence"] = ms(res)
        out["stage_sum_mean_ms"] = statistics.fmean(parts) / 1e6
        out["solo_share"] = 100.0 * sum(
            r.dispatch.stats["mode"] == "solo" for r in served) / len(served)
    return out


def device_view(run, spans: list) -> dict:
    flat = run.flat
    f = program_spans.fit(flat, run.all_records)
    out: dict = {"fit": None if f is None else
                 {"offset_ns": f[0], "mad_ns": f[1]}}
    w = trace.window(flat)
    by: dict = collections.defaultdict(lambda: [0, 0])
    for _, n, s, e in trace.clip(flat["modules"], *w):
        k = by[re.sub(r"\(\d+\)$", "", n)]
        k[0] += 1
        k[1] += e - s
    out["modules"] = [[n, c, ns / 1e9] for n, (c, ns) in
                      sorted(by.items(), key=lambda kv: -kv[1][1])[:20]]
    gaps = sorted(program_spans.idle(flat) or [],
                  key=lambda g: g[0] - g[1])[:10]
    labelled = []
    for a, b in gaps:
        label = "unattributed"
        if f is not None:
            mid = (a + b) / 2 - f[0]
            cover = [s for s in spans if s.start_ns <= mid <= s.end_ns]
            if cover:
                label = min(cover, key=lambda s: s.end_ns - s.start_ns).name
        labelled.append([label, (b - a) / 1e9])
    out["idle_gaps"] = labelled
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    dev = bench_run.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"stages: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    bench_run.prune_cache(bench_run.CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return report(cell, args.seed, args.seconds, bool(args.trace))


def report(cell, seed: int, seconds: float, traced: bool) -> int:
    staged, session = _stage_cell(cell)
    out = bench_run.execute(staged, seed, seconds, traced)
    print(json.dumps(out), flush=True)
    run = _Capture.run
    spans = program_spans.ring() or []
    lo, hi = (int(round(t * 1e9)) for t in run.window)
    reqs = program_spans.group(spans)
    waits = sorted(((r.start_ns - lo) / 1e9, r.dur("aeg.wait") / 1e6)
                   for r in reqs if r.dur("aeg.wait") is not None)
    readers = {kind: {name: reader.read(run) for name, (_, reader)
                      in cell.readers(per_layer=kind == "per_layer").items()}
               for kind in ("per_layer", "end_to_end")}
    extra = {"telemetry": session.telemetry.get("stages"),
             **{kind: {k: v for k, v in got.items() if v is not None}
                for kind, got in readers.items()},
             "window": window_stages(program_spans.in_window(reqs, lo, hi)),
             "longest_waits": sorted(waits, key=lambda w: -w[1])[:10],
             "recorder_us": recorder_us(), "clock_ns": clock_ns()}
    if traced and run.flat is not None:
        extra.update(device_view(run, spans))
    print(json.dumps(extra), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
