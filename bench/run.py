"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: the program's server and the load's client threads share it,
so this process alone holds the chip. A run makes the cell's weights and
inputs from ``--seed``, starts the server, warms every shape the cell's
traffic reaches, runs the mix's unmeasured ramp, measures for ``--seconds``,
waits for the requests due in the window, stops the program, checks what
it served against the configuration's plain reference, and prints one JSON
line last: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` the per-layer metrics and a ``breakdown``, and last the
``checks``, each number compared beside its limit. With ``--trace 0`` the
metrics are the cell's end-to-end ones. A machine without a TPU, or with
fewer chips than the cell asks for, exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                         # noqa: E402
import gc                               # noqa: E402
import json                             # noqa: E402
import math                             # noqa: E402
import sys                              # noqa: E402
import tempfile                         # noqa: E402
import threading                        # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path                # noqa: E402
from typing import Optional             # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import jax                              # noqa: E402

from harness import load as load_mod, registry, traffic  # noqa: E402
from harness import stalls, trace as trace_mod  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"         # fixed: the path is in the key
DRAIN_S = 60.0                          # wait past the close for replies
PEAKS = BENCH / "peaks.json"


@dataclass
class Run:
    """What one run measured, as the metric readers see it."""
    cfg: dict
    mix: dict
    seconds: float
    setup_s: float
    window: tuple                       # perf_counter start, end
    records: list                       # requests due in the window
    all_records: list
    counters: dict                      # "window"/"trace" -> (before, after)
    peak: dict
    log: list = field(default_factory=list)   # engine dispatch log
    flat: Optional[dict] = None         # reduced profiler trace
    trace_span: Optional[tuple] = None  # perf_counter start, end (= window)


class Compiles:
    """Programs compiled and programs loaded from the persistent cache,
    counted as they happen (JAX reports a load as a compile request too)."""

    def __init__(self):
        self.requests = self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def snap(self) -> tuple:
        """(compiled, loaded) so far."""
        return self.requests - self.loads, self.loads


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_for(kind: str) -> dict:
    table = registry.read_json(PEAKS)["devices"]
    if kind not in table:
        raise SystemExit(f"no peak rates for device kind {kind!r} in "
                         f"{PEAKS.name}")
    return table[kind]


def memory_peak() -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def prune_cache(path: Path) -> int:
    """Remove the persistent-cache entries that have no access-time file
    beside them, as a process killed while writing one leaves it. With
    eviction on, JAX reads that file for every entry before each write, so
    one such entry makes every later write fail and every later run cold.
    Returns the number removed."""
    if jax.config.jax_compilation_cache_max_size == -1 or not path.is_dir():
        return 0
    gone = 0
    for entry in path.glob("*-cache"):
        if not entry.with_name(entry.name[:-len("cache")] + "atime").exists():
            entry.unlink()
            gone += 1
    return gone


def sleep_until(t: float) -> None:
    while (left := t - time.perf_counter()) > 0:
        time.sleep(min(left, 0.2))


def execute(cell, seed: int, seconds: float, trace: bool,
            save_trace: Optional[str] = None, stall_dump: float = 0.0) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    dev = device_info()
    peak = peak_for(dev["kind"]) if trace else {}
    compiles = Compiles()
    watch = stalls.Watch(dump_s=stall_dump)
    mix = cell.mix
    t_build = time.perf_counter()
    session = cell.family.Session(cell.cfg, cell.ref, seed, mix,
                                  observe=trace)
    try:
        k_build, t_warm = compiles.snap(), time.perf_counter()
        shapes = session.warm()
        k_warm, t_gc = compiles.snap(), time.perf_counter()
        # One full collection, and the set-up's objects kept out of every
        # later one, so that no run's window pays for scanning them.
        gc.collect()
        gc.freeze()
        print(f"[bench] set-up: {t_build - T_START:.1f} s to start, "
              f"{t_warm - t_build:.1f} s to build and provision "
              f"({k_build[0]} compiled, {k_build[1]} loaded), "
              f"{t_gc - t_warm:.1f} s to warm {shapes} shapes "
              f"({k_warm[0] - k_build[0]} compiled, "
              f"{k_warm[1] - k_build[1]} loaded); a full collection took "
              f"{1e3 * (time.perf_counter() - t_gc):.0f} ms and froze "
              f"{gc.get_freeze_count()} objects", flush=True)
        ramp = float(mix["ramp_s"])
        specs = traffic.schedule(mix, seed, ramp + seconds + DRAIN_S)
        load = load_mod.Load(session.address, int(mix["connections"]),
                             session.payload, session.decode)
        tdir = None
        if trace:
            # started before the load and stopped after the window closes,
            # so that neither delays a request the window times
            tdir = tempfile.TemporaryDirectory(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # Python calls: too costly
            opts.host_tracer_level = 1          # the harness's own spans
            jax.profiler.start_trace(tdir.name, profiler_options=opts)
        stop = threading.Event()
        t0 = time.perf_counter()
        sender = None
        if mix["loop"] == "open":
            sender = threading.Thread(target=load.open_loop,
                                      args=(specs, t0, stop), daemon=True,
                                      name="bench-send")
            sender.start()
        else:
            load.closed_loop(specs, int(mix["pipeline"]))
        w0 = t0 + ramp
        w1 = w0 + seconds
        sleep_until(w0)
        setup_s = time.perf_counter() - T_START
        c_win = [session.counters()]
        k0 = compiles.snap()
        jax.config.update("jax_log_compiles", True)     # names any that do
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            sleep_until(w1)
        c_win.append(session.counters())
        k1 = compiles.snap()
        jax.config.update("jax_log_compiles", False)
        flat = None
        if trace:
            jax.profiler.stop_trace()
        due = [r for r in load.snapshot() if w0 <= r.due < w1]
        load.wait(due, w1 + DRAIN_S)
        stop.set()
        if sender is not None:
            sender.join()
        load.wait(load.snapshot(), time.perf_counter() + 5.0)
        load.close()
        print(f"[bench] {cell.name}: {shapes} shapes warmed; in the window "
              f"{k1[0] - k0[0]} programs compiled, {k1[1] - k0[1]} "
              f"persistent-cache loads; pauses: {watch.summary(w0, w1)}",
              flush=True)
        errors = sorted({r.error for r in due if r.error})
        if errors:
            print(f"[bench] {sum(1 for r in due if not r.ok)} of {len(due)} "
                  f"requests failed, e.g. {errors[:3]}", file=sys.stderr)
        mem = memory_peak()
        if trace:
            path = trace_mod.latest_xplane(tdir.name)
            flat = trace_mod.flatten(path) if path else None
            tdir.cleanup()
            if flat is not None and save_trace:
                trace_mod.save(flat, save_trace)
        run = Run(cfg=cell.cfg, mix=mix, seconds=seconds, setup_s=setup_s,
                  window=(w0, w1), records=due,
                  all_records=load.snapshot(),
                  counters={"window": tuple(c_win),
                            "trace": tuple(c_win) if trace else None},
                  peak=peak, log=list(getattr(session.engine, "log", []))
                  if hasattr(session, "engine") else [],
                  flat=flat, trace_span=(w0, w1) if trace else None)
    finally:
        watch.stop()
        session.stop()
    checks = judge(session.check(due, seed))
    metrics = {}
    for name, (m, reader) in cell.readers(per_layer=trace).items():
        v = reader.read(run)
        if v is not None and math.isfinite(v):
            metrics[name] = {"value": float(v), "unit": m["unit"]}
    device = {**dev, "memory_peak_bytes": mem}
    out = {"correct": all(ok for _, _, _, ok in checks.values()),
           "attempted": len(due),
           "failed": sum(1 for r in due if not r.ok),
           "metrics": metrics, "device": device}
    if trace and flat is not None:
        busy = trace_mod.busy_ns(flat)
        lo, hi = trace_mod.window(flat) or (0, 0)
        device["busy_s"] = (busy or 0.0) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": trace_mod.top_ops(flat),
                            "idle_gaps": trace_mod.idle_gaps(flat)}
    # a number that could not be read (no reply to compare) prints as null
    out["checks"] = {k: {"value": v if math.isfinite(v) else None,
                         "limit": lim, "rule": f"value {op} limit"}
                     for k, (v, op, lim, _) in checks.items()}
    return out


def judge(checks: dict) -> dict:
    """``{name: (value, op, limit)}`` -> the same with a pass flag."""
    out = {}
    for k, (v, op, lim) in checks.items():
        ok = v <= lim if op == "<=" else v >= lim
        out[k] = (v, op, lim, bool(ok))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="also write the reduced trace here (.json.gz)")
    ap.add_argument("--stall-dump", type=float, default=0.0,
                    help="write every thread's stack to stderr when the "
                         "process pauses longer than this many seconds")
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    dev = device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {dev['count']} {dev['platform']} device(s)",
              file=sys.stderr)
        return 2
    if gone := prune_cache(CACHE_DIR):
        print(f"[bench] removed {gone} persistent-cache entries with no "
              f"access-time file", flush=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = execute(cell, args.seed, args.seconds, bool(args.trace),
                  args.save_trace, args.stall_dump)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} ({c['rule']} {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
