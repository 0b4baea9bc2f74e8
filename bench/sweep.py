"""Offer a cell's open-loop mix at several rates in one process, to find
the rate the cell's traffic file fixes.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 20,40,80

The program is set up and warmed once; each rate then gets the mix's ramp
and a window of ``--seconds``, and one JSON line: the rate, requests due,
failed, p50 and p95 latency from due time, units per second, how far the
latency of the window's last third exceeds its first third (a growing
backlog), the requests still unanswered at the close, and the share served
solo where the server coalesces. The next rate starts once every reply is
in. Needs the chips the cell asks for, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402

import run as bench_run  # noqa: E402
from harness import load as load_mod, registry, traffic  # noqa: E402


def one_rate(session, mix: dict, seed: int, seconds: float) -> dict:
    ramp = float(mix["ramp_s"])
    specs = traffic.schedule(mix, seed, ramp + seconds)
    load = load_mod.Load(session.address, int(mix["connections"]),
                         session.payload, session.decode)
    stop = threading.Event()
    t0 = time.perf_counter()
    sender = threading.Thread(target=load.open_loop, args=(specs, t0, stop),
                              daemon=True)
    sender.start()
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    bench_run.sleep_until(w0)
    c0 = session.counters()
    bench_run.sleep_until(w1)
    c1 = session.counters()
    stop.set()
    sender.join()
    due = [r for r in load.snapshot() if w0 <= r.due < w1]
    open_at_close = sum(1 for r in due if not r.done or r.done > w1)
    load.wait(load.snapshot(), w1 + bench_run.DRAIN_S)
    load.close()
    ok = [r for r in due if r.ok]
    lat = np.array([r.done - r.due for r in ok])
    third = max(1, len(ok) // 3)
    out = {"rate_per_s": mix["rate_per_s"], "due": len(due),
           "failed": len(due) - len(ok),
           "p50_ms": float(np.percentile(lat, 50) * 1e3) if len(ok) else None,
           "p95_ms": float(np.percentile(lat, 95) * 1e3) if len(ok) else None,
           "units_per_s": sum(r.units for r in ok if r.done < w1) / seconds,
           "late_growth_ms": float((lat[-third:].mean() - lat[:third].mean())
                                   * 1e3) if len(ok) >= 3 else None,
           "open_at_close": open_at_close}
    if "batched_requests" in c0 and c1["processed"] > c0["processed"]:
        out["solo_share"] = 1.0 - (
            (c1["batched_requests"] - c0["batched_requests"])
            / (c1["processed"] - c0["processed"]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates per second")
    args = ap.parse_args()
    cell = registry.cell(args.workload)
    dev = bench_run.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"sweep: needs {cell.chips} TPU chip(s); JAX found "
              f"{dev['count']} {dev['platform']}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    session = cell.family.Session(cell.cfg, cell.ref, args.seed, cell.mix)
    try:
        session.warm()
        for rate in (float(r) for r in args.rates.split(",")):
            row = one_rate(session, traffic.with_rate(cell.mix, rate),
                           args.seed, args.seconds)
            print(json.dumps({"workload": args.workload, **row,
                              "device": dev}), flush=True)
    finally:
        session.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
