"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/tests/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3

For each seed, one run of the cell as ``run.py`` makes it (a window of
``--seconds`` at the cell's own load), then the check's numbers twice: for
what the program served (the lower reading) and for the control, the
configuration's reference computed one precision step down in the
program's place (the upper reading). One JSON line per seed. All seeds run
in one process, so set-up is paid once for the process.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402

import run as bench_run  # noqa: E402
from harness import registry  # noqa: E402


def with_control(cell) -> None:
    """Make this cell's check also report the control's numbers."""
    class Session(cell.family.Session):
        def check(self, records, seed):
            out = dict(super().check(records, seed))
            out.update({f"control.{k}": v
                        for k, v in self.control(records, seed).items()})
            return out

    cell.family = types.SimpleNamespace(Session=Session)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = registry.cell(args.workload)
    dev = bench_run.device_info()
    if dev["platform"] != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    with_control(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = bench_run.execute(cell, seed, args.seconds, trace=False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "attempted": out["attempted"],
                          "failed": out["failed"], "checks": out["checks"],
                          "metrics": out["metrics"], "device": out["device"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
