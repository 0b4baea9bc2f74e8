import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The three lines above MUST stay the first statements in this module — jax
locks the device count on first init, and the production meshes need 512
host placeholder devices. (Smoke tests and benchmarks import repro normally
and see 1 device; only this entrypoint forces 512.) The dry-run is pinned to
the CPU platform: on a TPU host it would otherwise take the chip and find
too few devices, and the ``--all`` children it spawns inherit the pin, so
none of them contends for the chip either.

Per cell we record: compile wall-time, ``memory_analysis()`` (proves the
per-device footprint), ``cost_analysis()`` (FLOPs / bytes for the roofline),
and the collective-op byte totals parsed from the optimized HLO (the
collective roofline term). Artifacts land in results/dryrun/ as JSON.
"""

import argparse
import json
import pathlib
import re
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[3]
RESULTS = REPO / "results" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?)")
_COLL_RE = re.compile(
    r"=\s*[^=]*?\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _line_result_bytes(line: str) -> int:
    """Total bytes of the result type(s) on an HLO def line."""
    eq = line.find("=")
    rest = line[eq + 1:]
    # result types come before the opcode name; grab leading shape literals
    # (covers tuples): stop at the first identifier that isn't a shape.
    total = 0
    for m in _SHAPE_RE.finditer(rest):
        # only count shapes that appear before the opening paren of operands
        par = rest.find("(")
        # tuples start with '(' immediately — find the opcode paren instead:
        # shapes inside the leading tuple are before the opcode word; simplest
        # robust rule: count shapes up to the first lowercase opcode token
        # followed by '('. We approximate by counting shapes that occur
        # before the first ' %' operand reference.
        first_operand = rest.find("%")
        if first_operand != -1 and m.start() > first_operand:
            break
        total += _shape_bytes(m.group(1), m.group(2))
    return total


def parse_collectives(hlo: str) -> dict:
    """Sum operand bytes of every collective op in optimized HLO text.

    Operands are printed by name only, so we first build a per-computation
    symbol table (name -> result bytes) and then resolve each collective's
    operand list against it. Async pairs (-start/-done) are counted once.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    sym: dict[str, int] = {}
    for line in hlo.splitlines():
        s = line.rstrip()
        d = _DEF_RE.match(s)
        if d:
            sym[d.group(1)] = _line_result_bytes(s)
        m = _COLL_RE.search(s)
        if not m or m.group(2) == "-done":
            continue
        op = m.group(1)
        args = s[m.end():]
        depth = 1
        end = len(args)
        for i, ch in enumerate(args):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        names = _OPERAND_RE.findall(args[:end])
        out[op] += sum(sym.get(n, 0) for n in names)
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def _compile_once(cfg, shape, mesh, rules, unroll: bool):
    """Lower + compile one step; return (compiled, seconds)."""
    import jax

    from repro.distributed.sharding import axis_rules
    from repro.launch import steps as st

    t0 = time.time()
    with axis_rules(mesh, rules):
        fn, args, donate = st.step_for(cfg, shape, unroll=unroll)
        with mesh:
            compiled = jax.jit(fn, donate_argnums=donate) \
                .lower(*args).compile()
    return compiled, time.time() - t0


def _cost_rec(compiled) -> dict:
    cost = compiled.cost_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "collectives": parse_collectives(compiled.as_text()),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             unroll: bool = False, force: bool = False,
             save: bool = True, rules_variant: str = "") -> dict:
    """One dry-run cell.

    Default ("extrapolate") protocol — required because (a) this container
    has one core, so full-unroll compiles of 40L models take many minutes,
    and (b) XLA's cost model counts a while-loop body once regardless of
    trip count, so scan-over-layers FLOPs are L-times under-reported:

      A. full-config *scan-over-layers* compile  -> the shardability +
         memory proof (memory_analysis, collective schedule, compile ok);
      B. 1-layer and 2-layer *unrolled* compiles -> exact per-layer
         FLOPs/bytes/collectives; totals extrapolate as X1 + (L-1)(X2-X1).

    ``unroll=True`` (--mode full) instead compiles the fully unrolled model
    and reports its exact cost analysis; used to validate the extrapolation
    (see EXPERIMENTS.md §Dry-run cross-check).
    """
    import dataclasses

    import jax

    from repro.configs import SHAPES, applicable_shapes, get_config
    from repro.launch.mesh import make_production_mesh

    mesh_tag = "pod512" if multi_pod else "pod256"
    suffix = "__full" if unroll else ""
    if rules_variant:
        suffix += f"__{rules_variant}"
    out_path = RESULTS / mesh_tag / f"{arch}__{shape_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name not in applicable_shapes(cfg):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "skipped": True,
               "reason": "long_500k reserved for sub-quadratic archs"}
        if save:
            out_path.parent.mkdir(parents=True, exist_ok=True)
            out_path.write_text(json.dumps(rec, indent=2))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_variant or {"train": "train", "prefill": "prefill",
                              "decode": "decode"}[shape.kind]
    L = cfg.num_layers

    if unroll:                                   # --mode full (validation)
        compiled, secs = _compile_once(cfg, shape, mesh, rules, unroll=True)
        proof_mem = compiled.memory_analysis()
        c = _cost_rec(compiled)
        totals = {"flops": c["flops"], "bytes": c["bytes"],
                  "coll_bytes": c["collectives"]["total_bytes"],
                  "coll_counts": c["collectives"]["counts"]}
        per_layer = {}
        t_proof = secs
    else:
        # A: full-config proof compile (scan over layers)
        compiled, t_proof = _compile_once(cfg, shape, mesh, rules,
                                          unroll=False)
        proof_mem = compiled.memory_analysis()
        # B: exact per-layer accounting from 1L/2L unrolled compiles
        c1, s1 = _compile_once(dataclasses.replace(cfg, num_layers=1),
                               shape, mesh, rules, unroll=True)
        c2, s2 = _compile_once(dataclasses.replace(cfg, num_layers=2),
                               shape, mesh, rules, unroll=True)
        r1, r2 = _cost_rec(c1), _cost_rec(c2)
        secs = t_proof + s1 + s2

        def extra(k):
            return r1[k] + (L - 1) * (r2[k] - r1[k])

        cb1 = r1["collectives"]["total_bytes"]
        cb2 = r2["collectives"]["total_bytes"]
        coll_by_kind = {
            k: r1["collectives"]["bytes"][k] + (L - 1) *
               (r2["collectives"]["bytes"][k] - r1["collectives"]["bytes"][k])
            for k in r1["collectives"]["bytes"]}
        totals = {"flops": extra("flops"), "bytes": extra("bytes"),
                  "coll_bytes": cb1 + (L - 1) * (cb2 - cb1),
                  "coll_bytes_by_kind": coll_by_kind}
        per_layer = {"flops_1L": r1["flops"], "flops_2L": r2["flops"],
                     "bytes_1L": r1["bytes"], "bytes_2L": r2["bytes"],
                     "coll_1L": cb1, "coll_2L": cb2,
                     "coll_counts_2L": r2["collectives"]["counts"]}

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag,
        "kind": shape.kind,
        "devices": int(mesh.devices.size),
        "mode": "full_unroll" if unroll else "extrapolated",
        "compile_seconds": round(secs, 2),
        "proof_compile_seconds": round(t_proof, 2),
        "flops_per_device": totals["flops"],
        "bytes_per_device": totals["bytes"],
        "collective_bytes_per_device": totals["coll_bytes"],
        "collective_detail": totals.get("coll_bytes_by_kind",
                                        totals.get("coll_counts")),
        "per_layer": per_layer,
        "memory": {
            "argument_bytes": proof_mem.argument_size_in_bytes,
            "output_bytes": proof_mem.output_size_in_bytes,
            "temp_bytes": proof_mem.temp_size_in_bytes,
            "alias_bytes": proof_mem.alias_size_in_bytes,
        },
        "model": {
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "global_batch": shape.global_batch,
            "seq_len": shape.seq_len,
        },
    }
    print(f"[dryrun] {arch} x {shape_name} x {mesh_tag} ({rec['mode']}): "
          f"compile={secs:.1f}s flops/dev={totals['flops']:.3e} "
          f"coll/dev={totals['coll_bytes']/1e6:.1f}MB "
          f"temp={proof_mem.temp_size_in_bytes/1e9:.2f}GB")
    print("  memory_analysis:", proof_mem)
    if save:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=2))
    return rec


def _all_cells():
    from repro.configs import ARCHES, SHAPES
    for arch in ARCHES:
        for shape in SHAPES:
            yield arch, shape


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every cell in crash-isolated subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--mode", choices=("extrapolate", "full"),
                    default="extrapolate")
    ap.add_argument("--rules", default="",
                    help="rule-set variant override (e.g. train_zero1)")
    args = ap.parse_args()

    if args.all:
        fails = []
        meshes = [False, True] if args.both_meshes or not args.multipod \
            else [True]
        for arch, shape in _all_cells():
            for mp in meshes:
                tag = "pod512" if mp else "pod256"
                suffix = "__full" if args.mode == "full" else ""
                out = RESULTS / tag / f"{arch}__{shape}{suffix}.json"
                if out.exists() and not args.force:
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mode", args.mode]
                if mp:
                    cmd.append("--multipod")
                if args.force:
                    cmd.append("--force")
                r = subprocess.run(cmd, cwd=str(REPO),
                                   env={**os.environ,
                                        "PYTHONPATH": str(REPO / "src")})
                if r.returncode != 0:
                    fails.append((arch, shape, tag))
                    print(f"[dryrun] FAILED {arch} x {shape} x {tag}")
        if fails:
            print("FAILURES:", fails)
            return 1
        print("[dryrun] all cells green")
        return 0

    rec = run_cell(args.arch, args.shape, args.multipod,
                   unroll=(args.mode == "full"), force=args.force,
                   rules_variant=args.rules)
    return 0 if rec else 1


if __name__ == "__main__":
    sys.exit(main())
