"""Find everything a cell needs by the names in ``BENCHMARK.json``:

* configuration ``<c>``: ``bench/configs/<c>.json`` (its sizes, engine
  settings and limits) and ``bench/configs/<c>.py`` beside it (its plain
  reference and weights); the file's ``family`` names the module under
  ``bench/families/`` that serves it;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* metric ``<m>``, end-to-end or per-layer: ``bench/metrics/<m>.py``, whose
  ``read(run)`` returns a number or ``None`` when it finds nothing to read.

A new cell, configuration, mix or metric is new files and new entries;
nothing here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = ROOT / "BENCHMARK.json"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                    # the configuration file, with its name
    ref: object                  # the configuration's reference module
    family: object               # bench/families/<family>.py
    mix: dict
    metrics: dict = field(default_factory=dict)   # name -> BENCHMARK entry

    def readers(self, per_layer: bool) -> dict:
        kind = "per_layer" if per_layer else "end_to_end"
        return {name: (m, load_module(BENCH / "metrics" / f"{name}.py"))
                for name, m in self.metrics.items() if m["_kind"] == kind}


def metrics_for(spec: dict, cell: str) -> dict:
    """The end-to-end and per-layer metrics that ``cell`` reports: those
    whose ``workloads`` list it, or that have no such list."""
    out = {}
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            if cell in m.get("workloads", [cell]):
                out[m["name"]] = {**m, "_kind": kind}
    return out


def build(name: str, config: str, traffic: str, chips: int = 1,
          metrics: dict = None) -> Cell:
    """A cell from the names of its configuration and traffic mix."""
    cfg_path = BENCH / "configs" / f"{config}.json"
    cfg = {**read_json(cfg_path), "name": config}
    return Cell(
        name=name, chips=chips, cfg=cfg,
        ref=load_module(cfg_path.with_suffix(".py")),
        family=importlib.import_module(f"families.{cfg['family']}"),
        mix=read_json(BENCH / "traffic" / f"{traffic}.json"),
        metrics=metrics or {})


def cell(name: str, spec_path: Path = SPEC) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` defines it."""
    spec = read_json(spec_path)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in {spec_path.name}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    if conf["file"] != f"bench/configs/{conf['name']}.json":
        raise SystemExit(f"configuration file {conf['file']} is not "
                         f"named after {conf['name']}")
    return build(name, w["config"], w["traffic"], int(w["chips"]),
                 metrics_for(spec, name))
