"""Find a cell's files by the names in BENCHMARK.json. Adding a cell, a
configuration, a traffic mix or a per-layer metric is adding files and
entries; nothing here names one."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load(spec: str):
    """What a data file names as `package.module:attribute`, or the module
    itself where no attribute is named. Drivers, builders, references, row
    generators, FLOP functions, optimizers and readers are all found so: a
    later PR adds a module and names it in a data file of its own."""
    module, _, attr = spec.partition(":")
    found = importlib.import_module(module)
    return getattr(found, attr) if attr else found


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def held_out(root: str = ROOT) -> dict:
    """The entries taken out of BENCHMARK.json, as they stood there: no cell
    of theirs runs (none has limits), the tests hold them to the contract's
    letters while they wait."""
    return _json(os.path.join(root, manifest(root)["paths"][0],
                              "held_out.json"))


def resolve(name: str, root: str = ROOT) -> dict:
    """The cell `name`: its entry, its configuration (the file's top level
    with `assumed` folded in), its traffic mix, its limits and the metrics it
    reports with and without a trace."""
    bench = manifest(root)
    here = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _json(os.path.join(root, conf["file"]))
    cfg.update(cfg.get("assumed", {}))
    traffic = _json(os.path.join(here, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(here, "limits", name + ".json"))

    def mine(metric):
        return name in metric.get("workloads", [name])

    per_layer = []
    for m in bench["per_layer"]:
        if mine(m):
            spec = _json(os.path.join(here, "metrics", m["name"] + ".json"))
            per_layer.append({**m, **spec})
    return {"name": name, "chips": cell["chips"], "config": cfg,
            "config_name": cell["config"], "traffic": traffic,
            "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": per_layer}
