"""Finding a cell's pieces by name.

``BENCHMARK.json`` names every cell, configuration and metric; each piece
lives in a file of its own under ``bench/``, found by that name:

- ``configs/<config>.json``: the configuration's sizes and guarantees,
  with the names of its model ``family`` and plain ``reference``;
- ``families/<family>.py``: everything of a cell that depends on the
  model (data, loss and init, evaluation, leaves, FLOPs of a sample);
- ``workloads/<traffic>.json``: the traffic mix (entry kind, rounds per
  call, callbacks, mobility, faults, variants, warm-up and traced calls);
- ``limits/<cell>.json``: the limits of the numbers ``correct`` compares;
- ``entries/<kind>.py``: how a traffic's entry kind is built and called;
- ``metrics/<metric>.py``: one reader per metric;
- ``roofline/<kernel>.py``: a kernel's operations and bytes from shapes;
- ``reference/<name>.py``: the plain reference a configuration names.

Adding a cell, a configuration, a model family, an entry kind or a
metric is adding files and ``BENCHMARK.json`` entries; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "workloads" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell_name}.json")


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported under a name of its own."""
    path = BENCH / kind / f"{name}.py"
    key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics(bench: dict, cell_name: str, traced: bool) -> list:
    """The metrics a run of ``cell_name`` reports: the end-to-end ones
    without a trace, the per-layer ones with it, each only where its
    ``workloads`` list (if any) names the cell."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def resolve(bench: dict, cell_name: str) -> dict:
    """Every file a cell needs, loaded: raises if one is missing."""
    w = cell(bench, cell_name)
    cfg = config(w["config"])
    tr = traffic(w["traffic"])
    return {
        "cell": w, "config": cfg, "traffic": tr,
        "limits": limits(cell_name),
        "entry": module("entries", tr["entry"]),
        "family": module("families", cfg["family"]),
        "reference": module("reference", cfg["reference"]),
        "metrics": {m["name"]: module("metrics", m["name"])
                    for traced in (False, True)
                    for m in metrics(bench, cell_name, traced)},
    }
