"""Cells shrunk to sizes a CPU test run holds: the same configuration
keys, entry kinds, traffic and reference, with the model and data cut by
the family's ``tiny`` (a small MLP, few items), two local steps, few
rounds per call and (for the city) 32 vehicles.

``CELLS`` are the cells of ``BENCHMARK.json`` and the two whose files are
ready but which no chip run has proven yet (``paper.sweep32``,
``city.static``); those borrow the limits of the benchmark cell on the
same configuration. ``FAMILIES`` are the configurations' own family,
``mlp``, and ``tiny_tokens``, which lives with the tests: a cell run in
it keeps its fleet, training and traffic, and swaps the model, the data
and the reference for that family's."""
import copy
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchlib import harness, spec  # noqa: E402

import tiny_tokens  # noqa: E402

PREPARED = {"paper.sweep32": ("paper-mlp-k4-dense", "sweep32",
                              "paper.study60"),
            "city.static": ("city-mlp-k1024-sparse", "static",
                            "city.mobile_faulty")}
CELLS = tuple(w["name"] for w in spec.benchmark()["workloads"]) + tuple(
    PREPARED)
FAMILIES = ("mlp", "tiny_tokens")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# every cell in its own family, under the cell's name; the benchmark's
# cells in the token family too
CASES = [pytest.param(c, "mlp", id=c) for c in CELLS] + [
    pytest.param(w["name"], "tiny_tokens", id=f"{w['name']}-tiny_tokens")
    for w in spec.benchmark()["workloads"]]


def _prepared(cell: str) -> dict:
    config, traffic, like = PREPARED[cell]
    bench = spec.benchmark()
    p = dict(spec.resolve(bench, like))
    p["cell"] = {"name": cell, "config": config, "traffic": traffic,
                 "chips": 1}
    p["config"], p["traffic"] = spec.config(config), spec.traffic(traffic)
    p["entry"] = spec.module("entries", p["traffic"]["entry"])
    p["family"] = spec.module("families", p["config"]["family"])
    p["reference"] = spec.module("reference", p["config"]["reference"])
    return p


def parts(cell: str, family: str = "mlp") -> dict:
    bench = spec.benchmark()
    p = (_prepared(cell) if cell in PREPARED
         else spec.resolve(bench, cell))
    cfg, tr = copy.deepcopy(p["config"]), copy.deepcopy(p["traffic"])
    if family == "tiny_tokens":
        cfg = tiny_tokens.configure(cfg)
        p["family"], p["reference"] = tiny_tokens, tiny_tokens.REFERENCE
    cfg = p["family"].tiny(cfg)
    cfg["fleet"]["local_steps"] = 2
    if cfg["fleet"]["num_nodes"] > 4:
        cfg["fleet"].update(num_nodes=32, degree=4)
    tr["rounds_per_call"] = 3
    if tr.get("variants"):
        tr["variants"]["seeds"] = 2
    p["config"], p["traffic"] = cfg, tr
    return p


def run(p: dict, seed: int = 2 ** 40 + 12345) -> dict:
    """A whole run past the device check: set-up, a short window, the
    reference and the comparison."""
    return harness.execute(p, seed, 0.2, False, time.perf_counter(), CPU)
