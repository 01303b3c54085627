"""Every cell of ``BENCHMARK.json`` finds its configuration, model
family, traffic, limits, entry kind, reference and metric readers by
name, the file keeps the benchmark contract's shape, and nothing of a
model is named outside its family and its reference."""
import json
import pathlib
import re
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import spec  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
FAMILY_API = ("fleet", "model", "batch", "items", "eval_fn", "leaves",
              "flops_per_sample", "reference_inputs", "tiny")


def test_top_level_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert 2 + 14 * cells <= 338     # the check's run count fits 24 cells
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_units_and_bounds():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in BENCH[group]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_cell_reports_what_the_contract_asks():
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        e2e = [m["name"] for m in spec.metrics(BENCH, w["name"], False)]
        per_layer = spec.metrics(BENCH, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert per_layer
        for m in per_layer:
            assert m["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    parts = spec.resolve(BENCH, cell)
    assert callable(parts["entry"].Entry)
    assert callable(parts["reference"].run)
    for name in FAMILY_API:
        assert callable(getattr(parts["family"], name)), name
    cfg = parts["config"]
    leaves = parts["family"].leaves(cfg)
    assert tuple(n for n, _ in leaves) == parts["reference"].LEAVES
    assert sum(n for _, n in leaves) == cfg["params"]
    assert set(parts["limits"]) == {"loss", "moment", "change"}
    for name, mod in parts["metrics"].items():
        assert callable(mod.read), name
        if name.endswith("_roofline"):
            kernel = name.removesuffix("_roofline")
            assert callable(spec.module("roofline", kernel).cost)
    tr = parts["traffic"]
    assert tr["warmup_calls"] >= 1 and tr["trace_calls"] >= 1


def test_config_files_are_the_configs():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert (spec.BENCH / "reference" / f"{cfg['reference']}.py").exists()
        assert (spec.BENCH / "families" / f"{cfg['family']}.py").exists()


def test_harness_names_no_model():
    """The harness, the entries and the readers reach a model only
    through its family, so a configuration of another family is added by
    files alone."""
    model_names = re.compile(r"input_dim|num_classes|MLPConfig|make_mlp_loss"
                             r"|mlp_forward|leaf_sizes")
    for kind in ("benchlib", "entries", "metrics"):
        for path in sorted((spec.BENCH / kind).glob("*.py")):
            found = model_names.findall(path.read_text())
            assert not found, (path.name, found)
