"""The decoder language-model family and its expert kernels' metric, on
the CPU: the family builds the share that ``repro.configs`` states, counts
a sequence's FLOPs as its docstring says, a fault that drops half of each
sequence's tokens from the loss reads not correct (a batch is one
sequence, so the half-batch fault of ``test_bench_faults.py`` leaves the
loss empty), and ``expert_gmm_roofline`` is its arithmetic by hand and
silent where the program counts no routes."""
import copy
import dataclasses
import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench_tiny  # noqa: E402
from benchlib import counters, metrics, spec  # noqa: E402
from benchlib import trace as tr  # noqa: E402

CELL = "mellum2.pair_code2k"
FAMILY = spec.module("families", "decoder_lm")


def test_family_builds_the_registered_share():
    from repro.configs import mellum2_12b_a2_5b as mellum
    cfg = spec.config("mellum2-ep8-k2-ring")
    assert FAMILY.model_config(cfg) == dataclasses.replace(
        mellum.chip_share(8, 0), matmul_precision="highest")
    assert sum(n for _, n in FAMILY.leaves(cfg)) == cfg["params"]


def test_flops_per_sample_by_hand():
    cfg = FAMILY.tiny(copy.deepcopy(spec.config("mellum2-ep8-k2-ring")))
    d, hd, f, s = 64, 16, 32, 32
    per_token = 32 * d                          # head over 32 held rows
    for window in (8, 8, 8, s):                 # 3 sliding, 1 full layer
        per_token += d * (2 * 2 + 2 * 1) * hd   # 2 q/o heads, 1 k/v head
        per_token += d * 8                      # router over 8 experts
        per_token += 2 * 4 / 8 * 3 * d * f      # top-2 x 4 of 8 held
        span = sum(min(q + 1, window) for q in range(s)) / s
        per_token += 2 * 2 * hd * span
    assert FAMILY.flops_per_sample(cfg) == round(6 * per_token * s)


def _half_sequence(monkeypatch, p):
    make = p["family"].model

    def half(config):
        loss, init = make(config)
        return (lambda params, b: loss(params, jax.tree.map(
            lambda a: a[:, : a.shape[1] // 2], b))), init
    monkeypatch.setattr(p["family"], "model", half)


def test_half_of_each_sequence_left_out_is_not_correct(monkeypatch):
    p = bench_tiny.parts(CELL)
    _half_sequence(monkeypatch, p)
    result = bench_tiny.run(p)
    assert not result["correct"], result["check"]
    assert all(isinstance(n["value"], float)
               for n in result["check"].values())


def _ctx(trace, calls):
    p = spec.resolve(spec.benchmark(), CELL)
    return metrics.Context(
        config=p["config"], family=p["family"], traffic=p["traffic"],
        cell=p["cell"],
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        calls=calls, setup_s=1.0, compiles=0, trace=trace)


def test_expert_gmm_roofline_by_hand():
    reader = spec.module("metrics", "expert_gmm_roofline")
    trace = tr.Trace(device={"/device:TPU:0": [
        ("expert_gmm.3", 0, 3_000_000), ("expert_tgmm.1", 3_000_000,
                                          4_000_000),
        ("fusion", 4_000_000, 9_000_000)]},
        spans=[("bench.call", 0, 10_000_000)])
    ctx = _ctx(trace, [(100.0, 101.0), (101.0, 102.0)])
    assert reader.read(ctx) is None             # the program counted none
    scalars = counters.listen()
    scalars.events.append((101.5, "/cdfl/moe/routes_held", 8000.0))
    try:
        got = reader.read(ctx)
    finally:
        scalars.events.pop()
    # 4 rounds in the window, 2 traced (one traced call of 2 rounds):
    # 4000 held routes; 2 rounds x 2 stations x 10 steps x 4 layers
    routes, calls = 4000, 2 * 2 * 10 * 4
    d, f = 2304, 896
    flops = 18 * d * f * routes
    nbytes = 36 * (d + f) * routes + 36 * 8 * d * f * calls
    least = max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * least / 4e-3)
