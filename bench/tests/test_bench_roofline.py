"""The benchmark's operation and byte counts against hand counts at small
shapes, and the end-to-end arithmetic that turns calls into rates."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench_tiny  # noqa: E402
from benchlib import metrics, spec  # noqa: E402
from benchlib import trace as tr  # noqa: E402


def shape(**kw):
    base = {"nodes": 2, "width": 128, "degree": 1, "wire_bytes": 4,
            "separate_wire": False}
    return dict(base, **kw)


def test_flat_mix_counts():
    cost = spec.module("roofline", "flat_mix").cost
    # (2,2)@(2,128): 2*2*2*128 = 1024; three passes: 3*256 = 768
    # master in, out: 8*256 = 2048 bytes; eta 16 bytes
    assert cost(shape()) == (1792, 2064)
    # a bf16 wire of its own adds 2*256 bytes
    assert cost(shape(wire_bytes=2, separate_wire=True)) == (1792, 2576)


def test_sparse_mix_counts():
    cost = spec.module("roofline", "sparse_mix").cost
    # K=4, P=256, D=2: 2*4*2*256 + 3*4*256 flops; master+out 8*1024,
    # gathered rows 4*2*1024, tables 8*8 bytes
    assert cost(shape(nodes=4, width=256, degree=2)) == (7168, 16448)


def test_padded_width_and_paper_mlp():
    cfg = spec.config("paper-mlp-k4-dense")
    family = spec.module("families", cfg["family"])
    assert sum(n for _, n in family.leaves(cfg)) == cfg["params"] == 23860
    assert family.flops_per_sample(cfg) == 6 * 23860
    assert metrics.padded_width(cfg) == 23936


def _ctx(cell, calls, trace=None):
    p = spec.resolve(spec.benchmark(), cell)
    return metrics.Context(
        config=p["config"], family=p["family"], traffic=p["traffic"],
        cell=p["cell"],
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        calls=calls, setup_s=2.5, compiles=0, trace=trace)


def test_samples_and_call_tail():
    ctx = _ctx("paper.study60", [(0.0, 1.0), (1.0, 3.0)])
    per_round = 4 * 10 * 32
    assert ctx.samples_per_round == per_round
    assert spec.module("metrics", "samples_per_s").read(ctx) == \
        pytest.approx(2 * 60 * per_round / 3.0)
    assert spec.module("metrics", "call_ms_p95").read(ctx) == \
        pytest.approx(1950.0)
    assert spec.module("metrics", "setup_s").read(ctx) == 2.5


def test_mfu_and_roofline_by_hand():
    ops = [("sparse_mix.1", 0, 2_000_000), ("fusion", 2_000_000, 9_000_000)]
    trace = tr.Trace(device={"/device:TPU:0": ops},
                     spans=[("bench.call", 0, 10_000_000)])
    ctx = _ctx("city.mobile_faulty", [(0.0, 0.01)], trace)
    flops = 1024 * 10 * 32 * 10 * 6 * 23860       # ten rounds of samples
    assert spec.module("metrics", "mfu").read(ctx) == \
        pytest.approx(100 * flops / (0.01 * 197e12))
    p = 23936
    least = (8 * 1024 * p + 4 * 1024 * 8 * p + 8 * 1024 * 8) / 819e9
    assert spec.module("metrics", "sparse_mix_roofline").read(ctx) == \
        pytest.approx(100 * least * 10 / 2e-3)


@pytest.mark.parametrize("family", bench_tiny.FAMILIES)
def test_mfu_counts_samples_by_the_family(family):
    """``mfu`` is the samples of the traced calls x the family's FLOPs of
    one sample over the window x the bf16 peak: an image in the MLP, a
    sequence of tokens in the token family."""
    p = bench_tiny.parts("paper.study60", family)
    trace = tr.Trace(device={"/device:TPU:0": [("fusion", 0, 4_000_000)]},
                     spans=[("bench.call", 0, 10_000_000)])
    ctx = metrics.Context(
        config=p["config"], family=p["family"], traffic=p["traffic"],
        cell=p["cell"],
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        calls=[(0.0, 0.01)], setup_s=1.0, compiles=0, trace=trace)
    samples = 4 * 2 * 32 * 3       # nodes x local steps x batch x rounds
    per_sample = {"mlp": 6 * (64 * 8 + 8 + 8 * 10 + 10),
                  "tiny_tokens": 6 * 16 * 32 * 8}[family]
    assert spec.module("metrics", "mfu").read(ctx) == \
        pytest.approx(100 * samples * per_sample / (0.01 * 197e12))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        metrics.peaks("TPU v0 imaginary")
