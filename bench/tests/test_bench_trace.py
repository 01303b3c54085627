"""The reduction from a profiler trace to busy time, idle gaps, kernel
time and the per-layer metrics that read them, on a small trace whose
numbers are counted by hand, and ``load`` on a trace recorded here."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchlib import metrics, spec  # noqa: E402
from benchlib import trace as tr  # noqa: E402

# device ops (ns): busy [0,10) [12,25) [40,45) of the window [0,50)
OPS = [("fusion.1", 0, 10), ("flat_mix.3", 12, 20), ("fusion.2", 15, 25),
       ("copy.7", 40, 45)]
SPANS = [("bench.call", 0, 30), ("host:faults.models.compile_plan", 24, 28),
         ("bench.call", 35, 50)]


def test_busy_gaps_and_union():
    assert tr.union([(5, 8), (0, 3), (2, 4), (8, 9)]) == [(0, 4), (5, 9)]
    assert tr.busy(OPS, 0, 50) == 10 + 13 + 5
    assert tr.gaps(OPS, 0, 50) == [(10, 12), (25, 40), (45, 50)]
    assert tr.busy(OPS, 16, 42) == 9 + 2      # clipped to the window


def test_kernel_time_and_top_ops():
    assert tr.kernel_time(OPS, "flat_mix", 0, 50) == 8
    assert tr.kernel_time(OPS, "flat_mix", 14, 50) == 6
    assert tr.kernel_time(OPS, "sparse_mix", 0, 50) == 0
    assert tr.top_ops(OPS, 0, 50) == [["fusion", 20e-9], ["flat_mix", 8e-9],
                                      ["copy", 5e-9]]
    # a loop's event spans its body's operations: each counts its own time
    nested = [("while.3", 0, 20), ("fusion.1", 2, 8), ("flat_mix.2", 10, 15)]
    assert tr.top_ops(nested, 0, 20) == [["while", 9e-9], ["fusion", 6e-9],
                                         ["flat_mix", 5e-9]]
    assert tr.kernel_time(nested, "flat_mix", 0, 20) == 5


def test_op_name_from_hlo_text():
    text = ('%flat_mix.6 = f32[4,23936]{1,0:T(4,128)S(1)} custom-call('
            'f32[1,1]{1,0:T(1,128)} %bitcast.265), '
            'custom_call_target="tpu_custom_call"')
    assert tr.op_name(text) == "flat_mix.6"
    assert tr.op_name("fusion.12") == "fusion.12"


def test_idle_named_by_innermost_span():
    got = dict(tr.idle_by_span(OPS, SPANS, 0, 50))
    # (10,12) call; (25,28) compile_plan; (28,30) call; (30,35) no span;
    # (35,40) and (45,50) call
    assert got == pytest.approx({"bench.call": 14e-9, "outside spans": 5e-9,
                                 "host:faults.models.compile_plan": 3e-9})


def _context(trace):
    p = spec.resolve(spec.benchmark(), "paper.study60")
    return metrics.Context(
        config=p["config"], family=p["family"], traffic=p["traffic"],
        cell=p["cell"],
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        calls=[(0.0, 0.03), (0.035, 0.05)], setup_s=1.0, compiles=0,
        trace=trace)


def test_metrics_read_from_trace():
    ctx = _context(tr.Trace(device={"/device:TPU:0": OPS}, spans=SPANS))
    read = {name: spec.module("metrics", name).read(ctx)
            for name in ("device_ms_per_round", "device_idle_share",
                         "host_gap_ms_per_round")}
    rounds = 2 * 60
    assert ctx.busy_window() == (28e-9, 50e-9)
    assert read["device_ms_per_round"] == pytest.approx(28e-6 / rounds)
    assert read["device_idle_share"] == pytest.approx(100 * 22 / 50)
    # idle inside the two call spans: (30 - 23) + (15 - 5)
    assert read["host_gap_ms_per_round"] == pytest.approx(17e-6 / rounds)
    assert ctx.breakdown()["device_ops"][0] == ["fusion", 20e-9]


def test_roofline_silent_without_its_kernel():
    ctx = _context(tr.Trace(device={"/device:TPU:0": OPS}, spans=SPANS))
    assert spec.module("metrics", "sparse_mix_roofline").read(ctx) is None
    assert spec.module("metrics", "flat_mix_roofline").read(ctx) > 0
    untraced = _context(None)
    assert spec.module("metrics", "flat_mix_roofline").read(untraced) is None


def test_load_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.call"):
        with jax.profiler.TraceAnnotation("host:stub.build"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = tr.load(str(tmp_path))
    names = [s[0] for s in got.spans]
    assert names == ["bench.call", "host:stub.build"]
    (_, cs, ce), (_, hs, he) = got.spans
    assert cs <= hs < he <= ce
    assert tr.span_at(got.spans, (hs + he) // 2) == "host:stub.build"


def test_window_traces_calls_past_its_middle():
    import time

    import jax
    import jax.numpy as jnp
    from benchlib import harness
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()

    class Entry:
        def call(self):
            time.sleep(0.01)
            f(x).block_until_ready()

    tracer = harness.Tracer(3, ["no_such.module:fn"])
    t0 = time.perf_counter()
    calls = harness.window(Entry(), 0.3, tracer)
    lasted = time.perf_counter() - t0
    trace = tracer.load()
    spans = [(s, e) for name, s, e in trace.spans if name == "bench.call"]
    assert tracer.traced == 3 and len(spans) == 3
    # the traced calls start past the middle, and the window goes on to
    # its end around them
    past = [s for s, _ in calls if s - t0 >= 0.15]
    assert len(past) >= 3
    assert lasted >= 0.3
