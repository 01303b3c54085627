"""The program's counters summed over the window: the listener's window
sum by hand, and ``schedule_useful_share`` against the rounds a tiny
city cell's calls started at."""
import pathlib
import sys

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
from benchlib import counters, harness, metrics, spec  # noqa: E402


def test_window_sum_keeps_program_counters_only():
    sc = counters.Scalars()
    sc("/cdfl/faults/rounds_built", 30)
    sc("/jax/other/scalar", 7)
    sc.events += [(1.0, "/cdfl/faults/rounds_built", 40.0),
                  (2.0, "/cdfl/faults/rounds_built", 50.0),
                  (2.5, "/cdfl/faults/rounds_used", 10.0),
                  (3.0, "/cdfl/faults/rounds_built", 60.0)]
    assert [e[1:] for e in sc.events[:1]] == [
        ("/cdfl/faults/rounds_built", 30.0)]
    assert len(sc.events) == 5
    assert sc.total("/cdfl/faults/rounds_built", 1.0, 2.0) == 90.0
    assert sc.total("/cdfl/faults/rounds_built", 1.5, 3.0) == 110.0
    assert sc.total("/cdfl/faults/rounds_used", 1.0, 3.0) == 10.0
    assert sc.total("/cdfl/mobility/rounds_built", 0.0, 9.0) == 0.0


def test_listener_hears_record_scalar():
    sc = counters.listen()
    assert counters.listen() is sc
    n = len(sc.events)
    jax.monitoring.record_scalar("/cdfl/test/probe", 3)
    assert sc.events[n:][0][1:] == ("/cdfl/test/probe", 3.0)


def _context(p, calls):
    return metrics.Context(config=p["config"], family=p["family"],
                           traffic=p["traffic"],
                           cell=p["cell"], device=bench_tiny.CPU,
                           calls=calls, setup_s=0.0, compiles=0)


def test_schedule_useful_share_from_call_start_rounds():
    """Each call starting at round s builds s + R rounds of the fault
    plan and of the mobility trace and uses R of each: the share is
    sum(R) / sum(s + R) over the window's calls."""
    p = bench_tiny.parts("city.mobile_faulty")
    reader = spec.module("metrics", "schedule_useful_share")
    run = harness.set_up(p, 2 ** 40 + 7)
    calls = harness.window(run["entry"], 0.2)
    rounds, warm = p["traffic"]["rounds_per_call"], p["traffic"][
        "warmup_calls"]
    starts = [(warm + i) * rounds for i in range(len(calls))]
    used = 2.0 * sum(rounds for _ in starts)
    built = 2.0 * sum(s + rounds for s in starts)
    assert reader.read(_context(p, calls)) == 100.0 * used / built


def test_schedule_useful_share_silent_without_counters():
    """A window in which no schedule was built (a static fleet, or a
    program that records no counter) gives nothing to read."""
    p = spec.resolve(spec.benchmark(), "paper.study60")
    reader = spec.module("metrics", "schedule_useful_share")
    assert reader.read(_context(p, [(0.0, 0.5), (0.6, 1.0)])) is None
