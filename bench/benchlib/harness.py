"""One run of one cell: set-up, a measured window, the correctness check.

A run, in order:

1. finds the cell's configuration, traffic, limits, entry kind, reference
   and metric readers by name (``spec``);
2. refuses to go on without as many TPU chips as the cell asks for;
3. turns on the persistent compilation cache at its fixed path;
4. makes the fleet data on the device from ``--seed`` through the
   configuration's model family and builds the entry
   (``Experiment.compile`` or ``compile_batch``);
5. makes the traffic's fixed number of warm-up calls, the first of
   which the reference follows later; this is set-up;
6. drives the entry back to back for ``--seconds``, counting the
   programs JAX lowers in that window; with ``--trace 1`` the first
   ``trace_calls`` calls that start past the window's middle run under
   the profiler, with host spans around each call and around the
   program's host schedule builders;
7. frees the program's state, runs the reference over the first call
   and compares;
8. prints each compared number beside its limit on standard error, and
   the result as the last line of standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import importlib
import json
import sys
import tempfile
import time

import numpy as np

from benchlib import check, data, spec

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    """The devices JAX found; raises unless they are TPU chips, as many
    as the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Instants at which JAX lowered a program (a new compilation, or a
    read of the persistent cache)."""

    def __init__(self):
        self.times = []

    def __call__(self, event, duration_secs=0.0, **kw):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, lo, hi) -> int:
        return sum(lo <= t <= hi for t in self.times)


@contextlib.contextmanager
def host_spans(names):
    """Wrap each ``module:function`` of the program in a profiler span
    named ``host:<module>.<function>`` while the block runs."""
    import jax
    undo = []
    for target in names:
        mod_name, fn_name = target.split(":")
        try:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, fn_name)
        except (ImportError, AttributeError):
            continue
        label = f"host:{mod_name.removeprefix('repro.')}.{fn_name}"

        def wrapped(*a, _fn=fn, _label=label, **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **kw)
        functools.update_wrapper(wrapped, fn)
        setattr(mod, fn_name, wrapped)
        undo.append((mod, fn_name, fn))
    try:
        yield
    finally:
        for mod, fn_name, fn in undo:
            setattr(mod, fn_name, fn)


def set_up(parts: dict, seed: int) -> dict:
    """Data, entry and the traffic's warm-up calls; the program's
    readings of the first call, which the reference follows. The first
    call compiles the entry's programs; the second compiles what only a
    call that continues a run uses (a continuing ``Session.run`` lowers
    one more small program). The parameters before the first call are
    kept on the host, so that the device holds no second copy of them."""
    import jax
    from benchlib import build
    config, traffic = parts["config"], parts["traffic"]
    family = parts["family"]
    desc = data.describe(config, traffic, seed)
    inputs = family.fleet(config, seed)
    entry = parts["entry"].Entry(family, config, traffic, inputs, desc)
    p0 = jax.tree.leaves(entry.params())
    result = entry.call()
    p1 = jax.tree.leaves(entry.params())
    readings = {
        "loss": [entry.loss(result)],
        "m": np.asarray(build.flat_leaf_norms(
            entry.first_moment(), [n for _, n in family.leaves(config)])),
        "change": build.leaf_norms([b - a for a, b in zip(p0, p1)])}
    del p0, p1, result
    for _ in range(traffic["warmup_calls"] - 1):
        entry.call()
    return {"entry": entry, "inputs": inputs, "desc": desc,
            "readings": readings}


class Tracer:
    """Runs ``n_calls`` calls of a window under the profiler, each in a
    ``bench.call`` span, with host spans around the program's functions
    named in ``spans``."""

    def __init__(self, n_calls: int, spans: list):
        self.n_calls, self.spans = n_calls, spans
        self.traced = 0
        self.trace = None
        self._stack = None

    def start(self):
        import jax
        self._dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(host_spans(self.spans))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir.name, profiler_options=opts)

    def stop(self):
        import jax
        try:
            jax.profiler.stop_trace()
        finally:
            self._stack.close()

    def load(self):
        from benchlib import trace as trace_lib
        try:
            self.trace = trace_lib.load(self._dir.name)
        finally:
            self._dir.cleanup()
        return self.trace


def window(entry, seconds: float, tracer: Tracer | None = None) -> list:
    """``[(start, end)]`` of every call made back to back until
    ``seconds`` have passed since the first began. With a ``tracer``,
    the first of its calls is the first to start past the window's
    middle, and the window runs on until all of them are made."""
    import jax
    calls = []
    w0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        if tracer is not None and tracer.traced < tracer.n_calls:
            if tracer.traced == 0 and t - w0 >= seconds / 2:
                tracer.start()
            if tracer.traced > 0 or t - w0 >= seconds / 2:
                with jax.profiler.TraceAnnotation("bench.call"):
                    t = time.perf_counter()
                    entry.call()
                    calls.append((t, time.perf_counter()))
                tracer.traced += 1
                if tracer.traced == tracer.n_calls:
                    tracer.stop()
                continue
        elif t - w0 >= seconds:
            break
        entry.call()
        calls.append((t, time.perf_counter()))
    return calls


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def execute(parts: dict, seed: int, seconds: float, traced: bool,
            t0: float, device: dict) -> dict:
    """Everything after the device check; returns the result line."""
    from benchlib import metrics as metrics_lib
    import jax
    config, traffic, cell = parts["config"], parts["traffic"], parts["cell"]
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    run = set_up(parts, seed)
    entry = run["entry"]
    setup_s = time.perf_counter() - t0
    tracer = (Tracer(traffic["trace_calls"],
                     spec.load_json(spec.BENCH / "host_spans.json"))
              if traced else None)
    calls = window(entry, seconds, tracer)
    peak = memory_peak()
    ctx = metrics_lib.Context(
        config=config, family=parts["family"], traffic=traffic, cell=cell,
        device=device, calls=calls, setup_s=setup_s,
        compiles=counter.between(calls[0][0], calls[-1][1]),
        trace=tracer and tracer.load())
    del entry, run["entry"]
    gc.collect()
    numbers = check.compare(parts, run)
    out_metrics = {}
    bench = spec.benchmark()
    for m in spec.metrics(bench, cell["name"], traced):
        value = parts["metrics"][m["name"]].read(ctx)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            print(f"bench: {m['name']} found nothing to read in this run",
                  file=sys.stderr)
    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": check.correct(numbers), "attempted": len(calls),
              "failed": 0, "metrics": out_metrics, "device": dev}
    if traced:
        dev["busy_s"], dev["window_s"] = ctx.busy_window()
        result["breakdown"] = ctx.breakdown()
    result["check"] = numbers
    return result


def main(argv, t0: float) -> int:
    args = parse(argv)
    bench = spec.benchmark()
    parts = spec.resolve(bench, args.workload)
    try:
        device = device_info(parts["cell"]["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = execute(parts, args.seed, args.seconds, bool(args.trace), t0,
                     device)
    for name, n in result["check"].items():
        print(f"check {name}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
