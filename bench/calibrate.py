"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...] [--faults <n>]

For every seed, in one process: the program's set-up as a run makes it
(its first call, on the data of the configuration's model family), then
the plain reference over the same calls and the numbers the check
compares (the lower reading); then the control, the reference computed
in bfloat16 in the program's place (the upper reading); and, for the
first ``--faults`` seeds, the reference with a fault planted in it (half
of each minibatch left out, the consensus exchange left out). The
reference, its control and its faults are the shared rounds of
``reference/cdfl_rounds.py`` with the model the configuration's
``reference`` binds, so any family is calibrated alike. One JSON line
per seed. The benchmark's own runs do not run this; it needs the chip
the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from benchlib import check, harness, spec  # noqa: E402

PLANTS = ("half_batch", "no_exchange")


def readings(parts: dict, seed: int, faults: bool) -> dict:
    import jax.numpy as jnp
    run = harness.set_up(parts, seed)
    del run["entry"]
    gc.collect()
    ref_in = check.reference_inputs(parts, run)
    calls = 1
    ref = parts["reference"].run(parts["config"], parts["traffic"], ref_in,
                                 calls=calls)
    out = {"seed": seed,
           "program": check.numbers(run["readings"], ref),
           "control": check.numbers(parts["reference"].run(
               parts["config"], parts["traffic"], ref_in, calls=calls,
               dtype=jnp.bfloat16), ref)}
    if faults:
        for plant in PLANTS:
            out[plant] = check.numbers(parts["reference"].run(
                parts["config"], parts["traffic"], ref_in, calls=calls,
                plant=plant), ref)
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    parts = spec.resolve(spec.benchmark(), args.workload)
    try:
        harness.device_info(parts["cell"]["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        out = readings(parts, seed, i < args.faults)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
