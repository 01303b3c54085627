"""Benchmark harness: one function per paper table + micro benches.
Prints ``name,us_per_call,derived`` CSV rows (harness contract) and a
readable paper-tables report. ``--json PATH`` additionally writes the
micro rows as machine-readable JSON (the perf trajectory future PRs are
judged against — see BENCH_consensus.json).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-vgg]
      [--micro-only] [--json BENCH_consensus.json]
"""
from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds (CI mode)")
    ap.add_argument("--skip-vgg", action="store_true")
    ap.add_argument("--micro-only", action="store_true",
                    help="skip the paper tables (perf rows only)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write micro rows as JSON "
                         "[{name, us_per_call, repeats, derived}, ...]")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import micro, paper_tables

    json_rows = []
    print("name,us_per_call,derived")
    quick_kw = {"quick": True} if args.quick else {}
    for fn, kw in ((micro.bench_sketch, {}),
                   (micro.bench_consensus_mix, {}),
                   (micro.bench_flatten, quick_kw),
                   (micro.bench_flat_consensus, quick_kw),
                   (micro.bench_transports, quick_kw),
                   (micro.bench_scan_consensus_rounds, quick_kw),
                   (micro.bench_sparse_mix, quick_kw),
                   (micro.bench_rwkv_formulations, {}),
                   (micro.bench_consensus_round, {}),
                   (micro.bench_scan_rounds, quick_kw),
                   (micro.bench_scan_rounds_xf, quick_kw),
                   (micro.bench_sweep, quick_kw),
                   (micro.bench_mobility, quick_kw),
                   (micro.bench_faults, quick_kw),
                   (micro.bench_ingest, quick_kw),
                   (micro.bench_hierarchy, quick_kw)):
        for row in fn(**kw):
            json_rows.append(row)
            print(f"{row['name']},{row['us_per_call']:.1f},"
                  f"{row['derived']}")
            sys.stdout.flush()

    if args.json:
        with open(args.json, "w") as f:
            json.dump([{"name": r["name"],
                        "us_per_call": round(float(r["us_per_call"]), 1),
                        "repeats": int(getattr(r["us_per_call"], "reps", 1)),
                        "derived": r["derived"]} for r in json_rows],
                      f, indent=1)
        print(f"# wrote {len(json_rows)} rows to {args.json}")

    if args.micro_only:
        return

    # --- CND accuracy (mechanism behind paper eq. 6-7) ---------------------
    print("\n# CND cardinality estimation (vs ground truth)")
    for row in paper_tables.cnd_accuracy_table():
        print(row)

    # --- paper tables 1-4 ---------------------------------------------------
    max_rounds = 15 if args.quick else 60
    print("\n# Paper Tables 1-4 (MLP on redundant synthetic-MNIST):"
          " rounds to 80% acc per base station")
    rows, curves = paper_tables.tables_1_to_4("mlp", max_rounds=max_rounds)
    for row in rows:
        print(row)
    print("\n# convergence curves (round, loss, acc) per algorithm [MLP]")
    for alg, curve in curves.items():
        pts = ";".join(f"{r}:{l:.3f}:{a:.3f}" for r, l, a in curve[::3])
        print(f"curve_mlp,{alg},{pts}")

    print("\n# Mobility scenario sweep (MLP): accuracy / rounds-to-80% "
          "vs topology churn (static ring baseline first)")
    for row in paper_tables.mobility_sweep("mlp", max_rounds=max_rounds):
        print(row)

    print("\n# Hierarchical consensus sweep (MLP): flat dense vs "
          "two-tier cluster consensus at growing fleet sizes "
          "(per-tier step sizes at cap 2.0)")
    hier_kw = (dict(max_rounds=6, fleet=(16, 64)) if args.quick
               else dict(max_rounds=20, fleet=(16, 64, 256)))
    for row in paper_tables.hierarchy_sweep(**hier_kw):
        print(row)

    if not args.skip_vgg:
        vgg_rounds = 10 if args.quick else 40
        print("\n# Paper Tables 1-4 (VGG on redundant synthetic-BIRD)")
        rows, curves = paper_tables.tables_1_to_4("vgg",
                                                  max_rounds=vgg_rounds)
        for row in rows:
            print(row)
        for alg, curve in curves.items():
            pts = ";".join(f"{r}:{l:.3f}:{a:.3f}" for r, l, a in curve[::3])
            print(f"curve_vgg,{alg},{pts}")

    # --- roofline table (reads the dry-run sweep output if present) --------
    import os
    for path in ("dryrun_singlepod.json", "dryrun_multipod.json"):
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
            print(f"\n# roofline terms from {path} "
                  f"({len(data['records'])} records)")
            print("arch,shape,t_compute_s,t_memory_s,t_collective_s,"
                  "bottleneck,useful_ratio")
            for r in data["records"]:
                print(f"{r['arch']},{r['shape']},{r['t_compute_s']:.3e},"
                      f"{r['t_memory_s']:.3e},{r['t_collective_s']:.3e},"
                      f"{r['bottleneck']},{r['useful_flops_ratio']:.2f}")


if __name__ == "__main__":
    main()
