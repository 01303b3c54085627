"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, print memory/cost analysis, extract roofline
terms. No arrays are ever allocated (ShapeDtypeStructs only) — the 512
placeholder host devices exist purely so jax.make_mesh can build the
production topology.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
      --out EXPERIMENTS_dryrun.json
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback

import jax

from repro.configs.base import INPUT_SHAPES, FedConfig, TrainConfig
from repro.configs.registry import ARCHS, get_arch
from repro.core import flatten, topology
from repro.core import transport as transport_lib
from repro.launch import mesh as meshlib
from repro.launch import roofline, sharding, steps

# --- per-arch dry-run policy -------------------------------------------------

# the chip the production meshes model: one 16x16 TPU v5e pod per pod
TARGET_DEVICE_KIND = "TPU v5 lite"
PLACEHOLDER_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"

# federated nodes (paper: 4 base stations). dbrx's optimizer state needs
# dp=8 FSDP shards per node to fit HBM -> 2 nodes on a single pod.
FED_NODES = {"dbrx-132b": 2}
DEFAULT_FED = 4

# long_500k requires sub-quadratic attention. rwkv6 is attention-free;
# mixtral's window is native; every other attention arch runs its
# sliding-window variant (window 4096) for this shape ONLY (DESIGN.md §4).
LONG_WINDOW = 4096


def _policy(arch: str, shape_name: str):
    cfg = get_arch(arch)
    fed = FED_NODES.get(arch, DEFAULT_FED)
    window = None
    if shape_name == "long_500k" and cfg.num_heads > 0 \
            and cfg.sliding_window is None:
        window = LONG_WINDOW
    return cfg, fed, window


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               verbose: bool = True, return_artifacts: bool = False,
               fed_override: int | None = None,
               train_cfg: TrainConfig | None = None,
               unroll: bool = True, transport: str = "dense",
               wire_dtype: str = "f32") -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg, fed_nodes, window = _policy(arch, shape_name)
    if fed_override:
        fed_nodes = fed_override
    train = train_cfg or TrainConfig(remat="full")
    pmesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    fed_layout = None
    if shape.mode == "train":
        fmesh = meshlib.make_fed_mesh(pmesh, fed_nodes)
        fed_cfg = FedConfig(num_nodes=fed_nodes, transport=transport,
                            wire_dtype=wire_dtype)
        state = steps.fed_state_struct(cfg, fed_nodes, train)
        # static pack layout of ONE node's params (leading F stripped):
        # prices the transport's per-link consensus payload below
        fed_layout = flatten.make_layout(state.params)
        # FSDP (ZeRO-3 over dp) only when a replica + optimizer state is
        # too big to replicate within the node's dp group
        use_fsdp = cfg.param_count() * 10 / fmesh.shape["tp"] > 4e9
        shardings = sharding.fed_state_shardings(state, fmesh,
                                                 fsdp=use_fsdp)
        state = jax.tree.map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=sh),
            state, shardings)
        batch = steps.input_specs(cfg, shape, fed_nodes)
        batch = sharding.with_sharding(batch, fmesh, sharding.fed_batch_spec)
        step = steps.make_fed_train_step(cfg, fed_cfg, train,
                                         unroll=unroll)
        with fmesh:
            lowered = jax.jit(step).lower(state, batch)
            compiled = lowered.compile()
        mesh_used = fmesh
    elif shape.mode == "prefill":
        params = steps.serve_params_struct(cfg)
        serve_fsdp = cfg.param_count() * 2 / pmesh.shape["model"] > 8e9
        shardings = sharding.serve_state_shardings(params, pmesh,
                                                   fsdp=serve_fsdp)
        params = jax.tree.map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=sh),
            params, shardings)
        batch = steps.input_specs(cfg, shape)
        batch = sharding.with_sharding(batch, pmesh,
                                       sharding.serve_batch_spec)
        step = steps.make_prefill_step(cfg, window_override=window,
                                       multi_pod=multi_pod, unroll=unroll)
        with pmesh:
            lowered = jax.jit(step).lower(params, batch)
            compiled = lowered.compile()
        mesh_used = pmesh
    else:  # decode
        params = steps.serve_params_struct(cfg)
        serve_fsdp = cfg.param_count() * 2 / pmesh.shape["model"] > 8e9
        shardings = sharding.serve_state_shardings(params, pmesh,
                                                   fsdp=serve_fsdp)
        params = jax.tree.map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=sh),
            params, shardings)
        dstate = steps.decode_state_struct(cfg, shape,
                                           window_override=window)
        dstate = sharding.with_sharding(dstate, pmesh, sharding.cache_spec)
        tokens = steps.input_specs(cfg, shape)["tokens"]
        tokens = sharding.with_sharding({"t": tokens}, pmesh,
                                        sharding.serve_batch_spec)["t"]
        step = steps.make_serve_step(cfg, window_override=window,
                                     multi_pod=multi_pod, unroll=unroll)
        with pmesh:
            lowered = jax.jit(step).lower(params, dstate, tokens)
            compiled = lowered.compile()
        mesh_used = pmesh

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = roofline.parse_collectives(hlo)
    n_dev = mesh_used.devices.size
    mf = roofline.model_flops_per_device(cfg, shape, n_dev, fed_nodes)
    rl = roofline.Roofline(
        flops=float(cost.get("flops", 0.0)),
        hbm_bytes=float(cost.get("bytes accessed", 0.0)),
        wire_bytes=colls.wire_bytes,
        collectives=colls,
        model_flops=mf,
        device_kind=TARGET_DEVICE_KIND,
    )
    consensus_bytes = 0.0
    if fed_layout is not None:
        # collective term reads the SELECTED transport's wire bytes
        # (bf16 / ring variants), not the dense-f32 roll the HLO lowered
        tr_obj = transport_lib.make_transport(fed_cfg)
        adj = topology.adjacency(fed_cfg.topology, fed_nodes)
        rl = rl.with_consensus(tr_obj, fed_layout, adj,
                               devices_per_node=n_dev // fed_nodes)
        consensus_bytes = roofline.transport_consensus_bytes(
            tr_obj, fed_layout, adj)
    rec = {
        "arch": arch, "shape": shape_name,
        "multi_pod": multi_pod, "devices": n_dev,
        "fed_nodes": fed_nodes if shape.mode == "train" else 0,
        "transport": transport if shape.mode == "train" else None,
        "wire_dtype": wire_dtype if shape.mode == "train" else None,
        "consensus_wire_bytes_per_node": consensus_bytes,
        "window_override": window,
        "compile_s": round(compile_s, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
            "total_gb": round((mem.argument_size_in_bytes
                               + mem.temp_size_in_bytes) / 1e9, 3),
        },
        "collective_counts": colls.count_by_op,
        "collective_bytes": colls.bytes_by_op,
        **rl.row(),
    }
    if verbose:
        print(f"== {arch} x {shape_name} "
              f"({'multi-pod 512' if multi_pod else 'single-pod 256'}) ==")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"temps={mem.temp_size_in_bytes/1e9:.2f}GB per device")
        print(f"  cost_analysis: flops/dev={rl.flops/1e9:.1f}G "
              f"bytes/dev={rl.hbm_bytes/1e9:.2f}GB")
        print(f"  collectives: {colls.count_by_op} "
              f"wire={colls.wire_bytes/1e9:.3f}GB")
        print(f"  roofline: compute={rl.t_compute:.3e}s "
              f"memory={rl.t_memory:.3e}s collective={rl.t_collective:.3e}s "
              f"-> {rl.bottleneck}-bound; useful={rl.useful_ratio:.2f} "
              f"(compile {compile_s:.0f}s)")
    if return_artifacts:
        rec["_artifacts"] = {"lowered": lowered, "compiled": compiled,
                             "hlo": hlo}
    return rec


def main() -> None:
    # the placeholder devices must exist before jax initializes its
    # backends, which happens at the first device query below
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), PLACEHOLDER_DEVICES_FLAG)
        if f)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) on the chosen mesh")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--fast", action="store_true",
                    help="layer-scan mode (fast compile; roofline flops "
                         "undercount loop bodies — lowering check only)")
    from repro.registry import transports, wire_codecs
    ap.add_argument("--transport", choices=transports.names(),
                    default="dense",
                    help="consensus transport backend priced into the "
                         "collective roofline term (train shapes)")
    ap.add_argument("--wire-dtype",
                    choices=wire_codecs.names(),
                    default="f32",
                    help="exchanged-buffer wire codec for the "
                         "collective term (bf16 halves consensus bytes)")
    args = ap.parse_args()

    combos = []
    if args.all:
        combos = [(a, s) for a in ARCHS for s in INPUT_SHAPES]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    records, failures = [], []
    for arch, shape in combos:
        try:
            records.append(dryrun_one(arch, shape,
                                      multi_pod=args.multi_pod,
                                      unroll=not args.fast,
                                      transport=args.transport,
                                      wire_dtype=args.wire_dtype))
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            traceback.print_exc()
            failures.append({"arch": arch, "shape": shape,
                             "error": f"{type(e).__name__}: {e}"})
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"records": records, "failures": failures}, f,
                      indent=1)
    print(f"\n{len(records)} ok, {len(failures)} failed")
    if failures:
        for f_ in failures:
            print("  FAIL", f_["arch"], f_["shape"], f_["error"])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
