"""Smoke run of the C-DFL round scan on one TPU chip.

    python chip_smoke.py

Drives the system's main path once, in this one process, through the
entry points a user calls (``Experiment`` / ``Session`` /
``BatchedSession`` and the ``repro.launch.train`` CLI), with the paper's
MLP (784-30-10) at its published widths and random weights from a seed:

  (a) paper   K=4 static ring, dense mixing, 10 local steps, batch 32
  (c) city    K=1024 Manhattan mobility, sparse top-D=8 mixing,
              link_drop + crash + straggle faults (wire guard on),
              duplicate_heavy streaming-redundancy ingest
  (d) hier    phase (c)'s fleet under two-tier hierarchical mixing
  (e) sweep   32 variants (16 seeds x {static, platoon}) of phase (a)
              in one vmapped scan (``Experiment.compile_batch``)
  cli         ``repro.launch.train.main`` on its reduced transformer

Every phase runs a few rounds twice and fails unless the per-round loss
is finite and falls, and unless its compiled round program holds the
Pallas kernel the phase should run. Each mixing kernel is also compared
with its XLA form on the same inputs at the phases' shapes. This is not
a benchmark: the figures it prints (compile seconds, host stack-build
seconds, wall ms/round) are smoke figures of one unrepeated run.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, before printing it, when JAX finds no TPU or
when any phase fails.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import (FaultConfig, FedConfig,  # noqa: E402
                                HierarchyConfig, IngestConfig,
                                MobilityConfig, TrainConfig)
from repro.configs.paper_models import MLP_CONFIG, MLPConfig  # noqa: E402
from repro.core import flatten  # noqa: E402
from repro.data import pipeline, synthetic  # noqa: E402
from repro.experiment import Experiment, SweepAxes  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import simple  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Size:
    """How large each phase runs. :data:`FULL` is what the chip runs;
    tests pass a tiny one."""

    mlp: MLPConfig = MLP_CONFIG  # model widths (P = 23,936 at full size)
    paper_nodes: int = 4         # K of phases (a) and (e)
    city_nodes: int = 1024       # K of phases (c) and (d)
    items: int = 320             # training items per vehicle (paper)
    degree: int = 8              # sparse top-D
    local_steps: int = 10        # paper
    rounds: int = 3              # rounds per run; each phase runs twice
    sweep_seeds: int = 16        # (e): seeds x {static, platoon}
    cli_argv: tuple = ("--rounds", "4", "--nodes", "4",
                       "--local-steps", "2")


FULL = Size()

# largest |kernel - XLA| allowed in compare_kernels. Both sides read the
# same f32 (or bf16-then-upcast) values and accumulate in f32; only the
# summation order differs, on unit-scale inputs.
TOLERANCE = 2e-5

# what each phase's compiled round program must contain
REQUIRED_KERNELS = {
    "paper": {"flat_mix"},
    "city_sparse": {"sparse_mix"},
    "city_hier": {"cluster_mix"},
    "sweep": {"flat_mix"},
}


class SmokeFailure(RuntimeError):
    pass


# --------------------------------------------------------------------------
# Shared pieces.
# --------------------------------------------------------------------------

def _mlp_fns(cfg: MLPConfig):
    return (simple.make_mlp_loss(cfg),
            lambda rng: simple.mlp_init(rng, cfg))


def _fleet_data(cfg: MLPConfig, nodes: int, items: int):
    """Per-vehicle synthetic MNIST-like data, made from seeds 0..K-1."""
    dim = int(round(cfg.input_dim ** 0.5))
    sets = [synthetic.synthetic_mnist(seed=i, n=items, image_dim=dim,
                                      num_classes=cfg.num_classes)
            for i in range(nodes)]
    data = {"x": np.stack([d.x for d in sets]),
            "y": np.stack([d.y for d in sets])}
    items_tok = pipeline.FederatedBatcher(sets, cfg.batch_size,
                                          1).node_items()
    return data, items_tok


def _train(size: Size) -> TrainConfig:
    cfg = size.mlp
    return TrainConfig(learning_rate=cfg.learning_rate, beta1=cfg.beta1,
                       beta2=cfg.beta2, eps=cfg.eps,
                       batch_size=cfg.batch_size)


def _check_loss(phase: str, loss) -> np.ndarray:
    """Per-round mean loss; fails unless finite everywhere and falling
    from the first round to the last."""
    loss = np.asarray(loss)
    per_round = loss.reshape(loss.shape[0], -1).mean(axis=1)
    if not np.isfinite(loss).all():
        raise SmokeFailure(f"{phase}: non-finite loss {per_round}")
    if not per_round[-1] < per_round[0]:
        raise SmokeFailure(f"{phase}: loss did not fall {per_round}")
    return per_round


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _run_session(phase: str, session, rounds: int) -> dict:
    """Stack build, compile and two ``Session.run`` calls of one phase."""
    trainer = session.experiment.trainer(session.data)
    _, stack_s = _timed(lambda: trainer.mixing_stack(session.state, rounds))
    compiled, compile_s = _timed(lambda: session.lower(rounds).compile())
    first = session.run(rounds)
    second = session.run(rounds)
    loss = np.concatenate([np.asarray(first.metrics["loss"]),
                           np.asarray(second.metrics["loss"])])
    return {"phase": phase, "loss": _check_loss(phase, loss),
            "kernels": ops.pallas_kernels(compiled.as_text()),
            "compile_s": compile_s, "stack_build_s": stack_s,
            "first_run_s": first.wall_time_s,
            "ms_per_round": second.wall_time_s / rounds * 1e3}


# --------------------------------------------------------------------------
# Phases.
# --------------------------------------------------------------------------

def phase_paper(size: Size) -> dict:
    """(a) The paper's setting: K=4 on the static ring, dense mixing."""
    loss_fn, init = _mlp_fns(size.mlp)
    data, items = _fleet_data(size.mlp, size.paper_nodes, size.items)
    exp = Experiment.from_parts(
        loss_fn, init, train=_train(size),
        fed=FedConfig(num_nodes=size.paper_nodes,
                      local_steps=size.local_steps))
    return _run_session("paper", exp.compile(data, items), size.rounds)


def _city_fed(size: Size, **kw) -> FedConfig:
    return FedConfig(
        num_nodes=size.city_nodes, local_steps=size.local_steps,
        degree=size.degree,
        mobility=MobilityConfig(kind="manhattan", radio_range=500.0,
                                speed=10.0, seed=0),
        faults=FaultConfig(kinds=("link_drop", "crash", "straggle"),
                           drop_rate=0.1, crash_rate=0.05,
                           straggle_rate=0.1),
        ingest=IngestConfig(scenario="duplicate_heavy"), **kw)


def _city(phase: str, size: Size, fed: FedConfig) -> dict:
    loss_fn, init = _mlp_fns(size.mlp)
    (data, items), data_s = _timed(
        lambda: _fleet_data(size.mlp, size.city_nodes, size.items))
    exp = Experiment.from_parts(loss_fn, init, fed=fed, train=_train(size))
    out = _run_session(phase, exp.compile(data, items), size.rounds)
    out["data_s"] = data_s
    return out


def phase_city_sparse(size: Size) -> dict:
    """(c) A city fleet on the sparse top-D format, faults and ingest."""
    return _city("city_sparse", size, _city_fed(size,
                                                mixing_format="sparse"))


def phase_city_hier(size: Size) -> dict:
    """(d) Phase (c)'s fleet under two-tier hierarchical mixing."""
    return _city("city_hier", size,
                 _city_fed(size, mixing_format="hierarchical",
                           hierarchy=HierarchyConfig()))


def phase_sweep(size: Size) -> dict:
    """(e) Seeds x {static, platoon} of phase (a) in one vmapped scan."""
    loss_fn, init = _mlp_fns(size.mlp)
    data, items = _fleet_data(size.mlp, size.paper_nodes, size.items)
    exp = Experiment.from_parts(
        loss_fn, init, train=_train(size),
        fed=FedConfig(num_nodes=size.paper_nodes,
                      local_steps=size.local_steps))
    axes = SweepAxes(seeds=size.sweep_seeds, mobility=[
        None, MobilityConfig(kind="platoon", speed=20.0,
                             speed_jitter=0.15, dt=2.0, seed=0)])
    batched = exp.compile_batch(data, items, axes)
    rounds = size.rounds
    compiled, compile_s = _timed(lambda: batched.lower(rounds).compile())
    first = batched.run_batch(rounds)
    second = batched.run_batch(rounds)
    # (V, R, K) -> rounds leading
    loss = np.concatenate([np.asarray(first.metrics["loss"]),
                           np.asarray(second.metrics["loss"])], axis=1)
    return {"phase": "sweep", "variants": batched.num_variants,
            "loss": _check_loss("sweep", np.swapaxes(loss, 0, 1)),
            "kernels": ops.pallas_kernels(compiled.as_text()),
            "compile_s": compile_s, "first_run_s": first.wall_time_s,
            "ms_per_round": second.wall_time_s / rounds * 1e3}


def phase_cli(size: Size) -> dict:
    """The ``repro.launch.train`` CLI, in this process."""
    from repro.launch import train
    result, wall_s = _timed(lambda: train.main(list(size.cli_argv)))
    return {"phase": "cli", "wall_s": wall_s,
            "loss": _check_loss("cli", result.metrics["loss"])}


PHASES = (phase_paper, phase_city_sparse, phase_city_hier, phase_sweep,
          phase_cli)


# --------------------------------------------------------------------------
# Kernel vs XLA.
# --------------------------------------------------------------------------

def compare_kernels(size: Size) -> list:
    """One call of each mixing kernel (``use_kernel=True``) against its
    XLA form (``use_kernel=False``) on the same random inputs, at the
    phases' shapes. Returns ``[(name, max |difference|), ...]``; the
    XLA side's dots run at full f32 precision."""
    from repro.faults.robust import sorted_weights
    from repro.kernels.robust_agg import robust_agg_xla

    rng = np.random.default_rng(0)
    p = flatten.make_layout_one(
        simple.mlp_init(jax.random.PRNGKey(0), size.mlp)).padded

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def both(kernel, xla):
        got = np.asarray(kernel())
        with jax.default_matmul_precision("highest"):
            want = np.asarray(xla())
        return float(np.abs(got - want).max())

    out = []
    k = size.paper_nodes
    eta = jnp.asarray(rng.uniform(0, 1, (k, k)) / k, jnp.float32)
    master = normal(k, p)
    for wdt in (jnp.float32, jnp.bfloat16):
        wire = (master + 0.1 * normal(k, p)).astype(wdt)
        out.append((f"flat_mix_k{k}_{jnp.dtype(wdt).name}", both(
            lambda: flatten.mix_flat(master, eta, 0.5, use_kernel=True,
                                     wire=wire),
            lambda: flatten.mix_flat(master, eta, 0.5, use_kernel=False,
                                     wire=wire))))
    k, d = size.city_nodes, size.degree
    idx = jnp.asarray(rng.integers(0, k, (k, d)), jnp.int32)
    val = jnp.asarray(rng.uniform(0, 1, (k, d)) / d, jnp.float32)
    gnode = jnp.asarray(rng.uniform(0.1, 0.9, (k,)), jnp.float32)
    master = normal(k, p)
    for wdt in (jnp.float32, jnp.bfloat16):
        wire = (master + 0.1 * normal(k, p)).astype(wdt)
        out.append((f"sparse_mix_k{k}_{jnp.dtype(wdt).name}", both(
            lambda: flatten.sparse_mix_flat(master, idx, val, 0.5,
                                            use_kernel=True, wire=wire),
            lambda: flatten.sparse_mix_flat(master, idx, val, 0.5,
                                            use_kernel=False, wire=wire))))
    sent = master + 0.1 * normal(k, p)
    out.append((f"cluster_mix_k{k}", both(
        lambda: flatten.cluster_mix_flat(master, idx, val, gnode,
                                         use_kernel=True, wire=sent,
                                         wire_self=master),
        lambda: flatten.cluster_mix_flat(master, idx, val, gnode,
                                         use_kernel=False, wire=sent,
                                         wire_self=master))))
    k = 8
    buf, sent = normal(k, p), normal(k, p)
    mask = jnp.asarray(rng.random((k, k)) < 0.6) | jnp.eye(k, dtype=bool)
    w = sorted_weights(mask, "trimmed_mean", 1)
    out.append((f"robust_agg_k{k}", both(
        lambda: ops.robust_agg(w, mask, buf, sent, force_kernel=True),
        lambda: robust_agg_xla(w, mask, buf, sent))))
    return out


# --------------------------------------------------------------------------
# Entry point.
# --------------------------------------------------------------------------

def _fmt(v):
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, np.ndarray):
        return "[" + " ".join(f"{x:.4f}" for x in v) + "]"
    if isinstance(v, set):
        return ",".join(sorted(v)) or "-"
    return str(v)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}"
          f"  compile cache: {cache}")
    print("# smoke figures of one unrepeated run, not benchmark results")
    for name, diff in compare_kernels(FULL):
        print(f"kernel_vs_xla {name} max_abs_diff={diff:.3e} "
              f"tol={TOLERANCE:.0e}", flush=True)
        if not diff <= TOLERANCE:
            raise SmokeFailure(f"{name}: kernel and XLA differ by {diff}")
    for phase in PHASES:
        res = phase(FULL)
        name = res["phase"]
        print("smoke " + " ".join(f"{k}={_fmt(v)}" for k, v in res.items()),
              flush=True)
        missing = REQUIRED_KERNELS.get(name, set()) - res.get("kernels",
                                                              set())
        if missing:
            raise SmokeFailure(f"{name}: compiled round program lacks "
                               f"Pallas kernel(s) {sorted(missing)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
