"""Declarative experiment/session API — the user-facing façade over the
C-DFL machinery.

Instead of hand-wiring ``build_trainer`` + ``trainer.init`` +
``run_rounds(eval_fn=..., n_items=...)`` in every caller, an experiment
is declared once and compiled into a resumable session::

    exp = Experiment.from_parts(loss_fn, init_params,
                                fed=FedConfig(num_nodes=4, local_steps=10),
                                train=TrainConfig(learning_rate=1e-3))
    session = exp.compile(data, node_items)
    result = session.run(60, callbacks=[EvalCallback(eval_fn),
                                        CheckpointCallback("ckpt", every=20)])
    result.metrics["loss"]          # (R, K) stacked per-round metrics
    result.final_params             # node-stacked pytree

    session2 = exp.compile(data, node_items).resume("ckpt")
    session2.run(40)                # rounds 60..99 of the SAME run

Every plugin name in the configs (transport, wire codec, mixing policy,
mobility trace, algorithm) resolves through ``repro.registry`` — a newly
registered plugin is immediately constructible here.

Design constraints the façade honors:

* **No per-round dispatch overhead.** ``Session.run`` issues ONE
  ``Trainer.run_rounds`` scan per host-callback segment; with no
  periodic callbacks that is one scan for the whole run, identical to
  calling the trainer directly (the ``cdfl_*rounds_scan_flat`` bench row
  is emitted through this path). The trainer is compiled once per
  Experiment and shared by every Session it compiles, so jit caches are
  reused across sessions.
* **Segmentation invariance.** Batch sampling and mobility graphs are
  keyed on the ABSOLUTE round index (``FedState.round``), so
  run(10) + checkpoint + resume + run(10) reproduces run(20) exactly —
  per transport, per mobility scenario.
* **Callbacks subsume the ad-hoc kwargs.** Per-round eval rides the
  scan as a device-side metric (:class:`EvalCallback`); host-side hooks
  (:class:`CheckpointCallback`, :class:`ChurnLogCallback`) fire on
  segment boundaries.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import registry
from repro.checkpointing import restore as _ckpt_restore
from repro.checkpointing import save as _ckpt_save
from repro.configs.base import FedConfig, RunConfig, TrainConfig
from repro.core.cdfl import FedState, Trainer, build_trainer

__all__ = [
    "Experiment", "Session", "RunResult",
    "SweepAxes", "BatchedSession", "BatchResult",
    "Callback", "EvalCallback", "CheckpointCallback", "ChurnLogCallback",
    "DegreeStatsCallback", "HealthCallback", "IngestCallback",
]


# --------------------------------------------------------------------------
# Callbacks.
# --------------------------------------------------------------------------

class Callback:
    """Per-round hook riding a :meth:`Session.run`.

    ``every=N`` makes the run segment its scan at every N rounds and
    call :meth:`on_rounds` there (host-side work: checkpoints, logs);
    ``every=None`` keeps the whole run in one scan. Device-side
    per-round metrics (eval) are declared via :attr:`eval_fn` instead —
    they ride the scan and cost no extra dispatch.
    """

    every: Optional[int] = None
    eval_fn: Optional[Callable] = None   # params -> metric, vmapped over K

    def on_run_start(self, session: "Session", rounds: int) -> None:
        pass

    def on_rounds(self, session: "Session", end_round: int) -> None:
        """Called after the scan segment ending at ``end_round`` (an
        absolute round index, multiples of ``every``)."""

    def on_run_end(self, session: "Session", result: "RunResult") -> None:
        pass


class EvalCallback(Callback):
    """Per-round evaluation as a device-side scan metric: the stacked
    ``(R, K)`` values appear under ``result.metrics[name]`` with no
    per-round host sync (subsumes the old ``build_trainer(eval_fn=...)``
    kwarg)."""

    def __init__(self, eval_fn: Callable, name: str = "eval"):
        self.eval_fn = eval_fn
        self.name = name

    def on_run_end(self, session: "Session", result: "RunResult") -> None:
        # the trainer stacks the metric under its internal "eval" key;
        # honor the caller's name
        if self.name != "eval" and "eval" in result.metrics:
            result.metrics[self.name] = result.metrics.pop("eval")


class CheckpointCallback(Callback):
    """Save the session state every ``every`` rounds (and at run end)
    to ``path`` — the artifact :meth:`Session.resume` restarts from."""

    def __init__(self, path: str, every: Optional[int] = None):
        self.path = path
        self.every = every

    def on_rounds(self, session: "Session", end_round: int) -> None:
        session.save(self.path)

    def on_run_end(self, session: "Session", result: "RunResult") -> None:
        session.save(self.path)


class ChurnLogCallback(Callback):
    """Log the mobility scenario's link-churn summary for the rounds
    this run will cover (no-op on static topologies)."""

    def __init__(self, print_fn: Callable[[str], None] = print):
        self.print_fn = print_fn

    def on_run_start(self, session: "Session", rounds: int) -> None:
        fed = session.experiment.fed
        mob = fed.mobility
        if mob is None or mob.kind == "static":
            return
        from repro import mobility as mobility_lib
        from repro.core import topology
        # report the graph the run actually uses: the ring transport
        # gates radio links to the physical ring
        mask = (topology.adjacency("ring", fed.num_nodes)
                if fed.transport == "ring" else None)
        stats = mobility_lib.handover_stats(mobility_lib.adjacency_stack(
            mob, rounds, fed.num_nodes, mask=mask,
            start=session.rounds_completed))
        self.print_fn(
            f"mobility={mob.kind} range={mob.radio_range:.0f}m "
            f"speed={mob.speed:.0f}m/s: "
            f"{stats['links_per_round']:.1f} links/round, "
            f"churn={stats['churn_rate']:.3f}, "
            f"{stats['handovers']} handovers, "
            f"{stats['partitioned_rounds']}/{stats['rounds']} "
            f"partitioned rounds")


class DegreeStatsCallback(Callback):
    """Surface ``mobility.degree_stats`` for the rounds a run covers:
    one greppable line at run start (mean/max degree, isolated
    node-rounds, and the smallest lossless sparse top-D cap) and the
    per-round ``(R,)`` stacks injected into ``result.metrics`` under
    ``degree_max`` / ``degree_mean`` / ``degree_isolated`` at run end —
    the observability that picks ``FedConfig.degree`` and
    ``HierarchyConfig.max_cluster_size``. No-op on static topologies."""

    def __init__(self, print_fn: Callable[[str], None] = print):
        self.print_fn = print_fn
        self._stats: Optional[dict] = None

    def on_run_start(self, session: "Session", rounds: int) -> None:
        self._stats = None
        fed = session.experiment.fed
        mob = fed.mobility
        if mob is None or mob.kind == "static":
            return
        from repro import mobility as mobility_lib
        from repro.core import topology
        mask = (topology.adjacency("ring", fed.num_nodes)
                if fed.transport == "ring" else None)
        stats = mobility_lib.degree_stats(mobility_lib.adjacency_stack(
            mob, rounds, fed.num_nodes, mask=mask,
            start=session.rounds_completed))
        self._stats = stats
        self.print_fn(
            f"degrees: mean={float(stats['mean_degree'].mean()):.1f} "
            f"max={int(stats['max_degree'].max())} "
            f"isolated_node_rounds={int(stats['isolated'].sum())} "
            f"lossless_top_d={stats['max_degree_overall']}")

    def on_run_end(self, session: "Session", result: "RunResult") -> None:
        if self._stats is None:
            return
        result.metrics["degree_max"] = self._stats["max_degree"]
        result.metrics["degree_mean"] = self._stats["mean_degree"]
        result.metrics["degree_isolated"] = self._stats["isolated"]


class HealthCallback(Callback):
    """Summarize the fault-injection telemetry the scan emits when
    ``fed.faults`` is active (``health`` / ``quarantined`` / ``frozen``
    per-round ``(R, K)`` stacks in ``result.metrics``): one greppable
    line per run with crashed node-rounds, quarantined payloads, and
    frozen (self-healed) buffer-rounds. No-op on fault-free runs."""

    def __init__(self, print_fn: Callable[[str], None] = print):
        self.print_fn = print_fn

    def on_run_end(self, session: "Session", result: "RunResult") -> None:
        if "health" not in result.metrics:
            return
        health = np.asarray(result.metrics["health"])
        crashed = int((1.0 - health).sum())
        quarantined = int(np.asarray(result.metrics["quarantined"]).sum())
        frozen = int(np.asarray(result.metrics["frozen"]).sum())
        self.print_fn(
            f"health: rounds={result.rounds} nodes={health.shape[1]} "
            f"crashed_node_rounds={crashed} quarantined={quarantined} "
            f"frozen={frozen}")


class IngestCallback(Callback):
    """Summarize the streaming-redundancy telemetry the scan emits when
    ``fed.ingest`` is active (the per-round ``(R, K)`` ``est_distinct``
    stack in ``result.metrics``): one greppable line per run with each
    node's final effective-cardinality estimate and the fleet spread the
    mixing reweight gates on. No-op on ingest-free runs."""

    def __init__(self, print_fn: Callable[[str], None] = print):
        self.print_fn = print_fn

    def on_run_end(self, session: "Session", result: "RunResult") -> None:
        if "est_distinct" not in result.metrics:
            return
        est = np.asarray(result.metrics["est_distinct"])[-1]
        spread = float(est.max() / max(float(est.min()), 1e-9))
        vals = " ".join(f"{v:.0f}" for v in est)
        self.print_fn(
            f"ingest: rounds={result.rounds} nodes={est.shape[0]} "
            f"est_distinct=[{vals}] spread={spread:.2f}")


# --------------------------------------------------------------------------
# RunResult.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    """What one :meth:`Session.run` produced: the resumable final state,
    every per-round metric stacked along a leading (rounds,) axis, and
    wall time."""

    state: FedState
    metrics: Dict[str, jax.Array]
    rounds: int
    wall_time_s: float

    @property
    def final_params(self):
        """Node-stacked params pytree after the last round."""
        return self.state.params


# --------------------------------------------------------------------------
# Batched fleet sweeps.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepAxes:
    """What varies across the V variants of a batched fleet sweep.

    Every axis is optional; the variant set is the CROSS PRODUCT of the
    given axes (last axis fastest, like nested loops). The axes are the
    run inputs the batched driver can map at RUNTIME against one shared
    device program:

    seeds:    an int N (seeds ``0..N-1``) or an explicit sequence —
              seed ``s`` inits params from ``PRNGKey(s)`` and samples
              batches from ``PRNGKey(s + 1)``.
    lr:       per-variant learning rates (promoted from trace-time
              constant to a runtime argument; not available when the
              config's learning rate is a schedule).
    gamma:    per-variant consensus step-size caps (eq. 5's gamma,
              bounded per round by the stability bound as usual).
    mobility: per-variant ``MobilityConfig`` (or ``None`` for the
              static graph) — each variant runs its own kinematic
              scenario via a per-variant ``(V, R, K, K)`` /
              ``(V, R, K, D)`` stack.

    Everything else — fleet size, topology family, transport, local
    steps, fault plan, model — is config-static: trace-shaping, shared
    by all variants. Sweep those by building one batch per config.
    """

    seeds: Any = None
    lr: Optional[Sequence[float]] = None
    gamma: Optional[Sequence[float]] = None
    mobility: Optional[Sequence[Any]] = None

    def seed_list(self) -> Optional[list]:
        if self.seeds is None:
            return None
        if isinstance(self.seeds, int):
            if self.seeds <= 0:
                raise ValueError(f"seeds count must be positive, got "
                                 f"{self.seeds}")
            return list(range(self.seeds))
        seeds = [int(s) for s in self.seeds]
        if not seeds:
            raise ValueError("seeds sequence is empty")
        return seeds

    def variants(self) -> list:
        """The cross product, as a list of (seed, lr, gamma, mobility)
        namedtuple-like dicts; unswept axes hold ``None``."""
        axes = [
            ("seed", self.seed_list()),
            ("lr", list(self.lr) if self.lr is not None else None),
            ("gamma", list(self.gamma) if self.gamma is not None
             else None),
            ("mobility", list(self.mobility) if self.mobility is not None
             else None),
        ]
        swept = [(name, vals) for name, vals in axes if vals is not None]
        if not swept:
            raise ValueError(
                "SweepAxes needs at least one axis (seeds / lr / gamma "
                "/ mobility)")
        for name, vals in swept:
            if len(vals) == 0:
                raise ValueError(f"sweep axis {name!r} is empty")
        out = [dict(seed=None, lr=None, gamma=None, mobility=None)]
        for name, vals in swept:
            out = [dict(v, **{name: val}) for v in out for val in vals]
        return out


@dataclasses.dataclass
class BatchResult(RunResult):
    """What one :meth:`BatchedSession.run_batch` produced: every leaf of
    ``state`` and every metric carries a leading (V,) variant axis
    (metrics: ``(V, R, K)``); ``variants`` names what each slot ran."""

    variants: Sequence[dict] = ()

    @property
    def num_variants(self) -> int:
        return len(self.variants)

    def select(self, i: int) -> RunResult:
        """The single-variant view: variant ``i``'s final state and
        ``(R, K)`` metrics as a plain :class:`RunResult`."""
        return RunResult(
            state=jax.tree.map(lambda a: a[i], self.state),
            metrics={k: v[i] for k, v in self.metrics.items()},
            rounds=self.rounds, wall_time_s=self.wall_time_s)


# --------------------------------------------------------------------------
# Experiment.
# --------------------------------------------------------------------------

class Experiment:
    """A declared C-DFL experiment: configs + model functions.

    ``Experiment(run_config)`` derives the token-LM loss/init from
    ``run_config.model`` (a ``ModelConfig``); :meth:`from_parts` wires
    explicit ``loss_fn(params, batch)`` / ``init_params(rng)`` functions
    (the paper's MLP/VGG models, custom research models).

    The trainer is built lazily, once per distinct eval function, and
    shared by every :class:`Session` this experiment compiles — so
    repeated ``compile()`` calls (benchmark reps, sweeps over datasets)
    reuse one jit cache.
    """

    def __init__(self, config: Optional[RunConfig] = None, *,
                 fed: Optional[FedConfig] = None,
                 train: Optional[TrainConfig] = None,
                 model: Any = None,
                 loss_fn: Optional[Callable] = None,
                 init_params: Optional[Callable] = None,
                 eval_fn: Optional[Callable] = None,
                 transport: Any = None):
        if config is None:
            config = RunConfig(model=model, fed=fed or FedConfig(),
                               train=train or TrainConfig())
        elif fed is not None or train is not None or model is not None:
            raise ValueError("pass EITHER a RunConfig or fed/train/model "
                             "parts, not both")
        self.config = config
        self.loss_fn = loss_fn
        self.init_params = init_params
        self.eval_fn = eval_fn
        self.transport = transport
        self._trainers: dict[Any, Trainer] = {}
        registry.ensure_plugins()

    @classmethod
    def from_parts(cls, loss_fn: Callable, init_params: Callable, *,
                   fed: Optional[FedConfig] = None,
                   train: Optional[TrainConfig] = None,
                   model: Any = None,
                   eval_fn: Optional[Callable] = None,
                   transport: Any = None) -> "Experiment":
        """Declare an experiment from explicit model functions:
        ``loss_fn(params, batch) -> scalar`` (no K dim — the trainer
        vmaps over nodes) and ``init_params(rng) -> params``."""
        return cls(fed=fed, train=train, model=model, loss_fn=loss_fn,
                   init_params=init_params, eval_fn=eval_fn,
                   transport=transport)

    # -- convenience views --------------------------------------------------
    @property
    def fed(self) -> FedConfig:
        return self.config.fed

    @property
    def train(self) -> TrainConfig:
        return self.config.train

    # -- model derivation ---------------------------------------------------
    def _model_fns(self, data) -> tuple[Callable, Callable]:
        """(loss_fn, init_params) — explicit ones, or the token-LM pair
        derived from ``config.model`` (group size from the data's
        sequence length, as launch/train.py hand-wired before)."""
        if self.loss_fn is not None:
            if self.init_params is None:
                raise ValueError("loss_fn given without init_params")
            return self.loss_fn, self.init_params
        cfg = self.config.model
        if cfg is None or not hasattr(cfg, "vocab_size"):
            raise ValueError(
                "Experiment needs either loss_fn/init_params "
                "(Experiment.from_parts) or a ModelConfig on "
                "RunConfig.model to derive the token-LM loss from")
        from repro.models import transformer
        seq = jax.tree.leaves(data)[0].shape[-1]
        group = self.train.batch_size * seq

        def loss_fn(params, batch):
            return transformer.loss_fn(params, cfg, batch,
                                       group_size=group)

        return loss_fn, (lambda r: transformer.init_params(r, cfg))

    def trainer(self, data, eval_fn: Optional[Callable] = None) -> Trainer:
        """The compiled trainer for this experiment, cached per eval
        function (the one thing that changes the scanned metrics graph)
        and — for model-derived losses, whose normalization captures the
        sequence length — per data shape. The cache is bounded: a sweep
        passing a fresh eval lambda per run re-jits but cannot grow
        memory without limit."""
        eval_fn = eval_fn if eval_fn is not None else self.eval_fn
        key = (eval_fn, None if self.loss_fn is not None
               else jax.tree.leaves(data)[0].shape[-1])
        if key not in self._trainers:
            if len(self._trainers) >= 8:          # evict oldest jit caches
                self._trainers.pop(next(iter(self._trainers)))
            loss_fn, _ = self._model_fns(data)
            self._trainers[key] = build_trainer(
                loss_fn, self.fed, self.train, eval_fn=eval_fn,
                transport=self.transport)
        return self._trainers[key]

    # -- compile ------------------------------------------------------------
    def compile(self, data, node_items, *,
                rng: Optional[jax.Array] = None,
                sample_rng: Optional[jax.Array] = None,
                n_items=None, same_init: bool = True) -> "Session":
        """Build a live :class:`Session`: trainer + device-resident data
        + initialized :class:`FedState`.

        data:       pytree of node-stacked dataset arrays, leaves
                    (K, N, ...), keyed as ``loss_fn`` expects a batch.
        node_items: (K, n, f) int feature tokens per node — the CND
                    sketches (eqs. 6-7 weights) are built from these.
        rng:        params/init key (default ``PRNGKey(train.seed)``).
        sample_rng: base key for batch sampling across ALL rounds
                    (default ``PRNGKey(train.seed + 1)``, the
                    ``run_rounds`` default); per-round keys are folded
                    from it on the absolute round index.
        n_items:    optional (K,) true per-node item counts when the
                    resident arrays are padded to a common N (ragged
                    nodes, e.g. after CND dedup).
        """
        if rng is None:
            rng = jax.random.PRNGKey(self.train.seed)
        data = jax.tree.map(jnp.asarray, data)
        trainer = self.trainer(data)
        _, init_params = self._model_fns(data)
        state = trainer.init(rng, init_params, jnp.asarray(node_items),
                             same_init=same_init)
        return Session(self, data, state, n_items=n_items,
                       sample_rng=sample_rng)

    def compile_batch(self, data, node_items, axes: SweepAxes, *,
                      rng: Optional[jax.Array] = None,
                      sample_rng: Optional[jax.Array] = None,
                      n_items=None,
                      same_init: bool = True) -> "BatchedSession":
        """Build a :class:`BatchedSession`: V variant runs — the cross
        product of ``axes`` — compiled into ONE vmapped scan over a
        (V,)-stacked :class:`FedState`.

        The dataset, node sketches and any fault plan are SHARED by all
        variants (mapped with ``in_axes=None`` — one device copy);
        per-variant state costs ``V x (K, P)`` params plus two Adam
        moment buffers of the same shape, so budget roughly ``3 V K P``
        f32 on top of a single run. ``rng``/``sample_rng`` seed the
        variants only when the seed axis is unswept (a swept seed ``s``
        uses ``PRNGKey(s)`` / ``PRNGKey(s + 1)``).
        """
        if (axes.lr is not None and callable(self.train.learning_rate)):
            raise ValueError(
                "cannot sweep lr: this experiment's learning rate is a "
                "schedule (callable); per-variant rates only override "
                "constant rates")
        variants = axes.variants()
        if rng is None:
            rng = jax.random.PRNGKey(self.train.seed)
        if sample_rng is None:
            sample_rng = jax.random.PRNGKey(self.train.seed + 1)
        data = jax.tree.map(jnp.asarray, data)
        trainer = self.trainer(data)
        _, init_params = self._model_fns(data)
        node_items = jnp.asarray(node_items)
        # one init per UNIQUE seed (the only axis that changes init),
        # then assemble the (V,)-stacked state once at compile time
        inits: dict[Any, FedState] = {}
        for v in variants:
            if v["seed"] not in inits:
                r = (rng if v["seed"] is None
                     else jax.random.PRNGKey(v["seed"]))
                inits[v["seed"]] = trainer.init(r, init_params,
                                                node_items,
                                                same_init=same_init)
        states = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[inits[v["seed"]] for v in variants])
        rngs = jnp.stack([
            (sample_rng if v["seed"] is None
             else jax.random.PRNGKey(v["seed"] + 1)) for v in variants])
        return BatchedSession(self, data, states, variants, rngs, axes,
                              n_items=n_items)


# --------------------------------------------------------------------------
# Session.
# --------------------------------------------------------------------------

class Session:
    """A compiled, resumable run: live :class:`FedState` + resident data
    + the experiment's shared trainer. Not constructed directly — use
    :meth:`Experiment.compile`."""

    def __init__(self, experiment: Experiment, data, state: FedState, *,
                 n_items=None, sample_rng: Optional[jax.Array] = None):
        self.experiment = experiment
        self.data = data
        self._state = state
        self._n_items = None if n_items is None else jnp.asarray(n_items)
        self._rng = (jax.random.PRNGKey(experiment.train.seed + 1)
                     if sample_rng is None else sample_rng)

    @property
    def state(self) -> FedState:
        """The live federated state (params/opt/CND ratios/round/
        transport state). Donated to each scan — snapshot via
        :meth:`save` rather than holding references across runs."""
        return self._state

    @property
    def rounds_completed(self) -> int:
        return int(self._state.round)

    # -- running ------------------------------------------------------------
    def run(self, rounds: int, callbacks: Sequence[Callback] = (),
            rng: Optional[jax.Array] = None) -> RunResult:
        """Advance the session ``rounds`` federated rounds.

        With no periodic (``every=N``) callbacks this is ONE
        device-resident ``run_rounds`` scan — the façade adds no
        per-round dispatch. Periodic callbacks split the run into
        boundary-aligned scan segments; metrics are re-stacked across
        segments so the result is indistinguishable from one scan.
        """
        if rounds <= 0:
            raise ValueError(f"rounds must be positive, got {rounds}")
        callbacks = list(callbacks)
        eval_fns = [cb.eval_fn for cb in callbacks
                    if cb.eval_fn is not None]
        if len(eval_fns) > 1:
            raise ValueError("at most one EvalCallback per run")
        trainer = self.experiment.trainer(
            self.data, eval_fn=eval_fns[0] if eval_fns else None)
        rng = self._rng if rng is None else rng

        marks = {rounds}
        for cb in callbacks:
            if cb.every:
                marks.update(range(cb.every, rounds + 1, cb.every))
        for cb in callbacks:
            cb.on_run_start(self, rounds)

        t0 = time.time()
        start = self.rounds_completed
        parts = []
        prev = 0
        for mark in sorted(marks):
            self._state, metrics = trainer.run_rounds(
                self._state, self.data, mark - prev, rng=rng,
                n_items=self._n_items)
            parts.append(metrics)
            prev = mark
            for cb in callbacks:
                if cb.every and mark % cb.every == 0 and mark < rounds:
                    cb.on_rounds(self, start + mark)
        metrics = (parts[0] if len(parts) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *parts))
        jax.block_until_ready(self._state.params)
        result = RunResult(state=self._state, metrics=metrics,
                           rounds=rounds, wall_time_s=time.time() - t0)
        for cb in callbacks:
            cb.on_run_end(self, result)
        return result

    def lower(self, rounds: int, rng: Optional[jax.Array] = None):
        """The scan one callback-free ``run(rounds)`` dispatches, lowered
        and not run: ``.compile()`` gives the device program (its
        ``as_text()`` shows which kernels a round runs). The live state
        is not donated."""
        trainer = self.experiment.trainer(self.data)
        return trainer.lower_rounds(self._state, self.data, rounds,
                                    rng=self._rng if rng is None else rng,
                                    n_items=self._n_items)

    # -- checkpoint / resume -------------------------------------------------
    def save(self, path: str) -> str:
        """Checkpoint the FULL resumable state (params, optimizer, CND
        ratios/sizes, round counter, transport state) to ``path``."""
        _ckpt_save(path, self._state, step=self.rounds_completed)
        return path

    def resume(self, path: str) -> "Session":
        """Restore a checkpoint written by :meth:`save` (or a
        :class:`CheckpointCallback`) into this session and continue the
        SAME run: the restored round counter keys batch sampling and the
        mobility trace, so resumed rounds reproduce an unsegmented run
        exactly (fault schedules included: they are compiled from round 0
        and sliced at the restored round). Returns ``self`` for
        chaining."""
        try:
            self._state = _ckpt_restore(path, self._state)
        except Exception as e:
            raise ValueError(
                f"cannot resume from {path!r}: checkpoint does not match "
                f"this session's state layout (was it saved under a "
                f"different algorithm/transport/fault config or model "
                f"size, or is it corrupt?): {e}") from e
        return self


# --------------------------------------------------------------------------
# BatchedSession.
# --------------------------------------------------------------------------

class BatchedSession:
    """V variant runs compiled into one vmapped scan: a (V,)-stacked
    :class:`FedState` over shared resident data. Not constructed
    directly — use :meth:`Experiment.compile_batch`.

    Unlike :class:`Session` this is NOT resumable: a batched run is a
    one-shot sweep (checkpointing V entangled variants into the
    single-run checkpoint format would silently break the
    segmentation-invariance contract), so :meth:`save` and
    :meth:`resume` raise. Re-run the winning variant through a plain
    ``compile()`` Session when it needs checkpoints."""

    def __init__(self, experiment: Experiment, data, states: FedState,
                 variants: Sequence[dict], rngs: jax.Array,
                 axes: SweepAxes, *, n_items=None):
        self.experiment = experiment
        self.data = data
        self._states = states
        self.variants = list(variants)
        self._rngs = rngs
        self._axes = axes
        self._n_items = None if n_items is None else jnp.asarray(n_items)

    @property
    def num_variants(self) -> int:
        return len(self.variants)

    @property
    def states(self) -> FedState:
        """The live (V,)-stacked federated state (donated to each
        batched scan — do not hold references across runs)."""
        return self._states

    @property
    def rounds_completed(self) -> int:
        return int(np.asarray(self._states.round)[0])

    def run_batch(self, rounds: int,
                  callbacks: Sequence[Callback] = ()) -> BatchResult:
        """Advance ALL variants ``rounds`` federated rounds in ONE
        device program — one trace, one dispatch, V runs.

        Only scan-riding callbacks are allowed (one
        :class:`EvalCallback`, run-boundary hooks): periodic
        ``every=N`` callbacks segment the scan with host-side work per
        variant, which defeats the batching — they raise here.
        """
        if rounds <= 0:
            raise ValueError(f"rounds must be positive, got {rounds}")
        callbacks = list(callbacks)
        for cb in callbacks:
            if cb.every:
                raise ValueError(
                    f"{type(cb).__name__}(every={cb.every}) needs "
                    f"host-side scan segmentation — unsupported on "
                    f"batched runs; use a plain Session per variant "
                    f"for periodic callbacks")
        eval_fns = [cb.eval_fn for cb in callbacks
                    if cb.eval_fn is not None]
        if len(eval_fns) > 1:
            raise ValueError("at most one EvalCallback per run")
        trainer = self.experiment.trainer(
            self.data, eval_fn=eval_fns[0] if eval_fns else None)
        for cb in callbacks:
            cb.on_run_start(self, rounds)
        t0 = time.time()
        self._states, metrics = trainer.run_rounds_batch(
            self._states, self.data, rounds,
            **self._batch_inputs(trainer, rounds))
        jax.block_until_ready(self._states.params)
        result = BatchResult(state=self._states, metrics=metrics,
                             rounds=rounds,
                             wall_time_s=time.time() - t0,
                             variants=self.variants)
        for cb in callbacks:
            cb.on_run_end(self, result)
        return result

    def lower(self, rounds: int):
        """The vmapped scan one callback-free ``run_batch(rounds)``
        dispatches, lowered and not run (see :meth:`Session.lower`)."""
        trainer = self.experiment.trainer(self.data)
        return trainer.lower_rounds_batch(
            self._states, self.data, rounds,
            **self._batch_inputs(trainer, rounds))

    def _batch_inputs(self, trainer: Trainer, rounds: int) -> dict:
        """The per-variant keyword inputs of the batched scan: sampling
        keys, item counts, and — when mobility or gamma is swept — the
        (V,)-stacked mixing and step-size stacks."""
        start = self.rounds_completed
        etas = gammas = None
        mob_swept = self._axes.mobility is not None
        gamma_swept = self._axes.gamma is not None
        if mob_swept or gamma_swept:
            # per-variant graphs: build each UNIQUE (scenario, cap)
            # stack once, share when the cross product collapses to one
            state0 = jax.tree.map(lambda a: a[0], self._states)
            keys = [(v["mobility"] if mob_swept else "config",
                     v["gamma"] if gamma_swept else None)
                    for v in self.variants]
            uniq: Dict[Any, Any] = {}
            for key in keys:
                if key not in uniq:
                    uniq[key] = trainer.mixing_stack(
                        state0, rounds, start=start, mobility=key[0],
                        gamma_cap=key[1])
            if len(uniq) == 1:
                etas, gammas = next(iter(uniq.values()))
            else:
                from repro.mobility import mixing as mobility_mixing
                etas = mobility_mixing.stack_variant_stacks(
                    [uniq[k][0] for k in keys])
                gammas = jnp.stack([jnp.asarray(uniq[k][1], jnp.float32)
                                    for k in keys])
        lrs = None
        if self._axes.lr is not None:
            lrs = jnp.asarray([v["lr"] for v in self.variants],
                              jnp.float32)
        return dict(rngs=self._rngs, n_items=self._n_items,
                    eta_stacks=etas, gamma_stacks=gammas, lrs=lrs)

    # -- checkpoint / resume: deliberately unsupported ----------------------
    def save(self, path: str) -> str:
        raise ValueError(
            "cannot checkpoint a batched run: the (V,)-stacked state "
            "does not fit the single-run checkpoint format. Re-run the "
            "variant you want to keep through Experiment.compile() and "
            "save that Session.")

    def resume(self, path: str) -> "BatchedSession":
        raise ValueError(
            "cannot resume a batched run: batched sessions are one-shot "
            "sweeps. Resume single-run checkpoints through "
            "Experiment.compile().resume(path).")


# --------------------------------------------------------------------------
# Legacy bridge.
# --------------------------------------------------------------------------

def run_experiment(config: RunConfig, data, node_items, rounds: int,
                   **compile_kw) -> RunResult:
    """One-call convenience: declare, compile, run."""
    return Experiment(config).compile(data, node_items,
                                      **compile_kw).run(rounds)
