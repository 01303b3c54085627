"""Entry kind ``batched``: one ``BatchedSession.run_batch(rounds,
callbacks)`` per call over every variant of a sweep, the way the paper's
tables are run (``Experiment.compile_batch`` once)."""
from __future__ import annotations

import jax
import numpy as np

from benchlib import build


class Entry:
    def __init__(self, family, config: dict, traffic: dict, inputs: dict,
                 desc: dict):
        from repro.experiment import EvalCallback, SweepAxes
        exp = build.experiment(family, config, desc, traffic)
        sweep = traffic["variants"]
        seeds = sorted({v["init_seed"] for v in desc["variants"]})
        mobs = [build.mobility_config(v["mobility"])
                for v in desc["variants"][:len(sweep["mobility"])]]
        self.session = exp.compile_batch(
            family.batch(inputs), family.items(inputs),
            SweepAxes(seeds=seeds, mobility=mobs))
        self.rounds = traffic["rounds_per_call"]
        self.callbacks = ([EvalCallback(family.eval_fn(inputs))]
                          if traffic.get("eval") else [])

    def call(self):
        result = self.session.run_batch(self.rounds,
                                        callbacks=self.callbacks)
        jax.block_until_ready((result.state, result.metrics))
        return result

    @staticmethod
    def loss(result) -> np.ndarray:
        """(V, R, K) per-round, per-node training loss of every variant."""
        return np.asarray(result.metrics["loss"])

    def params(self):
        """(V, K, ...) parameters of every variant: a copy on the host,
        which a later call's donation cannot touch."""
        return jax.tree.map(np.array, self.session.states.params)

    def first_moment(self) -> jax.Array:
        """Adam's first moment as a (V, K, P) buffer."""
        return self.session.states.opt.m
