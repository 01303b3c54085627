"""Entry kind ``session``: one ``Session.run(rounds, callbacks)`` per call,
the way a study drives the system (``Experiment.compile`` once, then
back-to-back runs that continue the same federated run)."""
from __future__ import annotations

import jax
import numpy as np

from benchlib import build


class Entry:
    def __init__(self, family, config: dict, traffic: dict, inputs: dict,
                 desc: dict):
        from repro.experiment import EvalCallback
        exp = build.experiment(family, config, desc, traffic)
        var = desc["variants"][0]
        self.session = exp.compile(family.batch(inputs),
                                   family.items(inputs),
                                   rng=jax.random.PRNGKey(var["init_seed"]),
                                   sample_rng=jax.random.PRNGKey(
                                       var["sample_seed"]))
        self.rounds = traffic["rounds_per_call"]
        self.callbacks = ([EvalCallback(family.eval_fn(inputs))]
                          if traffic.get("eval") else [])

    def call(self):
        result = self.session.run(self.rounds, callbacks=self.callbacks)
        jax.block_until_ready((result.state, result.metrics))
        return result

    @staticmethod
    def loss(result) -> np.ndarray:
        """(V=1, R, K) per-round, per-node training loss."""
        return np.asarray(result.metrics["loss"])[None]

    def params(self):
        """Node-stacked parameters with a leading variant axis of 1: a
        copy on the host, which a later call's donation cannot touch."""
        return jax.tree.map(lambda a: np.array(a)[None],
                            self.session.state.params)

    def first_moment(self) -> jax.Array:
        """Adam's first moment as a (1, K, P) buffer."""
        return self.session.state.opt.m[None]
