"""The system under test, declared from a configuration and a traffic.

Every setting is stated here from the benchmark's own files, so the
program runs what the configuration says and not its own defaults.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def experiment(family, config: dict, desc: dict, traffic: dict):
    """The cell's ``Experiment``, with every setting from the benchmark's
    files and the model from the configuration's ``family``."""
    from repro.configs.base import (FaultConfig, FedConfig, IngestConfig,
                                    MobilityConfig, TrainConfig)
    from repro.experiment import Experiment

    train, fleet = config["train"], config["fleet"]
    mob = desc["variants"][0]["mobility"]
    if traffic.get("variants") is not None:
        mob = None                      # swept per variant, not configured
    faults, ingest = desc["faults"], desc["ingest"]
    fed = FedConfig(
        num_nodes=fleet["num_nodes"], topology=fleet["topology"],
        gamma=fleet["gamma"], algorithm=fleet["algorithm"],
        local_steps=fleet["local_steps"], cnd_bits=fleet["cnd_bits"],
        cnd_hashes=fleet["cnd_hashes"],
        mixing_format=fleet["mixing_format"],
        degree=fleet.get("degree") or 8,
        wire_dtype=fleet["wire_dtype"],
        mobility=None if mob is None else MobilityConfig(**mob),
        faults=None if faults is None else FaultConfig(
            **dict(faults, kinds=tuple(faults["kinds"]))),
        ingest=None if ingest is None else IngestConfig(**ingest))
    tc = TrainConfig(learning_rate=train["learning_rate"],
                     beta1=train["beta1"], beta2=train["beta2"],
                     eps=train["eps"], batch_size=train["batch_size"],
                     seed=desc["variants"][0]["init_seed"])
    loss_fn, init_params = family.model(config)
    return Experiment.from_parts(loss_fn, init_params, fed=fed, train=tc)


def mobility_config(mob: dict | None):
    from repro.configs.base import MobilityConfig
    return None if mob is None else MobilityConfig(**mob)


def leaf_norms(leaves: list) -> np.ndarray:
    """(V, K, leaves) Euclidean norm of every node's every leaf, on the
    host; each leaf a NumPy array with leading (V, K) axes."""
    return np.stack([np.sqrt(np.sum(np.square(a.astype(np.float64)).reshape(
        a.shape[:2] + (-1,)), axis=-1)) for a in leaves], axis=-1)


def flat_leaf_norms(buf, sizes) -> jax.Array:
    """(V, K, leaves) norms of consecutive slices of a (V, K, P) buffer,
    the leaves packed in the order of ``sizes``."""
    out, off = [], 0
    for size in sizes:
        out.append(jnp.sqrt(jnp.sum(jnp.square(buf[..., off:off + size]),
                                    axis=-1)))
        off += size
    return jnp.stack(out, axis=-1)
