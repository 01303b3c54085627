"""Plain reference of C-DFL rounds with the paper's MLP (arXiv:2209.10722
Sec. 5): the MLP 784-30-10 written from the paper, bound to the
model-agnostic rounds of ``cdfl_rounds.py``. Imports nothing of the
system under test."""
from __future__ import annotations

import sys
from functools import partial

import jax
import jax.numpy as jnp

from benchlib import spec

LEAVES = ("b1", "b2", "w1", "w2")          # sorted, as a dict pytree flattens
BATCH = ("x", "y")                         # (K, N, D) images, (K, N) labels


def init(key, config: dict, dtype):
    m = config["model"]
    d, h, c = m["input_dim"], m["hidden"], m["num_classes"]
    r1, r2 = jax.random.split(key)
    return {"w1": (jax.random.normal(r1, (d, h)) * d ** -0.5).astype(dtype),
            "b1": jnp.zeros((h,), dtype),
            "w2": (jax.random.normal(r2, (h, c)) * h ** -0.5).astype(dtype),
            "b2": jnp.zeros((c,), dtype)}


def loss(p, batch):
    """Softmax cross-entropy of the 784-30-10 MLP (one ReLU layer)."""
    h = jnp.maximum(batch["x"] @ p["w1"] + p["b1"], 0)
    logits = h @ p["w2"] + p["b2"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()


# run(config, traffic, inputs, calls, dtype=jnp.float32, plant=None)
run = partial(spec.module("reference", "cdfl_rounds").run,
              sys.modules[__name__])
