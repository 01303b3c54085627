"""A model family that exists only in the tests: next-token prediction
on token sequences, to show that a configuration of another family runs
through the harness by files alone.

The family half (``fleet`` ... ``tiny``) is what ``families/<family>.py``
gives for a cell; ``Plain`` is its plain reference model, which the
shared rounds of ``reference/cdfl_rounds.py`` train, and ``REFERENCE``
the binding a configuration's ``reference`` names. Data: (K, N, S) int32
sequences whose tokens step by a stride of the vehicle's own, some
replaced at random, with next-token labels. Model: an embedding, a tanh
and an output projection, leaves ``embed`` and ``head``; one sample is
one sequence of S tokens.
"""
from __future__ import annotations

import copy
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import data, spec


def configure(config: dict) -> dict:
    """A cell's configuration with this family's model and data in
    place of its own; fleet, training and ingest stay."""
    cfg = copy.deepcopy(config)
    cfg.update(family="tiny_tokens", reference="tiny_tokens",
               model={"vocab": 256, "width": 64, "seq": 32},
               data={"train_per_node": 320, "test_per_node": 8,
                     "noise": 0.1})
    cfg["params"] = sum(n for _, n in leaves(cfg))
    return cfg


# -- the family -----------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "n", "n_test", "vocab", "seq"))
def _make(key, noise, *, k, n, n_test, vocab, seq):
    ks, kr, kt = jax.random.split(key, 3)
    stride = jax.random.randint(ks, (k, 1, 1), 1, vocab)

    def sequences(key, shape, stride):
        k0, k1, k2 = jax.random.split(key, 3)
        start = jax.random.randint(k0, shape + (1,), 0, vocab)
        seqs = (start + stride * jnp.arange(seq + 1)) % vocab
        flip = jax.random.uniform(k1, seqs.shape) < noise
        return jnp.where(flip, jax.random.randint(k2, seqs.shape, 0, vocab),
                         seqs).astype(jnp.int32)
    train = sequences(kr, (k, n), stride)
    test = sequences(kt, (n_test,), stride[0, 0])
    return train[..., :-1], train[..., 1:], test[:, :-1], test[:, 1:]


def fleet(config: dict, seed: int) -> dict:
    """``tokens`` and ``labels`` (K, N, S), and a shared test set drawn
    with the first vehicle's stride."""
    m, d = config["model"], config["data"]
    k = config["fleet"]["num_nodes"]
    key = jax.random.fold_in(jax.random.PRNGKey(data.base_seed(seed)), 11)
    tokens, labels, tt, tl = _make(
        key, jnp.float32(d["noise"]), k=k, n=d["train_per_node"],
        n_test=max(1, k * d["test_per_node"]), vocab=m["vocab"],
        seq=m["seq"])
    out = {"tokens": tokens, "labels": labels, "test_tokens": tt,
           "test_labels": tl}
    jax.block_until_ready(out)
    return out


def _logits(params, tokens):
    return jnp.tanh(params["embed"][tokens]) @ params["head"]


def model(config: dict):
    v, w = config["model"]["vocab"], config["model"]["width"]

    def init(rng):
        r1, r2 = jax.random.split(rng)
        return {"embed": jax.random.normal(r1, (v, w)),
                "head": jax.random.normal(r2, (w, v)) * w ** -0.5}

    def loss(params, batch):
        logp = jax.nn.log_softmax(_logits(params, batch["tokens"]), -1)
        onehot = jax.nn.one_hot(batch["labels"], v, dtype=logp.dtype)
        return -(logp * onehot).sum(-1).mean()
    return loss, init


def batch(inputs: dict) -> dict:
    return {"tokens": inputs["tokens"], "labels": inputs["labels"]}


def items(inputs: dict):
    """A sequence's tokens are its CND features."""
    return inputs["tokens"]


def eval_fn(inputs: dict):
    tt, tl = inputs["test_tokens"], inputs["test_labels"]

    def fn(params):
        return (_logits(params, tt).argmax(-1) == tl).mean()
    return fn


def leaves(config: dict) -> list:
    v, w = config["model"]["vocab"], config["model"]["width"]
    return [("embed", v * w), ("head", w * v)]


def flops_per_sample(config: dict) -> int:
    """One sequence: forward and backward through the output projection
    at every position, 6 x width x vocab x seq."""
    m = config["model"]
    return 6 * m["width"] * m["vocab"] * m["seq"]


def reference_inputs(inputs: dict) -> dict:
    return {"tokens": inputs["tokens"], "labels": inputs["labels"],
            "items": np.asarray(inputs["tokens"])}


def tiny(config: dict) -> dict:
    """``config`` cut in place: vocabulary 32, width 16, sequences of 8,
    64 a node."""
    config["model"].update(vocab=32, width=16, seq=8)
    config["params"] = sum(n for _, n in leaves(config))
    config["data"]["train_per_node"] = 64
    return config


# -- the plain reference model --------------------------------------------

class Plain:
    """Embedding, tanh and output projection, the loss written as a
    log-softmax gather."""

    LEAVES = ("embed", "head")
    BATCH = ("tokens", "labels")

    @staticmethod
    def init(key, config: dict, dtype):
        v, w = config["model"]["vocab"], config["model"]["width"]
        r1, r2 = jax.random.split(key)
        return {"embed": jax.random.normal(r1, (v, w)).astype(dtype),
                "head": (jax.random.normal(r2, (w, v))
                         * w ** -0.5).astype(dtype)}

    @staticmethod
    def loss(p, batch):
        logits = jnp.tanh(p["embed"][batch["tokens"]]) @ p["head"]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        return -jnp.take_along_axis(logp, batch["labels"][..., None],
                                    axis=-1).mean()


REFERENCE = types.SimpleNamespace(
    run=partial(spec.module("reference", "cdfl_rounds").run, Plain))
