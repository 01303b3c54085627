"""The paper's MLP family (arXiv:2209.10722 Sec. 5): one ReLU layer on
class-template synthetic MNIST.

A family is everything of a cell that depends on the model, found by the
``family`` key of its configuration (``benchlib/spec.py``): the fleet's
data made from the seed, the model the program trains, what the entries
compile on, the evaluation, the packed leaves, the FLOPs of a sample, the
reference's inputs and the CPU shrink of the tests. The harness reads
nothing of the model but through these functions.

The data's arithmetic is that of class-template synthetic MNIST (fixed
class templates, each item its class template plus Gaussian noise),
vectorized over vehicles so that a fleet of 1,024 vehicles costs one
jitted call rather than a loop over them. The CND feature tokens of each
item are its pixels pooled into ``features`` buckets and quantized to 12
bits over the vehicle's own range, as a vehicle would tokenize its
records.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import data

TEMPLATE_SEED = 1234          # class templates are shared by every run


@partial(jax.jit, static_argnames=("k", "n", "n_test", "dim", "classes",
                                   "features"))
def _make(key, noise, *, k, n, n_test, dim, classes, features):
    templates = jax.random.normal(jax.random.PRNGKey(TEMPLATE_SEED),
                                  (classes, dim))
    ky, kx, kty, ktx = jax.random.split(key, 4)
    y = jax.random.randint(ky, (k, n), 0, classes)
    x = templates[y] + noise * jax.random.normal(kx, (k, n, dim))
    pooled = x.reshape(k, n, features, dim // features).mean(-1)
    lo = pooled.min(axis=(1, 2), keepdims=True)
    hi = pooled.max(axis=(1, 2), keepdims=True)
    items = ((pooled - lo) / (hi - lo + 1e-9) * 4095).astype(jnp.int32)
    yt = jax.random.randint(kty, (n_test,), 0, classes)
    xt = templates[yt] + noise * jax.random.normal(ktx, (n_test, dim))
    return x, y, items, xt, yt


def fleet(config: dict, seed: int) -> dict:
    """``x`` (K, N, D), ``y`` (K, N), ``items`` (K, N, F) and the shared
    test set ``xt``, ``yt`` of K x test_per_node items, on the device."""
    model, spec = config["model"], config["data"]
    k = config["fleet"]["num_nodes"]
    key = jax.random.fold_in(jax.random.PRNGKey(data.base_seed(seed)), 7)
    x, y, items, xt, yt = _make(
        key, jnp.float32(spec["noise"]), k=k, n=spec["train_per_node"],
        n_test=max(1, k * spec["test_per_node"]), dim=model["input_dim"],
        classes=model["num_classes"], features=spec["features"])
    out = {"x": x, "y": y, "items": items, "xt": xt, "yt": yt}
    jax.block_until_ready(out)
    return out


def _mlp(config: dict):
    from repro.configs.paper_models import MLPConfig
    model, train = config["model"], config["train"]
    return MLPConfig(input_dim=model["input_dim"], hidden=model["hidden"],
                     num_classes=model["num_classes"],
                     learning_rate=train["learning_rate"],
                     beta1=train["beta1"], beta2=train["beta2"],
                     eps=train["eps"], batch_size=train["batch_size"])


def model(config: dict):
    """``(loss_fn, init_params)`` for ``Experiment.from_parts``."""
    from repro.models import simple
    mlp = _mlp(config)
    return simple.make_mlp_loss(mlp), lambda r: simple.mlp_init(r, mlp)


def batch(inputs: dict) -> dict:
    """The node-stacked arrays the program samples its minibatches from."""
    return {"x": inputs["x"], "y": inputs["y"]}


def items(inputs: dict):
    """(K, N, F) CND feature tokens of every node's items."""
    return inputs["items"]


def eval_fn(inputs: dict):
    """Accuracy of one node's parameters on the fleet's shared test set."""
    from repro.models import simple
    xt, yt = inputs["xt"], inputs["yt"]

    def fn(params):
        return simple.accuracy(simple.mlp_forward(params, xt), yt)
    return fn


def leaves(config: dict) -> list:
    """``(name, elements)`` of every leaf in packed (sorted-name) order."""
    m = config["model"]
    d, h, c = m["input_dim"], m["hidden"], m["num_classes"]
    return [("b1", h), ("b2", c), ("w1", d * h), ("w2", h * c)]


def flops_per_sample(config: dict) -> int:
    """FLOPs of one training sample, one image and its label: forward and
    backward through every weight, 6 x the parameters."""
    return 6 * config["params"]


def reference_inputs(inputs: dict) -> dict:
    """The fleet data the plain reference gathers its minibatches from,
    and the CND feature tokens on the host."""
    return {"x": inputs["x"], "y": inputs["y"],
            "items": np.asarray(inputs["items"])}


def tiny(config: dict) -> dict:
    """``config`` with the model and data cut, in place, to a CPU test's
    size: 64 inputs, 8 hidden units, 64 items a node."""
    config["model"].update(input_dim=64, hidden=8)
    config["params"] = sum(n for _, n in leaves(config))
    config["data"]["train_per_node"] = 64
    return config
