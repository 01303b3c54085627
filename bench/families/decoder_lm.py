"""Decoder language models of the catalog, cut to one chip's share of a
stated deployment: next-token prediction on token sequences.

The configuration file holds the model's own ``config.json`` keys (the
held counts where the deployment divides a layer: experts, heads,
vocabulary rows) beside the harness's keys; ``arch`` names the model in
``repro.configs.registry`` and ``deployment`` says over how many chips
each layer is divided and which chip this is. ``model_config`` builds the
program's ``ModelConfig`` from those, through ``configs.base.chip_share``,
so the next catalog model of this kind is a new configuration file.

Data: each station's sequences repeat lines of ``line_tokens`` tokens
drawn from a pool of ``lines``, the lines Zipf-distributed, their tokens
Zipf-distributed over the station's own ordering of the held vocabulary
slice: synthetic, code-like (repeated lines, a skewed vocabulary), made on
the device from the seed. One sample is one sequence; its first
``features`` tokens are its CND feature tokens.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import data


def _attention_kind(config: dict, name: str):
    from repro.configs.base import AttentionKind, RopeConfig
    rope = config["rope_parameters"][name]
    return AttentionKind(
        window=config["sliding_window"] if name == "sliding_attention"
        else None,
        rope=RopeConfig(
            theta=float(rope["rope_theta"]), kind=rope["rope_type"],
            factor=float(rope.get("factor", 1.0)),
            original_max_positions=int(
                rope.get("original_max_position_embeddings", 0)),
            beta_fast=float(rope.get("beta_fast", 32.0)),
            beta_slow=float(rope.get("beta_slow", 1.0)),
            attention_factor=float(rope.get("attention_factor", 1.0))))


def model_config(config: dict):
    """The program's ``ModelConfig`` of the configuration: the whole
    model at the file's widths and the deployment's published counts,
    then this chip's share of its first ``num_hidden_layers`` layers,
    which has to hold what the file's counts say."""
    from repro.configs import base
    from repro.configs.registry import get_arch
    if (config["attention_bias"] or config["hidden_act"] != "silu"
            or config["rms_norm_eps"] != 1e-6
            or set(config["mlp_layer_types"]) != {"sparse"}):
        raise ValueError("decoder_lm runs bias-free SwiGLU expert layers "
                         "under RMSNorm (eps 1e-6)")
    dep = config["deployment"]
    pub = dep["published"]
    pattern = tuple(config["layer_types"])
    whole = dataclasses.replace(
        get_arch(config["arch"]),
        num_layers=len(pattern), block_pattern=pattern,
        attention_kinds=tuple((n, _attention_kind(config, n))
                              for n in dict.fromkeys(pattern)),
        d_model=config["hidden_size"], head_dim=config["head_dim"],
        d_ff=config["moe_intermediate_size"],
        num_heads=pub["num_attention_heads"],
        num_kv_heads=pub["num_key_value_heads"],
        num_experts=pub["num_experts"], experts_held=pub["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=pub["vocab_size"],
        router_aux_coef=config["router_aux_loss_coef"],
        tie_embeddings=config["tie_word_embeddings"], dtype="float32",
        matmul_precision=config.get("matmul_precision", ""))
    share = base.chip_share(whole, dep["chips_per_layer"], dep["chip"],
                            config["num_hidden_layers"])
    held = (share.held_experts(), share.held_heads(), share.held_kv_heads(),
            share.held_vocab())
    if held != (config["num_experts"], config["num_attention_heads"],
                config["num_key_value_heads"], config["vocab_size"]):
        raise ValueError(f"the deployment's share holds {held}, not the "
                         f"file's experts, heads and vocabulary")
    return share


# -- data -------------------------------------------------------------------

def _zipf_draw(key, n: int, alpha: float, shape):
    """Ranks in [0, n) with P(rank r) proportional to (r + 1)^-alpha,
    by inverse CDF."""
    w = jnp.arange(1, n + 1, dtype=jnp.float32) ** -alpha
    cdf = jnp.cumsum(w) / w.sum()
    u = jax.random.uniform(key, shape)
    return jnp.minimum(jnp.searchsorted(cdf, u), n - 1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k", "n", "n_test", "seq", "vocab",
                                   "offset", "lines", "width", "features"))
def _make(key, token_zipf, line_zipf, *, k, n, n_test, seq, vocab, offset,
          lines, width, features):
    kp, kt, kl, kx = jax.random.split(key, 4)
    order = jax.vmap(lambda kk: jax.random.permutation(kk, vocab))(
        jax.random.split(kp, k))                              # (K, V)
    ranks = _zipf_draw(kt, vocab, token_zipf, (k, lines * width))
    pool = jnp.take_along_axis(order, ranks, axis=1).reshape(
        k, lines, width)                                      # (K, L, W)
    per_seq = seq // width + 1

    def sequences(key, count):
        pick = _zipf_draw(key, lines, line_zipf, (k, count, per_seq))
        s = pool[jnp.arange(k)[:, None, None], pick]
        return s.reshape(k, count, -1)[..., :seq + 1] + offset

    train = sequences(kl, n)
    test = sequences(kx, n_test)[0]
    return (train[..., :-1], train[..., 1:], train[..., :features],
            test[:, :-1], test[:, 1:])


def fleet(config: dict, seed: int) -> dict:
    """``tokens`` and ``labels`` (K, N, S) int32 ids of the held slice,
    ``items`` (K, N, F) and a test set of sequences drawn like the first
    station's, on the device; ``model``, the program's model config."""
    d = config["data"]
    k = config["fleet"]["num_nodes"]
    dep = config["deployment"]
    vocab = config["vocab_size"]
    key = jax.random.fold_in(jax.random.PRNGKey(data.base_seed(seed)), 13)
    tokens, labels, items, tt, tl = _make(
        key, jnp.float32(d["token_zipf"]), jnp.float32(d["line_zipf"]),
        k=k, n=d["train_per_node"], n_test=max(1, k * d["test_per_node"]),
        seq=d["seq_len"], vocab=vocab, offset=dep["chip"] * vocab,
        lines=d["lines"], width=d["line_tokens"], features=d["features"])
    out = {"tokens": tokens, "labels": labels, "items": items,
           "test_tokens": tt, "test_labels": tl}
    jax.block_until_ready(out)
    return dict(out, model=model_config(config))


# -- the model -------------------------------------------------------------

def model(config: dict):
    """``(loss_fn, init_params)`` for ``Experiment.from_parts``: the loss
    returns ``(loss, counters)``, the held expert layer's route counts."""
    from repro.models import transformer
    mc = model_config(config)

    def loss_fn(params, batch):
        return transformer.loss_and_aux(params, mc, batch)
    return loss_fn, lambda rng: transformer.init_params(rng, mc)


def batch(inputs: dict) -> dict:
    return {"tokens": inputs["tokens"], "labels": inputs["labels"]}


def items(inputs: dict):
    return inputs["items"]


def eval_fn(inputs: dict):
    """Next-token accuracy of one station's parameters on the test set."""
    from repro.models import transformer
    tt, tl, mc = inputs["test_tokens"], inputs["test_labels"], inputs["model"]

    def fn(params):
        logits, _ = transformer.forward(params, mc, {"tokens": tt})
        return (logits.argmax(-1) + mc.vocab_offset == tl).mean()
    return fn


def leaves(config: dict) -> list:
    """``(name, elements)`` of every leaf in packed order: the program's
    parameter tree flattened, each name its path joined by ``/``."""
    from repro.models import transformer
    mc = model_config(config)
    shapes = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), mc))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path), int(np.prod(leaf.shape)))
            for path, leaf in flat]


def flops_per_sample(config: dict) -> int:
    """FLOPs of one training sample, one sequence of ``seq_len`` tokens,
    forward and backward (3 x the forward's 2 per multiply-add): per
    token, every held matmul (the held heads' q, k, v and o projections,
    the router over all experts, the output head over the held
    vocabulary), the routed experts at their expected share here
    (``num_experts_per_tok`` x held / all experts of the three SwiGLU
    matmuls: one expert a token for Mellum2's 8 of 64 at top-8), and per
    layer the attention scores and values of the held heads over the
    keys each position sees under its kind (all earlier positions in a
    full layer, at most ``sliding_window`` in a sliding one). The
    embedding lookup, norms and softmaxes are not counted."""
    mc = model_config(config)
    d, hd, f = mc.d_model, mc.resolved_head_dim(), mc.d_ff
    h, kv = mc.held_heads(), mc.held_kv_heads()
    s = config["data"]["seq_len"]
    share = mc.experts_per_token * mc.held_experts() / mc.num_experts
    per_token = mc.held_vocab() * d                       # output head
    for kind in mc.blocks():
        per_token += d * (2 * h + 2 * kv) * hd            # q, k, v, o
        per_token += d * mc.num_experts                   # router
        per_token += share * 3 * d * f                    # routed experts
        window = mc.attention_kind(kind).window or s
        span = sum(min(q + 1, window) for q in range(s)) / s
        per_token += 2 * h * hd * span                    # scores, values
    return int(round(6 * per_token * s))


def reference_inputs(inputs: dict) -> dict:
    return {"tokens": inputs["tokens"], "labels": inputs["labels"],
            "items": np.asarray(inputs["items"])}


def tiny(config: dict) -> dict:
    """``config`` cut in place to a CPU test's size, every kind of layer
    and the share kept: hidden 64, head_dim 16, 2 held of 4 query heads
    with 1 of 2 key/value heads, 4 held of 8 experts of width 32 at
    top-2, 32 held of 64 vocabulary rows, window 8, sequences of 32, 16
    a station."""
    config.update(hidden_size=64, head_dim=16, moe_intermediate_size=32,
                  num_attention_heads=2, num_key_value_heads=1,
                  num_experts=4, num_experts_per_tok=2, vocab_size=32,
                  sliding_window=8)
    config["deployment"] = dict(
        config["deployment"], chips_per_layer=2, chip=1,
        published=dict(num_hidden_layers=4, num_experts=8,
                       num_attention_heads=4, num_key_value_heads=2,
                       vocab_size=64))
    config["data"].update(train_per_node=16, seq_len=32, lines=8,
                          line_tokens=4, features=4)
    config["params"] = sum(n for _, n in leaves(config))
    return config
