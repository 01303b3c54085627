"""Plain reference of C-DFL rounds with one chip's share of Mellum2
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct): the share
written in plain ``jax.numpy`` and float32 from the model's config and
the configuration's deployment, bound to the model-agnostic rounds of
``cdfl_rounds.py``. Imports nothing of the system under test.

The share: the first ``num_hidden_layers`` layers of ``layer_types``
(sliding layers attend to keys ``q - sliding_window < k <= q`` under
default RoPE; full layers are causal under YaRN, whose frequencies and
cos/sin scale are computed here from the published formula); over this
chip's query heads and their key/value heads, its rows of ``o_proj``
(the other chips' heads left out); RMSNorm (eps ``rms_norm_eps``) before
attention and experts and at the end; the router over all experts,
top-``num_experts_per_tok`` of its softmax renormalised; every held
expert computed densely over all tokens and weighted by its gate (zero
where the token did not pick it); the output head and the cross entropy
over the held vocabulary rows; plus ``router_aux_loss_coef`` times the
Switch load-balance term of each layer over all experts.

Departures from the published model: one pipeline stage's layers and one
chip's heads, experts and vocabulary rows, the others' parts left out as
the deployment states; no MTP head (the config gives no keys for it).
The initial weights follow the program's seeded initializer, key by key,
so both start from the same parameters.
"""
from __future__ import annotations

import math
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec

BATCH = ("tokens", "labels")            # (K, N, S) ids of the held slice
LAYERS = 4


def _layer_leaves(i: int) -> tuple:
    return tuple(f"layers_list/{i}/{name}" for name in (
        "ffn/router", "ffn/w_down", "ffn/w_gate", "ffn/w_up", "mix/wk",
        "mix/wo", "mix/wq", "mix/wv", "norm1/scale", "norm2/scale"))


# in the order the program packs them (a nested dict flattens sorted)
LEAVES = (("embed/table", "final_norm/scale")
          + sum((_layer_leaves(i) for i in range(LAYERS)), ())
          + ("lm_head/table",))


def _sizes(config: dict) -> dict:
    dep = config["deployment"]
    pub = dep["published"]
    heads = config["num_attention_heads"]
    return {
        "d": config["hidden_size"], "hd": config["head_dim"],
        "f": config["moe_intermediate_size"], "h": heads,
        "kv": config["num_key_value_heads"],
        "experts": pub["num_experts"], "held": config["num_experts"],
        "first": dep["chip"] * config["num_experts"],
        "top": config["num_experts_per_tok"],
        "vocab": config["vocab_size"],
        "vocab_first": dep["chip"] * config["vocab_size"],
    }


def init(key, config: dict, dtype):
    """The share's weights from ``key``: normal draws at the held shapes,
    each scaled by 1/sqrt(fan-in) (the embedding and head by 0.02), the
    norms at one, split from the key as the program's initializer
    splits it."""
    z = _sizes(config)
    d, hd, f = z["d"], z["hd"], z["f"]

    def dense(k, shape, scale=None):
        scale = shape[0] ** -0.5 if scale is None else scale
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    k_embed, k_layers, _, k_head = jax.random.split(key, 4)
    p = {"embed/table": dense(k_embed, (z["vocab"], d), 0.02),
         "final_norm/scale": jnp.ones((d,), dtype),
         "lm_head/table": dense(k_head, (z["vocab"], d), 0.02)}
    for i, k in enumerate(jax.random.split(k_layers, LAYERS)):
        k_mix, k_ffn = jax.random.split(k)
        km = jax.random.split(k_mix, 4)
        kf = jax.random.split(k_ffn, 4)
        n = f"layers_list/{i}/"
        p.update({
            n + "mix/wq": dense(km[0], (d, z["h"] * hd)),
            n + "mix/wk": dense(km[1], (d, z["kv"] * hd)),
            n + "mix/wv": dense(km[2], (d, z["kv"] * hd)),
            n + "mix/wo": dense(km[3], (z["h"] * hd, d)),
            n + "ffn/router": dense(kf[0], (d, z["experts"])),
            n + "ffn/w_gate": (jax.random.normal(kf[1], (z["held"], d, f))
                               * d ** -0.5).astype(dtype),
            n + "ffn/w_up": (jax.random.normal(kf[2], (z["held"], d, f))
                             * d ** -0.5).astype(dtype),
            n + "ffn/w_down": (jax.random.normal(kf[3], (z["held"], f, d))
                               * f ** -0.5).astype(dtype),
            n + "norm1/scale": jnp.ones((d,), dtype),
            n + "norm2/scale": jnp.ones((d,), dtype)})
    return p


def rope(config: dict, kind: str) -> tuple:
    """(inverse frequencies (head_dim/2,), cos/sin scale) of a layer
    kind, from ``rope_parameters``: YaRN as arXiv:2309.00071 and HF
    ``_compute_yarn_parameters`` give it."""
    r = config["rope_parameters"][kind]
    dim, base = config["head_dim"], r["rope_theta"]
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if r["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    orig = r["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(r["beta_fast"])), 0)
    high = min(math.ceil(corr(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    inv = inv / r["factor"] * (1 - extrapolation) + inv * extrapolation
    return inv.astype(np.float32), r["attention_factor"]


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate(x, inv, scale):
    """x (S, H, D) rotated by position; halves paired (rotate_half)."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * scale)[:, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, wq, wk, wv, wo, *, heads, kv_heads, head_dim, kind,
              config):
    """Causal GQA self-attention of one sequence h (S, d) over the given
    heads (``heads`` query heads sharing ``kv_heads``), with the window
    and rotary embedding of layer ``kind``: (S, d)."""
    s = h.shape[0]
    inv, scale = rope(config, kind)
    q = _rotate((h @ wq).reshape(s, heads, head_dim), inv, scale)
    k = _rotate((h @ wk).reshape(s, kv_heads, head_dim), inv, scale)
    v = (h @ wv).reshape(s, kv_heads, head_dim)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    seen = k_pos <= q_pos
    if kind == "sliding_attention":
        seen &= k_pos > q_pos - config["sliding_window"]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", probs.astype(h.dtype), v)
    return out.reshape(s, -1) @ wo


def experts(h, router, w_gate, w_up, w_down, *, top, first):
    """The part of the expert layer that experts ``first ..`` (the
    leading axis of the weights) give for tokens h (S, d): every one of
    them over all tokens, weighted by its renormalised gate where the
    token picked it among its ``top`` and by 0 elsewhere; with the
    router's probabilities (S, E) and each token's chosen experts as a
    count (S, E)."""
    probs = jax.nn.softmax(h @ router, -1)
    gate, pick = jax.lax.top_k(probs, top)
    gate = gate / gate.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(w_gate.shape[0]):
        w = (gate * (pick == first + e)).sum(-1)
        act = jax.nn.silu(h @ w_gate[e]) * (h @ w_up[e])
        out = out + w[:, None].astype(h.dtype) * (act @ w_down[e])
    chosen = jax.nn.one_hot(pick, probs.shape[-1], dtype=probs.dtype).sum(1)
    return out, probs, chosen


def forward(p, tokens, config):
    """One sequence of held-slice ids (S,): logits over the held slice
    (S, V_held), and each layer's router probabilities and chosen
    experts (L, S, E)."""
    z = _sizes(config)
    eps = config["rms_norm_eps"]
    x = p["embed/table"][tokens - z["vocab_first"]]
    routers, choices = [], []
    for i, kind in enumerate(config["layer_types"][:LAYERS]):
        n = f"layers_list/{i}/"
        x = x + attention(
            _rms(x, p[n + "norm1/scale"], eps), p[n + "mix/wq"],
            p[n + "mix/wk"], p[n + "mix/wv"], p[n + "mix/wo"],
            heads=z["h"], kv_heads=z["kv"], head_dim=z["hd"], kind=kind,
            config=config)
        out, probs, chosen = experts(
            _rms(x, p[n + "norm2/scale"], eps), p[n + "ffn/router"],
            p[n + "ffn/w_gate"], p[n + "ffn/w_up"], p[n + "ffn/w_down"],
            top=z["top"], first=z["first"])
        x = x + out
        routers.append(probs)
        choices.append(chosen)
    x = _rms(x, p["final_norm/scale"], eps)
    logits = (x @ p["lm_head/table"].T).astype(jnp.float32)
    return logits, jnp.stack(routers), jnp.stack(choices)


def _sequence(p, tokens, labels, config):
    """One sequence: each position's next-token loss, and each layer's
    router probabilities and chosen experts (L, S, E)."""
    logits, routers, choices = forward(p, tokens, config)
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    first = _sizes(config)["vocab_first"]
    nll = -jnp.take_along_axis(logp, (labels - first)[:, None], -1)[:, 0]
    return nll, routers, choices


def loss(p, batch, config: dict):
    """Mean next-token cross entropy over the batch's tokens, plus
    ``router_aux_loss_coef`` times each layer's Switch load-balance term
    over all experts, E x sum_e (share of routes to e) x (mean
    probability of e), the means over the batch's tokens."""
    nll, routers, choices = jax.vmap(
        lambda t, l_: _sequence(p, t, l_, config))(batch["tokens"],
                                                   batch["labels"])
    e = routers.shape[-1]
    balance = e * (choices.mean((0, 2)) * routers.mean((0, 2))).sum()
    return (nll.mean() + config["router_aux_loss_coef"]
            * balance.astype(nll.dtype))


def run(config: dict, traffic: dict, inputs: dict, calls: int,
        dtype=jnp.float32, plant: str | None = None) -> dict:
    """The first ``calls`` entry calls of the cell, in the reference: the
    rounds of ``cdfl_rounds`` (its CND weights, static ring schedule and
    ``_round``: eq. 5, then the local Adam steps) with this model, and
    its results (``loss``, ``m``, ``change``) as ``cdfl_rounds.run``
    gives them. Static graph, no faults, no ingest.

    ``cdfl_rounds.run`` keeps a round's input and output states and the
    round-entry parameters on the device together, which at 267 M
    parameters a station is 19.6 GB for a v5e's 16; here each round's
    state is donated to the next, the entry parameters are not kept,
    and the initial parameters wait on the host."""
    rounds = spec.module("reference", "cdfl_rounds")
    if inputs.get("faults") is not None or inputs.get("ingest") is not None:
        raise ValueError("the Mellum2 reference runs fault-free rounds "
                         "without ingest")
    if config["num_hidden_layers"] != LAYERS:
        raise ValueError(f"the reference holds {LAYERS} layers")
    train, fleet = config["train"], config["fleet"]
    data = {name: jnp.asarray(inputs[name]) for name in BATCH}
    k = data["tokens"].shape[0]
    rpc = traffic["rounds_per_call"]
    ratios = rounds.cnd_ratios(np.asarray(inputs["items"]),
                               fleet["cnd_hashes"], fleet["cnd_bits"])
    variants = inputs["variants"]
    etas, gammas = zip(*[rounds.mixing_schedule(
        fleet, v["mobility"], calls * rpc, ratios) for v in variants])
    etas, gammas = np.stack(etas), np.stack(gammas)
    shape = (k, fleet["local_steps"], train["batch_size"])
    body = partial(rounds._round, partial(loss, config=config), train,
                   shape, False, None, 0.0, plant)
    none = jnp.zeros((k, 1), jnp.int32)

    @partial(jax.jit, donate_argnums=(0,))
    def one_round(st, e, g, r, keys, data):
        def variant(s_, e_, g_, key):
            p, m, v, step, hll = s_
            (p, m, v, step, _, hll), l_ = body(
                (p, m, v, step, None, hll),
                (e_, g_, r, key, data, none, none, None, None))
            return (p, m, v, step, hll), l_
        return jax.vmap(variant)(st, e, g, keys)

    def start(seed):
        p = jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape),
                         init(jax.random.PRNGKey(seed), config, dtype))
        return (p, jax.tree.map(jnp.zeros_like, p),
                jax.tree.map(jnp.zeros_like, p),
                jnp.zeros((k,), jnp.int32), jnp.zeros((k, 1), jnp.int32))

    state = jax.tree.map(lambda *a: jnp.stack(a),
                         *[start(v["init_seed"]) for v in variants])
    p0 = jax.tree.map(np.asarray, state[0])
    keys = jnp.stack([jax.random.PRNGKey(v["sample_seed"])
                      for v in variants])
    out = {"loss": []}
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        for c in range(calls):
            losses = []
            for r in range(c * rpc, (c + 1) * rpc):
                state, l_ = one_round(
                    state, jnp.asarray(etas[:, r], dtype),
                    jnp.asarray(gammas[:, r], dtype), r, keys, data)
                losses.append(np.asarray(l_))
            out["loss"].append(np.stack(losses, axis=1))
            if c == 0:
                out["m"] = np.asarray(rounds._norms(state[1], LEAVES))
    out["change"] = np.stack([np.sqrt(np.sum(np.square(
        np.asarray(state[0][n], np.float64) - p0[n].astype(np.float64)
    ).reshape(p0[n].shape[:2] + (-1,)), axis=-1)) for n in LEAVES],
        axis=-1)
    return out
