"""Mixture-of-Experts layer: top-k router + grouped capacity dispatch.

TPU-native formulation (MaxText/Mesh-TF style): tokens are routed with a
dense one-hot dispatch einsum under a per-group capacity bound, so all
shapes are static and the expert matmuls hit the MXU. Groups bound the
dispatch tensor to (group, E, capacity) — without grouping the dispatch
mask is quadratic in sequence length.

Expert weights are stacked on a leading E dim -> shardable over the mesh
('expert parallel'); token dispatch across expert shards lowers to
all-to-all in the dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers, pspec

# what the held-expert layer counts (``held_forward``)
COUNTERS = ("moe/routes", "moe/routes_held")


def init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """The router over all experts, and the held experts' weights (all
    of them unless the config holds a share)."""
    e, d, f = cfg.held_experts(), cfg.d_model, cfg.d_ff
    r = jax.random.split(rng, 4)
    scale = d ** -0.5
    return {
        "router": layers._dense_init(r[0], (d, cfg.num_experts),
                                     dtype=jnp.float32),
        "w_gate": (jax.random.normal(r[1], (e, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(r[2], (e, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(r[3], (e, f, d))
                   * f ** -0.5).astype(dtype),
    }


def _capacity(group_size: int, num_experts: int, top_k: int,
              factor: float) -> int:
    cap = int(group_size * top_k * factor / num_experts)
    return max(cap, top_k)


def forward(params, cfg: ModelConfig, x, group_size: int = 2048):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Gather-based dispatch: tokens are placed into a static (E, C) slot
    table (scatter of indices, then gathers) instead of the classic
    one-hot dispatch einsum, whose T*E*C*d flops dwarf the expert matmuls
    at long sequence lengths. All shapes static; overflow tokens beyond
    an expert's capacity are dropped (Switch semantics)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    gs = min(group_size, t)
    assert t % gs == 0, f"tokens {t} not divisible by group {gs}"
    g = t // gs
    xg = tokens.reshape(g, gs, d)
    cap = _capacity(gs, e, k, cfg.capacity_factor)

    xg = pspec.constrain(xg, "batch", None, None)   # groups follow batch
    logits = xg.astype(jnp.float32) @ params["router"]       # (g, gs, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                  # (g, gs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)               # renormalize

    # position of each (token, choice) within its expert's capacity queue;
    # priority: choice rank first, then token order (Switch-style).
    mask = jax.nn.one_hot(idx, e, dtype=jnp.float32)          # (g, gs, k, E)
    mask_r = mask.transpose(0, 2, 1, 3).reshape(g, k * gs, e)
    pos = (jnp.cumsum(mask_r, axis=1) - 1.0).reshape(
        g, k, gs, e).transpose(0, 2, 1, 3)                    # (g, gs, k, E)
    pos = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)      # (g, gs, k)
    keep = pos < cap

    # slot table: token index per (expert, capacity slot); sentinel gs
    # points at a zero pad row. Overflow writes land in slot C (sliced off).
    slot = jnp.where(keep, pos, cap)                          # (g, gs, k)
    lin = idx * (cap + 1) + slot                              # (g, gs, k)
    g_idx = jnp.arange(g)[:, None, None]
    tok_ids = jnp.broadcast_to(jnp.arange(gs)[None, :, None], (g, gs, k))
    table = jnp.full((g, e * (cap + 1)), gs, jnp.int32)
    table = table.at[g_idx, lin].set(tok_ids, mode="drop")
    table = table.reshape(g, e, cap + 1)[..., :cap]           # (g, E, C)

    xpad = jnp.concatenate([xg, jnp.zeros((g, 1, d), x.dtype)], axis=1)
    xin = xpad[jnp.arange(g)[:, None, None], table]           # (g, E, C, d)
    # dispatch/expert tensors stay sharded on the group dim (groups are
    # batch-major, so this follows the dp token sharding); without these
    # pins GSPMD replicates the full (g,E,C,d) dispatch tensor on every
    # device and all-reduces it (dry-run: 64GB/layer/device on dbrx).
    xin = pspec.constrain(xin, "batch", None, None, None)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, params["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", xin, params["w_up"])
    h = pspec.constrain(h, "batch", None, None, "ffn")
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    expert_out = pspec.constrain(expert_out, "batch", None, None, None)

    # combine: gather each token's k expert outputs, gate-weight, sum
    eo = expert_out.reshape(g, e * cap, d)
    lin2 = jnp.minimum(idx * cap + pos, e * cap - 1)          # (g, gs, k)
    gathered = eo[jnp.arange(g)[:, None, None], lin2]         # (g, gs, k, d)
    w = (gate_vals * keep).astype(x.dtype)
    out = jnp.einsum("gsk,gskd->gsd", w, gathered)
    out = pspec.constrain(out, "batch", None, None)

    # Switch load-balance auxiliary loss: E * sum_e f_e * P_e
    frac_dispatched = mask.sum(axis=2).mean(axis=1)           # (g, E)
    mean_prob = probs.mean(axis=1)                            # (g, E)
    aux = (e * (frac_dispatched * mean_prob).sum(-1)).mean()

    return out.reshape(b, s, d), aux


def decode_forward(params, cfg: ModelConfig, x):
    """Decode path: few tokens (B, 1, d) — dense gather-free top-k without
    capacity (every token gets its k experts; no dropping)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    tokens = x.reshape(-1, d)
    logits = tokens.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    sel = jax.nn.one_hot(idx, e, dtype=jnp.float32)           # (T, k, E)
    w = (sel * gate_vals[..., None]).sum(axis=1)              # (T, E)
    # compute all experts on the (few) decode tokens, weight-combine
    h = jax.nn.silu(jnp.einsum("td,edf->tef", tokens, params["w_gate"]))
    h = h * jnp.einsum("td,edf->tef", tokens, params["w_up"])
    eo = jnp.einsum("tef,efd->ted", h, params["w_down"])
    out = jnp.einsum("te,ted->td", w.astype(x.dtype), eo)
    return out.reshape(b, s, d), jnp.float32(0.0)


def held_forward(params, cfg: ModelConfig, x):
    """The expert layer of a chip that holds experts
    ``[expert_offset, expert_offset + experts_held)``: x (B, S, d) ->
    (this chip's part of the output (B, S, d), load-balance loss,
    counters).

    The router scores all ``num_experts`` in float32 at the highest
    precision (near-ties in the top-k would otherwise pick other experts
    than a float32 reference does), keeps each token's top
    ``experts_per_token`` and renormalises their probabilities. Every
    route to a held expert is computed, none dropped: the routes are
    sorted by held expert (the others after them) and the SwiGLU
    experts run as grouped matmuls over the held experts only
    (``ops.grouped_matmul``, a Pallas kernel on the TPU, whose products
    and their gradients take ``cfg.matmul_precision``). Each token
    then sums its held experts' outputs weighted by their gates; what
    the absent experts would add is the other chips' part. The
    load-balance loss is the Switch term over all experts. Counters:
    ``moe/routes`` the token-choice routes made, ``moe/routes_held``
    those computed here."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    g, first = cfg.held_experts(), cfg.expert_offset
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    with jax.named_scope("cdfl.moe.route"):
        logits = jnp.dot(tokens.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)                   # (T, k)
        gate = gate / gate.sum(-1, keepdims=True)
        held = (idx >= first) & (idx < first + g)
        # held routes by expert, then the absent ones (bucket g); a
        # route's rank is its row in that order
        key = jnp.where(held, idx - first, g).reshape(-1)     # (T k,)
        onehot = jax.nn.one_hot(key, g + 1, dtype=jnp.int32)
        sizes = onehot.sum(0)
        starts = jnp.cumsum(sizes) - sizes
        rank = (starts[key]
                + (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1)
        order = jnp.zeros((t * k,), jnp.int32).at[rank].set(
            jnp.arange(t * k, dtype=jnp.int32))
        routed = tokens[order // k]                           # (T k, d)
    with jax.named_scope("cdfl.moe.experts"):
        sizes = sizes[:g]
        prec = (jax.lax.Precision(cfg.matmul_precision)
                if cfg.matmul_precision else None)
        h = jax.nn.silu(ops.grouped_matmul(routed, params["w_gate"], sizes,
                                           prec))
        h = h * ops.grouped_matmul(routed, params["w_up"], sizes, prec)
        expert_out = ops.grouped_matmul(h, params["w_down"], sizes, prec)
    with jax.named_scope("cdfl.moe.combine"):
        per_route = expert_out[rank].reshape(t, k, d)
        w = jnp.where(held, gate, 0.0).astype(x.dtype)
        out = jnp.einsum("tk,tkd->td", w, per_route)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)   # (T, E)
    aux = e * (chosen.mean(0) * probs.mean(0)).sum()
    counters = {"moe/routes": jnp.int32(t * k),
                "moe/routes_held": sizes.sum()}
    return out.reshape(b, s, d), aux, counters
