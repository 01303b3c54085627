"""Decoder-only model assembled from a ModelConfig — covers every assigned
architecture: dense (llama/qwen-style), MoE (mixtral/dbrx), SSM (rwkv6),
hybrid (zamba2 mamba + shared attention), VLM and audio backbones.

Homogeneous stacks are **layer-scanned** (stacked layer params + lax.scan):
one block's HLO instead of L copies — smaller programs, faster compiles,
and the natural remat boundary. Heterogeneous stacks (zamba2) use a python
loop with true parameter sharing for the shared attention block.

API:
  init_params(rng, cfg)               -> params pytree
  forward(params, cfg, batch, ...)    -> (logits, aux_loss)
  loss_fn(params, cfg, batch)         -> scalar
  loss_and_aux(params, cfg, batch)    -> (scalar, counters)
  init_decode(cfg, batch, max_len)    -> DecodeState
  decode_step(params, cfg, state, tk) -> (logits, DecodeState)
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, layers, mamba, moe, pspec, rwkv


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _is_homogeneous(cfg: ModelConfig) -> bool:
    return len(set(cfg.blocks())) == 1


def _precision(cfg: ModelConfig):
    """The matmul precision the forward is traced under."""
    return (jax.default_matmul_precision(cfg.matmul_precision)
            if cfg.matmul_precision else contextlib.nullcontext())


# --------------------------------------------------------------------------
# Block init / apply
# --------------------------------------------------------------------------

def _block_init(rng, cfg: ModelConfig, kind: str, dtype,
                with_mix: bool = True):
    norm_init, _ = layers.make_norm(cfg.norm)
    r_mix, r_ffn = jax.random.split(rng)
    p = {"norm1": norm_init(cfg.d_model, dtype),
         "norm2": norm_init(cfg.d_model, dtype)}
    if with_mix:
        if kind == "attn" or cfg.attention_kind(kind) is not None:
            p["mix"] = attention.init(r_mix, cfg, dtype)
        elif kind == "rwkv":
            p["mix"] = rwkv.init(r_mix, cfg, dtype)
        elif kind == "mamba":
            p["mix"] = mamba.init(r_mix, cfg, dtype)
    if cfg.num_experts:
        p["ffn"] = moe.init(r_ffn, cfg, dtype)
    else:
        mlp_init, _ = layers.make_mlp(cfg.act)
        p["ffn"] = mlp_init(r_ffn, cfg.d_model, cfg.d_ff, dtype)
    return p


def _apply_ffn(p, cfg: ModelConfig, x, decode: bool, group_size: int):
    """Returns (out, aux, counters); only the held-expert layer counts."""
    if cfg.experts_held:
        return moe.held_forward(p["ffn"], cfg, x)
    if cfg.num_experts:
        if decode:
            return moe.decode_forward(p["ffn"], cfg, x) + ({},)
        return moe.forward(p["ffn"], cfg, x, group_size) + ({},)
    _, mlp_fn = layers.make_mlp(cfg.act)
    return mlp_fn(p["ffn"], x), jnp.float32(0.0), {}


def _add(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}


def _apply_block(p, cfg: ModelConfig, kind: str, x, *, shared=None,
                 state=None, decode: bool = False,
                 window_override=None, group_size: int = 2048):
    """Returns (x, aux, new_state, counters)."""
    _, norm_fn = layers.make_norm(cfg.norm)
    mix_params = shared if shared is not None else p["mix"]
    x = pspec.constrain(x, "batch", None, None)
    h = pspec.constrain(norm_fn(p["norm1"], x), "batch", None, None)
    akind = cfg.attention_kind(kind)
    if kind in ("attn", "shared_attn") or akind is not None:
        if decode:
            mix_out, new_state = attention.decode_step(
                mix_params, cfg, h, state, window_override, kind=akind)
        else:
            with jax.named_scope("cdfl.attn"):
                mix_out = attention.forward(mix_params, cfg, h,
                                            window_override=window_override,
                                            kind=akind)
            new_state = state
    elif kind == "rwkv":
        mix_out, new_state = rwkv.forward(mix_params, cfg, h, state)
    elif kind == "mamba":
        mix_out, new_state = mamba.forward(mix_params, cfg, h, state)
    else:
        raise ValueError(kind)
    # pin the residual stream to batch-only sharding: matmul outputs whose
    # weights are tp-sharded on d_out would otherwise leave x d-sharded and
    # every downstream op all-gathers the f32-cast residual (dry-run:
    # 167GB/step for qwen3 before this constraint).
    x = x + pspec.constrain(mix_out, "batch", None, None)
    h = pspec.constrain(norm_fn(p["norm2"], x), "batch", None, None)
    ffn_out, aux, counters = _apply_ffn(p, cfg, h, decode, group_size)
    x = x + pspec.constrain(ffn_out, "batch", None, None)
    return x, aux, new_state, counters


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------

def init_params(rng, cfg: ModelConfig, dtype=None):
    dtype = dtype or _dtype(cfg)
    kinds = cfg.blocks()
    r_embed, r_layers, r_shared, r_head = jax.random.split(rng, 4)
    norm_init, _ = layers.make_norm(cfg.norm)
    params = {
        "embed": layers.embedding_init(r_embed, cfg.held_vocab(),
                                       cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "table": layers._dense_init(r_head, (cfg.held_vocab(),
                                                 cfg.d_model),
                                        scale=0.02, dtype=dtype)}
    if _is_homogeneous(cfg):
        kind = kinds[0]
        rs = jax.random.split(r_layers, cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda r: _block_init(r, cfg, kind, dtype))(rs)
    else:
        rs = jax.random.split(r_layers, cfg.num_layers)
        params["layers_list"] = [
            _block_init(rs[i], cfg, kinds[i], dtype,
                        with_mix=(kinds[i] != "shared_attn"))
            for i in range(cfg.num_layers)
        ]
        if "shared_attn" in kinds:
            params["shared_attn"] = attention.init(r_shared, cfg, dtype)
    return params


# --------------------------------------------------------------------------
# Forward (train / prefill)
# --------------------------------------------------------------------------

def _held_ids(cfg: ModelConfig, ids):
    """Token ids as rows of the held vocabulary slice."""
    return ids - cfg.vocab_offset if cfg.vocab_offset else ids


def forward(params, cfg: ModelConfig, batch: dict, **kw):
    """batch: {"tokens": (B, S) int32} (+ "embeds": (B, P, d) for VLM).
    Returns (logits (B, S_out, V) f32, aux scalar). last_only: unembed only
    the final position (prefill serving — avoids the (B,S,V) logits).
    Over a held vocabulary slice the tokens are ids of the slice and the
    logits are over the slice."""
    with _precision(cfg):
        logits, aux, _ = _forward(params, cfg, batch, **kw)
    return logits, aux


def _forward(params, cfg: ModelConfig, batch: dict, *,
             window_override=None, group_size: int = 2048,
             remat: bool = False, last_only: bool = False,
             unroll: bool = False):
    """``forward``, and the counters of its layers summed over them."""
    tokens = _held_ids(cfg, batch["tokens"])
    x = layers.embed(params["embed"], tokens).astype(_dtype(cfg))
    x = pspec.constrain(x, "batch", None, None)
    n_text = tokens.shape[1]
    if cfg.modality == "vision" and "embeds" in batch:
        x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
    kinds = cfg.blocks()

    if _is_homogeneous(cfg):
        kind = kinds[0]

        def body(carry, layer_p):
            h, aux, counts = carry
            h, a, _, c = _apply_block(layer_p, cfg, kind, h,
                                      window_override=window_override,
                                      group_size=group_size)
            return (h, aux + a, _add(counts, c)), None

        if remat:
            body = jax.checkpoint(body)
        counts0 = ({name: jnp.int32(0) for name in moe.COUNTERS}
                   if cfg.experts_held else {})
        # unroll=True: straight-line HLO so cost_analysis counts every
        # layer (XLA while-loop bodies are costed ONCE) — dry-run only.
        (x, aux, counts), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0), counts0), params["layers"],
            unroll=cfg.num_layers if unroll else 1)
    else:
        aux, counts = jnp.float32(0.0), {}
        for i, kind in enumerate(kinds):
            shared = params.get("shared_attn") if kind == "shared_attn" \
                else None

            def blk(p, h, sh, kind=kind):
                out, a_, _, c_ = _apply_block(
                    p, cfg, kind, h, shared=sh,
                    window_override=window_override,
                    group_size=group_size)
                return out, a_, c_

            fn = jax.checkpoint(blk) if remat else blk
            x, a, c = fn(params["layers_list"][i], x, shared)
            aux, counts = aux + a, _add(counts, c)

    _, norm_fn = layers.make_norm(cfg.norm)
    x = norm_fn(params["final_norm"], x)
    if cfg.modality == "vision" and "embeds" in batch:
        x = x[:, -n_text:, :]                      # loss on text positions
    if last_only:
        x = x[:, -1:, :]
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.unembed(head, x)
    logits = pspec.constrain(logits, "batch", None, "vocab")
    return logits, aux, counts


def loss_fn(params, cfg: ModelConfig, batch: dict, **kw):
    """Next-token cross entropy plus the router's load-balance term
    (``loss_and_aux`` without its counters)."""
    return loss_and_aux(params, cfg, batch, **kw)[0]


def loss_and_aux(params, cfg: ModelConfig, batch: dict, **kw):
    """Next-token cross entropy (labels provided by the data pipeline).

    Formulated as logsumexp - selected-logit (one-hot contraction): both
    reduce over the vocab dim locally and combine with a tiny all-reduce,
    so vocab-sharded logits are never all-gathered (take_along_axis on the
    sharded dim would gather the full (B,S,V) logits — the dry-run showed
    that costing ~400GB/step of wire traffic).

    Returns (loss, counters): the layers' counters summed (the held
    expert layer's ``moe/routes`` and ``moe/routes_held``; none for
    other models). Over a held vocabulary slice the labels are ids of
    the slice and the cross entropy is over it."""
    with _precision(cfg):
        logits, aux, counts = _forward(params, cfg, batch, **kw)
    labels = _held_ids(cfg, batch["labels"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    picked = jnp.sum(logits * onehot, axis=-1)
    nll = lse - picked
    mask = batch.get("mask")
    if mask is not None:
        loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    else:
        loss = nll.mean()
    return loss + cfg.router_aux_coef * aux, counts


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

class DecodeState(NamedTuple):
    states: object          # per-layer mix states (stacked or list)
    pos: jax.Array


def _layer_state(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 dtype, window):
    if kind in ("attn", "shared_attn"):
        return attention.init_cache(cfg, batch, max_len, dtype, window)
    akind = cfg.attention_kind(kind)
    if akind is not None:
        return attention.init_cache(
            cfg, batch, max_len, dtype,
            akind.window if window is None else window)
    if kind == "rwkv":
        return rwkv.init_state(cfg, batch)
    if kind == "mamba":
        return mamba.init_state(cfg, batch)
    raise ValueError(kind)


def init_decode(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None, window_override=None) -> DecodeState:
    dtype = dtype or _dtype(cfg)
    window = window_override if window_override is not None \
        else cfg.sliding_window
    kinds = cfg.blocks()
    if cfg.attention_kinds:
        window = window_override       # each kind's own window otherwise
    if _is_homogeneous(cfg):
        one = _layer_state(cfg, kinds[0], batch, max_len, dtype, window)
        states = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (cfg.num_layers,) + l.shape).copy(),
            one)
    else:
        states = [_layer_state(cfg, k, batch, max_len, dtype, window)
                  for k in kinds]
    return DecodeState(states=states, pos=jnp.zeros((), jnp.int32))


def decode_step(params, cfg: ModelConfig, state: DecodeState,
                tokens: jax.Array, *, window_override=None,
                unroll: bool = False):
    """tokens: (B,) int32 — one new token per sequence.
    Returns (logits (B, V) f32, new DecodeState)."""
    x = layers.embed(params["embed"], _held_ids(cfg, tokens)[:, None]
                     ).astype(_dtype(cfg))
    kinds = cfg.blocks()

    if _is_homogeneous(cfg):
        kind = kinds[0]

        def body(h, xs):
            layer_p, st = xs
            h, _, new_st, _ = _apply_block(layer_p, cfg, kind, h, state=st,
                                        decode=True,
                                        window_override=window_override)
            return h, new_st

        x, new_states = jax.lax.scan(body, x,
                                     (params["layers"], state.states),
                                     unroll=cfg.num_layers if unroll else 1)
    else:
        new_states = []
        for i, kind in enumerate(kinds):
            shared = params.get("shared_attn") if kind == "shared_attn" \
                else None
            x, _, st, _ = _apply_block(params["layers_list"][i], cfg, kind,
                                       x,
                                    shared=shared, state=state.states[i],
                                    decode=True,
                                    window_override=window_override)
            new_states.append(st)

    _, norm_fn = layers.make_norm(cfg.norm)
    x = norm_fn(params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.unembed(head, x)[:, 0, :]
    return logits, DecodeState(states=new_states, pos=state.pos + 1)
