"""Grouped-query attention with qk-norm, RoPE, causal + sliding-window
masking; train/prefill forward and single-token decode with a KV cache.

The jnp path here is the reference; the Pallas flash kernel
(repro.kernels.flash_attention) is numerically validated against
``attend`` and swapped in via ``use_flash`` on TPU.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, pspec


class KVCache(NamedTuple):
    k: jax.Array          # (B, S_cache, KV, D)
    v: jax.Array
    length: jax.Array     # int32 — tokens currently in cache


def init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """The held heads' projections: ``wq``/``wk``/``wv`` columns and
    ``wo`` rows of this chip's query heads and their key/value heads (all
    of them unless the config holds a share)."""
    hd = cfg.resolved_head_dim()
    h, kv = cfg.held_heads(), cfg.held_kv_heads()
    r = jax.random.split(rng, 4)
    p = {
        "wq": layers._dense_init(r[0], (cfg.d_model, h * hd), dtype=dtype),
        "wk": layers._dense_init(r[1], (cfg.d_model, kv * hd), dtype=dtype),
        "wv": layers._dense_init(r[2], (cfg.d_model, kv * hd), dtype=dtype),
        "wo": layers._dense_init(r[3], (h * hd, cfg.d_model), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.rmsnorm_init(hd, dtype)
        p["k_norm"] = layers.rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params, cfg: ModelConfig, x, positions, kind=None):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q = (x @ params["wq"]).reshape(b, s, cfg.held_heads(), hd)
    k = (x @ params["wk"]).reshape(b, s, cfg.held_kv_heads(), hd)
    v = (x @ params["wv"]).reshape(b, s, cfg.held_kv_heads(), hd)
    if cfg.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
        k = layers.rmsnorm(params["k_norm"], k)
    freqs, scale = (None, 1.0) if kind is None else layers.rope_table(
        hd, kind.rope)
    q = layers.apply_rope(q, positions, cfg.rope_theta, freqs, scale)
    k = layers.apply_rope(k, positions, cfg.rope_theta, freqs, scale)
    return q, k, v


def _window(cfg: ModelConfig, kind, window_override):
    if window_override is not None:
        return window_override
    return cfg.sliding_window if kind is None else kind.window


def attend(q, k, v, *, causal: bool, window: Optional[int],
           q_offset: jax.Array | int = 0) -> jax.Array:
    """Reference GQA attention.

    q: (B, Sq, H, D); k/v: (B, Sk, KV, D). H % KV == 0.
    q_offset: absolute position of q[0] relative to k[0] (decode: cache len).
    window: sliding-window size (keys within [pos-window+1, pos]).

    GQA is realized by repeating kv heads to H — the flat 4-D einsums are
    what GSPMD partitions cleanly over the head axis (the grouped 5-D form
    triggers involuntary resharding; see models/pspec.py).
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    groups = h // kv
    if groups > 1:
        # row-parallel wk/wv leave k/v replicated across tp; the repeat is
        # then a free local broadcast (no constraint — forcing heads->tp
        # here made GSPMD reshard batch->d, costing 33.8GB/step on qwen3)
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32) \
        / jnp.sqrt(d).astype(jnp.float32)
    scores = pspec.constrain(scores, "batch", "heads", None, None)
    qpos = jnp.arange(sq) + q_offset                    # absolute q positions
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
    return pspec.constrain(out, "batch", None, "heads", None)


def forward(params, cfg: ModelConfig, x, positions=None,
            window_override: Optional[int] = None, kind=None):
    """Training / prefill self-attention over the full sequence, with the
    window and rotary embedding of ``kind`` (a ``configs.base.
    AttentionKind``; None: the model-wide ``sliding_window`` and
    ``rope_theta``). Over held heads this is the chip's partial output:
    its heads' rows of ``wo``, the other chips' heads left out."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, cfg, x, positions, kind)
    out = attend(q, k, v, causal=True,
                 window=_window(cfg, kind, window_override))
    return out.reshape(b, s, params["wo"].shape[0]) @ params["wo"]


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32, window: Optional[int] = None) -> KVCache:
    """window: cap the cache to the sliding window (ring buffer)."""
    eff = min(max_len, window) if window else max_len
    hd = cfg.resolved_head_dim()
    shape = (batch, eff, cfg.held_kv_heads(), hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))


def decode_step(params, cfg: ModelConfig, x, cache: KVCache,
                window_override: Optional[int] = None, kind=None):
    """One-token decode: x (B, 1, d_model); returns (out, new_cache).

    The cache is a ring buffer of size S_cache; with a sliding window the
    buffer equals the window so positions wrap (long_500k path).
    """
    b = x.shape[0]
    s_cache = cache.k.shape[1]
    pos = jnp.broadcast_to(cache.length, (b, 1))
    q, k, v = _project_qkv(params, cfg, x, pos, kind)
    slot = cache.length % s_cache
    new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, k, slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache.v, v, slot, axis=1)
    window = _window(cfg, kind, window_override)

    # attention over the valid region of the ring buffer.
    # Grouped-query einsums (NO kv repeat): the cache is usually
    # seq-sharded on the mesh (kv_heads < tp); the grouped form keeps the
    # scores seq-sharded so the softmax/out reduce with tiny all-reduces —
    # repeating kv heads made GSPMD all-gather the full 2GB cache per
    # layer (dry-run: 60GB/step on qwen3 decode_32k).
    hd = q.shape[-1]
    kv = cfg.held_kv_heads()
    groups = cfg.held_heads() // kv
    qg = q.reshape(b, 1, kv, groups, hd)
    # bf16 operands, f32 accumulation (MXU-native) — casting the cache to
    # f32 first would double the HBM bytes of the dominant decode read
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, new_k,
                        preferred_element_type=jnp.float32) \
        / jnp.sqrt(hd).astype(jnp.float32)               # (b,kv,g,1,S)
    # slot indices -> absolute positions in the ring buffer
    idx = jnp.arange(s_cache)
    wraps = cache.length >= s_cache
    abs_pos = jnp.where(
        wraps,
        jnp.where(idx <= slot, cache.length - slot + idx,
                  cache.length - slot - s_cache + idx),
        idx)
    valid = abs_pos <= cache.length
    if window is not None:
        valid &= abs_pos > cache.length - window
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(new_v.dtype), new_v)
    out = out.reshape(b, 1, -1) @ params["wo"]
    return out, KVCache(k=new_k, v=new_v, length=cache.length + 1)
