"""Shared NN layers: norms, rotary embeddings, gated MLPs, embeddings.

Params are plain nested dicts of jnp arrays; init functions take an rng and
return the dict. All matmuls keep a ``dtype`` for activations while params
may be stored in bf16 (configs) or f32 (tests / paper repro).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import pspec


def _dense_init(rng, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


# --- norms ----------------------------------------------------------------

def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * params["scale"].astype(jnp.float32) \
        + params["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def make_norm(kind: str):
    if kind == "rmsnorm":
        return rmsnorm_init, rmsnorm
    if kind == "layernorm":
        return layernorm_init, layernorm
    raise ValueError(kind)


# --- rotary ----------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def yarn_frequencies(head_dim: int, rope) -> jax.Array:
    """YaRN's inverse frequencies (arXiv:2309.00071, as HF
    ``_compute_yarn_parameters`` computes them): dims that turn more than
    ``beta_fast`` times within ``original_max_positions`` keep their
    frequency, dims that turn fewer than ``beta_slow`` times take it over
    ``factor``, and a linear ramp blends the dims between."""
    def correction_dim(rotations):
        return (head_dim * math.log(rope.original_max_positions
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))
    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolated = rope_frequencies(head_dim, rope.theta)
    interpolated = extrapolated / rope.factor
    keep = 1.0 - ramp
    return interpolated * (1.0 - keep) + extrapolated * keep


def rope_table(head_dim: int, rope) -> tuple:
    """(inverse frequencies (D/2,), cos/sin scale) of a ``RopeConfig``."""
    if rope.kind == "default":
        return rope_frequencies(head_dim, rope.theta), 1.0
    if rope.kind == "yarn":
        return yarn_frequencies(head_dim, rope), rope.attention_factor
    raise ValueError(f"unknown rope kind {rope.kind!r}")


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: jax.Array | None = None, scale: float = 1.0):
    """x: (..., S, H, D); positions: broadcastable to (..., S). ``freqs``
    (D/2,) replaces the default frequencies of ``theta``; ``scale``
    multiplies cos and sin (YaRN's attention factor)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(d, theta)                  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- MLPs -------------------------------------------------------------------

def swiglu_init(rng, d_model, d_ff, dtype=jnp.float32):
    r1, r2, r3 = jax.random.split(rng, 3)
    return {
        "w_gate": _dense_init(r1, (d_model, d_ff), dtype=dtype),
        "w_up": _dense_init(r2, (d_model, d_ff), dtype=dtype),
        "w_down": _dense_init(r3, (d_ff, d_model), dtype=dtype),
    }


def swiglu(params, x):
    gate = jax.nn.silu(x @ params["w_gate"])
    gate = pspec.constrain(gate, *( (None,) * (gate.ndim - 1) ), "ffn")
    return (gate * (x @ params["w_up"])) @ params["w_down"]


def gelu_mlp_init(rng, d_model, d_ff, dtype=jnp.float32):
    r1, r2 = jax.random.split(rng)
    return {
        "w_up": _dense_init(r1, (d_model, d_ff), dtype=dtype),
        "w_down": _dense_init(r2, (d_ff, d_model), dtype=dtype),
    }


def gelu_mlp(params, x):
    h = jax.nn.gelu(x @ params["w_up"])
    h = pspec.constrain(h, *((None,) * (h.ndim - 1)), "ffn")
    return h @ params["w_down"]


def make_mlp(kind: str):
    if kind == "swiglu":
        return swiglu_init, swiglu
    if kind == "gelu":
        return gelu_mlp_init, gelu_mlp
    raise ValueError(kind)


# --- embeddings --------------------------------------------------------------

def embedding_init(rng, vocab, d_model, dtype=jnp.float32):
    return {"table": _dense_init(rng, (vocab, d_model), scale=0.02,
                                 dtype=dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    """Logits in f32 (loss stability)."""
    return x.astype(jnp.float32) @ params["table"].astype(jnp.float32).T
