"""Modality frontends — STUBS per spec.

[vlm] and [audio] architectures specify the transformer backbone only; the
ViT/SigLIP vision encoder and the EnCodec conv codec are NOT implemented.
These helpers generate concrete embeddings/tokens of the right shape for
smoke tests and examples.

musicgen note: real MusicGen decodes 4 interleaved EnCodec codebooks with a
delay pattern; per the assignment ("decoder-only over EnCodec tokens",
vocab 2048) we model the single-stream decoder and treat codebook
interleaving as part of the stubbed frontend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def vision_patch_embeddings(rng, cfg: ModelConfig, batch: int,
                            num_patches: int | None = None,
                            dtype=None) -> jax.Array:
    """Stand-in for InternViT + projector output: (B, P, d_model)."""
    p = num_patches or cfg.num_patches
    dtype = dtype or jnp.dtype(cfg.dtype)
    return (jax.random.normal(rng, (batch, p, cfg.d_model)) * 0.02
            ).astype(dtype)


def audio_codec_tokens(rng, cfg: ModelConfig, batch: int,
                       seq_len: int) -> jax.Array:
    """Stand-in for the EnCodec tokenizer output: (B, S) codes."""
    return jax.random.randint(rng, (batch, seq_len), 0, cfg.vocab_size,
                              dtype=jnp.int32)
