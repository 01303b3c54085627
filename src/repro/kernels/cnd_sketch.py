"""Pallas TPU kernel: CND sketch build (paper Algorithm 1).

The paper's hot loop — hash every item, set Bitmap[hash] = 1 — is a
pointer-chasing scatter on CPU/GPU. TPUs have no scatter unit, so the
TPU-native rewrite is:

  * hashing: xxhash-style integer avalanche, vectorized across the 8x128
    VPU lanes (a block of items is hashed simultaneously);
  * bitmap update: for each 32-bit bitmap word, an OR-reduction of the
    items' one-hot contributions (compare + shift + reduce), tiled so the
    (block_items x words) compare matrix stays in VMEM.

Each grid step folds its item block into a ``(num_hashes, 8, m/32)``
partial-OR accumulator (the output block, resident in VMEM across the
sequential item-block grid dimension): the block's rows are OR-ed one
8-row tile at a time, and the last 8 -> 1 fold runs in XLA after the
kernel. Everything in the body is tile-aligned 2-D VPU work — Pallas
TPU has no lowering for a general ``lax.reduce`` with ``bitwise_or``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sketch import _mix32

SUBLANES = 8


def _kernel(items_ref, out_ref, *, num_hashes: int, m: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    items = items_ref[...].astype(jnp.uint32)            # (blk, f)
    blk, f = items.shape
    words = m // 32
    wid = jax.lax.broadcasted_iota(jnp.int32, (blk, words), 1)
    for s in range(num_hashes):
        # rolling fold over the item's feature tokens (Alg. 1 hash(item)),
        # kept (blk, 1) so every op stays a 2-D vector op
        h = jnp.zeros((blk, 1), jnp.uint32)
        for j in range(f):
            h = _mix32(h * jnp.uint32(31) + items[:, j:j + 1], s + j)
        h = _mix32(h, 101 + s)
        idx = h & jnp.uint32(m - 1) if m & (m - 1) == 0 else h % m
        word = (idx >> 5).astype(jnp.int32)
        bit = idx & jnp.uint32(31)
        vals = jnp.where(word == wid, jnp.uint32(1) << bit,
                         jnp.uint32(0))                   # (blk, W)
        part = vals[:SUBLANES]
        for t in range(SUBLANES, blk, SUBLANES):
            part = part | vals[t:t + SUBLANES]
        out_ref[s] = out_ref[s] | part


def cnd_bitmaps(items: jax.Array, num_hashes: int = 3, m: int = 8192,
                *, block_items: int = 256,
                interpret: bool = False) -> jax.Array:
    """items: (n, f) int32 feature tokens -> (num_hashes, m//32) uint32.

    n is padded to a multiple of the item block (itself a multiple of 8)
    by repeating row 0 (idempotent for a bitmap: duplicates OR the same
    bit)."""
    assert m % 32 == 0 and block_items % SUBLANES == 0, (m, block_items)
    n, f = items.shape
    blk = min(block_items, -(-n // SUBLANES) * SUBLANES)
    pad = (-n) % blk
    if pad:
        items = jnp.concatenate(
            [items, jnp.broadcast_to(items[:1], (pad, f))], axis=0)
    partial_or = pl.pallas_call(
        functools.partial(_kernel, num_hashes=num_hashes, m=m),
        grid=(items.shape[0] // blk,),
        in_specs=[pl.BlockSpec((blk, f), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((num_hashes, SUBLANES, m // 32),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_hashes, SUBLANES, m // 32),
                                       jnp.uint32),
        interpret=interpret,
        name="cnd_bitmaps",
    )(items)
    return jax.lax.reduce(partial_or, jnp.uint32(0), jax.lax.bitwise_or,
                          (1,))


# --- popcount kernel (cardinality readout) ---------------------------------

def _popcount_kernel(bm_ref, out_ref):
    x = bm_ref[...]
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    counts = ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)
    out_ref[...] = counts.sum(axis=-1, keepdims=True)


def cnd_popcount(bitmaps: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(H, W) uint32 -> (H,) int32 set-bit counts."""
    h, w = bitmaps.shape
    out = pl.pallas_call(
        _popcount_kernel,
        in_specs=[pl.BlockSpec((h, w), lambda: (0, 0))],
        out_specs=pl.BlockSpec((h, 1), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((h, 1), jnp.int32),
        interpret=interpret,
        name="cnd_popcount",
    )(bitmaps)
    return out[:, 0]
