"""Pallas TPU kernels: top-D gather-mix (paper eq. 5, sparse eta).

    out_k = W_k + g_k * (sum_d val[k,d] * WIRE[idx[k,d]] - rowsum_k * WSELF_k)

The dense ``flat_mix`` kernel pays an O(K^2 P) matmul even when the
radio-range graph is bounded-degree; these kernels gather only the D
neighbor rows each node actually mixes with — O(K D P). The neighbor
indices ride the scalar-prefetch channel (SMEM) so each grid step's
BlockSpec index map can select the *data-dependent* wire row to DMA:
the gather never materializes a dense operator.

Two entry points share one body:

* :func:`sparse_mix` — one global step size (the flat sparse format);
* :func:`cluster_mix` — a per-node step size ``g`` (the intra-cluster
  tier of hierarchical consensus: the index table only lists co-cluster
  members, so each cluster mixes at its OWN stability bound).

Tiling. The flat ``(K, P)`` buffers are viewed as ``(K, P/128, 128)``
(a free row-major reshape: P is lane-padded at pack time) and every
block is one node's ``(block_rows, 128)`` slab, with the node axis
squeezed. A ``(1, block_cols)`` block of the 2-D buffer is not a legal
TPU tile (its second-minor dim is neither a multiple of 8 nor the whole
K axis); the slab is, for f32 and for bf16 wires (16-row tiles) alike,
because ``block_rows`` is either all of ``P/128`` or a multiple of 16.

Grid: ``(row blocks, K, D)`` with D innermost. The out block at
``(k, c)`` is revisited across the D steps (its index map ignores
``dd``), so it stays resident in VMEM: step ``dd == 0`` initializes it
with the self/row-sum term, every step accumulates one gathered
neighbor slab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# row-block cap: a multiple of 16 (the bf16 sublane tile), 256 KiB per
# f32 block; widths up to this many 128-lane rows take one block per node
MAX_BLOCK_ROWS = 512


def _gather_mix_kernel(idx_ref, val_ref, row_ref, g_ref,
                       master_ref, wself_ref, wnb_ref, out_ref, *,
                       degree: int):
    # idx_ref/val_ref: (K*D,) flattened neighbor table in SMEM;
    # row_ref: (K,) kept-weight row sums; g_ref: (K,) per-node gamma.
    # master_ref/wself_ref: this node's (block_rows, 128) slab (f32
    # master, wire-precision self copy); wnb_ref: the gathered neighbor
    # slab — which HBM row it holds was chosen by the in_spec index map
    # from idx_ref, before the body ran.
    kk = pl.program_id(1)
    dd = pl.program_id(2)
    g = g_ref[kk]

    @pl.when(dd == 0)
    def _init():
        m = master_ref[...].astype(jnp.float32)
        ws = wself_ref[...].astype(jnp.float32)
        out_ref[...] = (m - g * row_ref[kk] * ws).astype(out_ref.dtype)

    v = val_ref[kk * degree + dd]
    out_ref[...] += (g * v * wnb_ref[...].astype(jnp.float32)
                     ).astype(out_ref.dtype)


def _gather_mix(idx, val, master, wself, wire, g, *, name: str,
                interpret: bool):
    k, p = master.shape
    d = idx.shape[1]
    assert idx.shape == (k, d) and val.shape == (k, d), (idx.shape,
                                                         val.shape)
    assert wire.shape == (k, p) and wself.shape == (k, p), (
        wself.shape, wire.shape, master.shape)
    assert g.shape == (k,), (g.shape, k)
    assert p % LANE == 0, (p, LANE)
    rows = p // LANE
    block_rows = min(rows, MAX_BLOCK_ROWS)
    val32 = val.astype(jnp.float32)
    idx_flat = idx.astype(jnp.int32).reshape(-1)
    val_flat = val32.reshape(-1)
    row = val32.sum(axis=1)

    def _self(c, kk, dd, idx_r, val_r, row_r, g_r):
        return (kk, c, 0)

    def _gather(c, kk, dd, idx_r, val_r, row_r, g_r):
        return (idx_r[kk * d + dd], c, 0)

    slab = (None, block_rows, LANE)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(pl.cdiv(rows, block_rows), k, d),
        in_specs=[
            pl.BlockSpec(slab, _self),      # master slab
            pl.BlockSpec(slab, _self),      # wire self slab
            pl.BlockSpec(slab, _gather),    # gathered neighbor slab
        ],
        out_specs=pl.BlockSpec(slab, _self),
    )
    out = pl.pallas_call(
        functools.partial(_gather_mix_kernel, degree=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, rows, LANE), master.dtype),
        interpret=interpret,
        name=name,
    )(idx_flat, val_flat, row, g.astype(jnp.float32),
      master.reshape(k, rows, LANE), wself.reshape(k, rows, LANE),
      wire.reshape(k, rows, LANE))
    return out.reshape(k, p)


def sparse_mix(idx: jax.Array, val: jax.Array, master: jax.Array,
               wself: jax.Array, wire: jax.Array, gamma: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """Fused sparse eq.5 delta mix over the flat (K, P) buffer.

    idx: (K, D) int32 neighbor indices; val: (K, D) f32 weights (zero
    slots gather-and-discard — isolated nodes come out as pure
    self-updates); master: (K, P) f32 master copy, P a multiple of 128;
    wself/wire: the self/neighbor payloads as exchanged (master itself,
    a bf16 cast, a stale gossip snapshot or a fault-overridden frame) —
    only the difference terms see wire precision.
    """
    k = master.shape[0]
    g = jnp.broadcast_to(jnp.asarray(gamma, jnp.float32), (k,))
    return _gather_mix(idx, val, master, wself, wire, g,
                       name="sparse_mix", interpret=interpret)


def cluster_mix(idx: jax.Array, val: jax.Array, master: jax.Array,
                wself: jax.Array, wire: jax.Array, gamma_node: jax.Array,
                *, interpret: bool = False) -> jax.Array:
    """Fused intra-cluster eq.5 delta mix with per-node step sizes.

    Same arguments as :func:`sparse_mix`, except ``gamma_node``: a (K,)
    cluster-local gamma vector, read per node from SMEM. The index
    table (``repro.hierarchy.mixing.hier_geometry``) only points at
    co-cluster members, so the implied dense operator is block-diagonal
    under the cluster permutation — the kernel never needs the
    permutation, it just gathers the D listed rows.
    """
    return _gather_mix(idx, val, master, wself, wire, gamma_node,
                       name="cluster_mix", interpret=interpret)
