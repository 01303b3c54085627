"""Pallas TPU kernel: fused consensus mixing (paper eq. 5).

    out = W_k + gamma * sum_i eta_i * (W_i - W_k)

Naively each neighbor term is a separate HBM pass over the full parameter
vector (2 reads + 1 write per neighbor); the fused kernel streams W_k and
all N neighbor shards through VMEM once: (N+1) reads + 1 write total.
Tiles are (block_rows, 128) — f32/bf16 lane-aligned for the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _kernel(scal_ref, w_ref, nb_ref, out_ref, *, n_neighbors: int):
    # scal_ref: (1, n_neighbors + 1) f32 — [gamma, eta_0..eta_{N-1}]
    w = w_ref[...].astype(jnp.float32)
    gamma = scal_ref[0, 0]
    acc = jnp.zeros_like(w)
    for i in range(n_neighbors):                    # static unroll (N <= ~8)
        eta = scal_ref[0, i + 1]
        acc += eta * (nb_ref[i].astype(jnp.float32) - w)
    out_ref[...] = (w + gamma * acc).astype(out_ref.dtype)


def _flat_kernel(a_ref, buf_ref, out_ref):
    # a_ref: (K, K) consensus operator; buf_ref: (K, block_cols) slice of
    # the flat parameter buffer. One MXU matmul mixes every node at once.
    a = a_ref[...].astype(jnp.float32)
    buf = buf_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.dot(
        a, buf, preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _flat_mix_kernel(scal_ref, eta_ref, master_ref, wire_ref, *rest):
    # scal_ref: (1, 1) gamma. eta_ref: (K, K) neighbor weights.
    # master_ref: (K, block_cols) f32 master slab; wire_ref: the slab as it
    # traveled the wire (f32 or bf16); rest: [wself_ref,] out_ref — a
    # separate self payload only when it differs from the wire (fault
    # injection). Delta form in one VMEM pass:
    #     out = master + gamma * (eta @ wire - rowsum(eta) * wself)
    # so a bf16 wire perturbs only the *difference* terms (which vanish at
    # consensus), never the f32 master copy.
    *wself_ref, out_ref = rest
    eta = eta_ref[...].astype(jnp.float32)
    w = wire_ref[...].astype(jnp.float32)
    ws = wself_ref[0][...].astype(jnp.float32) if wself_ref else w
    m = master_ref[...].astype(jnp.float32)
    g = scal_ref[0, 0]
    row = eta.sum(axis=1)[:, None]
    # full f32 contraction: a one-pass bf16 MXU dot would leave a ~1e-3
    # relative floor under ``mixed - row * ws``, which must cancel at
    # consensus (the XLA form at paper-scale K is an exact f32
    # broadcast-sum, see flatten.matmul_nodes)
    mixed = jnp.dot(eta, w, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    out_ref[...] = (m + g * (mixed - row * ws)).astype(out_ref.dtype)


def flat_mix(eta: jax.Array, master: jax.Array, wire: jax.Array,
             gamma: jax.Array, wire_self: jax.Array | None = None, *,
             block_cols: int = 512, interpret: bool = False) -> jax.Array:
    """Fused paper-eq.5 delta mix over the flat (K, P) buffer:

        OUT = MASTER + gamma * (ETA @ WIRE - rowsum(ETA) * WIRE_SELF)

    One kernel launch streams the master slab and the wire slab through
    VMEM once — the matmul, row-sum rescale, and master add that were
    previously separate XLA ops all fuse here. ``wire`` is the exchanged
    representation of the buffer (``master`` itself, a bf16 cast of it,
    or a stale gossip snapshot); a bf16 wire halves the neighbor-read
    bytes while the accumulation stays f32. ``wire_self`` (default
    ``wire``) is each node's own payload for the self-cancellation term:
    under fault injection the neighbor frames diverge from it.
    """
    k, p = master.shape
    assert eta.shape == (k, k), (eta.shape, k)
    assert wire.shape == (k, p), (wire.shape, master.shape)
    assert p % block_cols == 0, (p, block_cols)
    scal = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    slab = pl.BlockSpec((k, block_cols), lambda c: (0, c))
    operands = [scal, eta, master, wire]
    if wire_self is not None:
        assert wire_self.shape == (k, p), (wire_self.shape, master.shape)
        operands.append(wire_self)
    return pl.pallas_call(
        _flat_mix_kernel,
        grid=(p // block_cols,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda c: (0, 0)),           # gamma
            pl.BlockSpec((k, k), lambda c: (0, 0)),           # eta
        ] + [slab] * (len(operands) - 2),   # master, wire[, wire_self]
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((k, p), master.dtype),
        interpret=interpret,
        name="flat_mix",
    )(*operands)


def flat_consensus(matrix: jax.Array, buf: jax.Array, *,
                   block_cols: int = 512,
                   interpret: bool = False) -> jax.Array:
    """OUT = A @ BUF over the whole flat (K, P) parameter buffer.

    ONE kernel launch replaces the seed's per-leaf dispatch (and its
    per-leaf padding to 32K-element tiles): the grid tiles P, each step
    streams a (K, block_cols) slab through VMEM once. A is any linear
    consensus operator (eq. 5 matrix, FedAvg weights, ...).

    matrix: (K, K); buf: (K, P) with P a multiple of ``block_cols``
    (repro.core.flatten pads P to a 128-lane multiple once, at pack time).
    """
    k, p = buf.shape
    assert matrix.shape == (k, k), (matrix.shape, k)
    assert p % block_cols == 0, (p, block_cols)
    grid = (p // block_cols,)
    return pl.pallas_call(
        _flat_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, k), lambda c: (0, 0)),          # operator
            pl.BlockSpec((k, block_cols), lambda c: (0, c)),  # buffer slab
        ],
        out_specs=pl.BlockSpec((k, block_cols), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((k, p), buf.dtype),
        interpret=interpret,
    )(matrix, buf)


def consensus_mix(w: jax.Array, neighbors: jax.Array, eta: jax.Array,
                  gamma: jax.Array, *, block_rows: int = 256,
                  interpret: bool = False) -> jax.Array:
    """w: (rows, 128); neighbors: (N, rows, 128); eta: (N,); gamma scalar."""
    n, rows, lane = neighbors.shape
    assert lane == LANE and w.shape == (rows, LANE)
    assert rows % block_rows == 0, (rows, block_rows)
    scal = jnp.concatenate(
        [jnp.asarray(gamma, jnp.float32)[None], eta.astype(jnp.float32)]
    )[None, :]                                       # (1, N+1)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, n_neighbors=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n + 1), lambda r: (0, 0)),          # scalars
            pl.BlockSpec((block_rows, LANE), lambda r: (r, 0)),  # W_k
            pl.BlockSpec((n, block_rows, LANE), lambda r: (0, r, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), w.dtype),
        interpret=interpret,
    )(scal, w, neighbors)
