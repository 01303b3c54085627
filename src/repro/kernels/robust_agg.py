"""Pallas TPU kernel: coordinate-wise robust neighbor aggregation.

Byzantine-robust consensus replaces the eq. 5 weighted mix with a
per-coordinate order statistic over each node's neighborhood (own row
included): trimmed mean or median. Per output element that is "sort the
masked column of K candidate values, then dot with position weights" —
a row reduction, so the kernel tiles the flat ``(K, P)`` buffer along P
exactly like ``consensus_mix.flat_consensus`` and sorts the K-axis in
VMEM with an odd-even transposition network (K compare-exchange passes
of pure ``minimum``/``maximum`` — no data-dependent control flow, which
is what makes it lower on the VPU).

Masked-out candidates are set to ``+inf`` so they sort to the tail; the
position-weight matrix (built by ``repro.faults.robust.sorted_weights``
from the per-row neighbor counts) only addresses the live prefix, and a
final ``isfinite`` scrub turns the padding into zeros before the
weighted sum. Payloads are expected finite (the wire guard runs first);
NaNs would poison ``min``/``max`` like any sort.

``robust_agg_xla`` is the ``matmul_nodes``-style XLA fallback used off
TPU: same masking, ``jnp.sort`` over a broadcast ``(K, K, P)`` tensor
(K is small — at most ``flatten._BSUM_MAX_NODES``-scale), same weighted
sum. Both are validated against a numpy oracle in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sort_net(v: jax.Array, k: int) -> jax.Array:
    """Odd-even transposition sort along axis 1 of a (K, K, B) tensor.

    K static passes of vectorized compare-exchange on adjacent pairs
    ((0,1),(2,3),... then (1,2),(3,4),...): after K passes the axis is
    ascending. Pure min/max + where — lowers inside Pallas and under
    XLA alike.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
    for step in range(k):
        par = step % 2
        up = jnp.roll(v, -1, axis=1)      # candidate at position j+1
        down = jnp.roll(v, 1, axis=1)     # candidate at position j-1
        lo = (idx >= par) & ((idx - par) % 2 == 0) & (idx + 1 < k)
        hi = (idx >= par + 1) & ((idx - par) % 2 == 1)
        v = jnp.where(lo, jnp.minimum(v, up),
                      jnp.where(hi, jnp.maximum(v, down), v))
    return v


def _candidates(mask, buf, sent, k: int):
    """(K, K, B) candidate tensor: receiver k aggregates sender i's wire
    payload — except its own slot, which is its clean local buffer (a
    node never receives itself over the radio). Masked-out slots -> +inf
    so they sort past every live value."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (k, k), 1))
    base = jnp.where(eye[:, :, None], buf[None, :, :], sent[None, :, :])
    return jnp.where(mask[:, :, None] > 0, base, jnp.inf)


def _robust_kernel(w_ref, mask_ref, buf_ref, sent_ref, out_ref, *, k: int):
    # w_ref/mask_ref: (K, K) position weights / aggregation support;
    # buf_ref/sent_ref: (K, block_cols) slabs of the flat buffer and the
    # wire payloads. Everything stays 2-D (Mosaic cannot relayout the
    # 3-D broadcasts of ``_candidates``): slot i of every receiver's
    # candidate list is one (K, block_cols) array — row k holds what
    # receiver k sees from sender i — and the sort network runs over
    # the Python list of K such arrays.
    buf = buf_ref[...].astype(jnp.float32)
    sent = sent_ref[...].astype(jnp.float32)
    mask = mask_ref[...]
    w = w_ref[...].astype(jnp.float32)
    recv = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 0)
    slots = []
    for i in range(k):
        own = jnp.where(recv == i, buf[i:i + 1, :], sent[i:i + 1, :])
        slots.append(jnp.where(mask[:, i:i + 1] > 0, own, jnp.inf))
    for step in range(k):                 # odd-even transposition sort
        for j in range(step % 2, k - 1, 2):
            lo = jnp.minimum(slots[j], slots[j + 1])
            slots[j + 1] = jnp.maximum(slots[j], slots[j + 1])
            slots[j] = lo
    acc = jnp.zeros_like(buf)
    for j in range(k):
        acc += w[:, j:j + 1] * jnp.where(jnp.isfinite(slots[j]),
                                         slots[j], 0.0)
    out_ref[...] = acc.astype(out_ref.dtype)


def robust_agg(weights: jax.Array, mask: jax.Array, buf: jax.Array,
               sent: jax.Array, *, block_cols: int = 512,
               interpret: bool = False) -> jax.Array:
    """OUT[k] = sum_j weights[k, j] * sort_i({payload_i : mask[k, i]})[j].

    weights/mask: (K, K); buf/sent: (K, P) with P a multiple of
    ``block_cols`` (flatten pads P to a 128-lane multiple at pack time).
    """
    k, p = buf.shape
    assert weights.shape == (k, k) and mask.shape == (k, k)
    assert sent.shape == (k, p), (sent.shape, buf.shape)
    assert p % block_cols == 0, (p, block_cols)
    grid = (p // block_cols,)
    return pl.pallas_call(
        functools.partial(_robust_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, k), lambda c: (0, 0)),           # weights
            pl.BlockSpec((k, k), lambda c: (0, 0)),           # mask
            pl.BlockSpec((k, block_cols), lambda c: (0, c)),  # buffer slab
            pl.BlockSpec((k, block_cols), lambda c: (0, c)),  # wire slab
        ],
        out_specs=pl.BlockSpec((k, block_cols), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((k, p), buf.dtype),
        interpret=interpret,
        name="robust_agg",
    )(weights, mask.astype(jnp.float32), buf, sent)


def robust_agg_xla(weights: jax.Array, mask: jax.Array, buf: jax.Array,
                   sent: jax.Array) -> jax.Array:
    """XLA fallback: identical math via ``jnp.sort`` on the broadcast
    (K, K, P) candidate tensor — K is node-count small, so the
    broadcast is the same K-term blowup ``flatten.matmul_nodes``
    already accepts on CPU."""
    k = buf.shape[0]
    v = jnp.sort(_candidates(mask, buf.astype(jnp.float32),
                             sent.astype(jnp.float32), k), axis=1)
    v = jnp.where(jnp.isfinite(v), v, 0.0)
    out = jnp.einsum("ki,kip->kp", weights.astype(jnp.float32), v)
    return out.astype(buf.dtype)
