"""Flat parameter-buffer engine for the consensus exchange (paper eq. 5).

The seed implementation applied the K×K consensus operator leaf-by-leaf:
one einsum dispatch per pytree leaf, and the Pallas path additionally
padded *every* leaf to 32K-element tiles (catastrophic for bias-sized
leaves). This module packs any node-stacked pytree (leaves ``(K, ...)``)
into ONE contiguous ``(K, P)`` float32 buffer — P padded once to a
128-lane multiple — so the whole exchange becomes a single fused
``(K, K) @ (K, P)`` operation (XLA matmul, or one
``kernels.consensus_mix.flat_consensus`` Pallas call on TPU).

Layout metadata (:class:`FlatLayout`) is static Python data: per-leaf
trailing shapes, dtypes, and offsets recorded once at pack time, so
unpack restores the exact original pytree (shapes AND dtypes, bit-exact
for f32/bf16 leaves). Everything here is jit-transparent — layouts are
computed from static shapes and close over no tracers.

This buffer is the substrate for every consensus-path scaling direction
(bf16 comms, mesh ring consensus on flat shards, async gossip): those
only need to change how the single ``(K, P)`` buffer moves, never how
the model pytree is traversed.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128                      # TPU lane width: pad P once to a multiple


class FlatLayout(NamedTuple):
    """Static pack/unpack metadata for one node-stacked pytree."""

    treedef: Any                # jax treedef of the packed pytree
    shapes: tuple               # per-leaf trailing shape (K stripped)
    dtypes: tuple               # per-leaf dtype (restored on unpack)
    offsets: tuple              # per-leaf start offset into the buffer
    sizes: tuple                # per-leaf element count (trailing dims)
    total: int                  # unpadded per-node element count
    padded: int                 # total rounded up to a LANE multiple
    num_nodes: int              # K


def make_layout(params) -> FlatLayout:
    """Compute the static layout of a node-stacked pytree.

    Every leaf must be shaped ``(K, ...)`` with the same leading K.
    """
    leaves, treedef = jax.tree.flatten(params)
    if not leaves:
        raise ValueError("cannot flatten an empty pytree")
    k = leaves[0].shape[0]
    shapes, dtypes, offsets, sizes = [], [], [], []
    off = 0
    for leaf in leaves:
        if leaf.ndim < 1 or leaf.shape[0] != k:
            raise ValueError(
                f"leaf {leaf.shape} lacks the leading node dim K={k}")
        size = int(np.prod(leaf.shape[1:], dtype=np.int64))
        shapes.append(tuple(leaf.shape[1:]))
        dtypes.append(jnp.dtype(leaf.dtype))
        offsets.append(off)
        sizes.append(size)
        off += size
    padded = -(-off // LANE) * LANE
    return FlatLayout(treedef=treedef, shapes=tuple(shapes),
                      dtypes=tuple(dtypes), offsets=tuple(offsets),
                      sizes=tuple(sizes), total=off, padded=padded,
                      num_nodes=k)


# XLA:CPU lowers an n-ary concatenate into one fused stitch loop whose
# throughput degrades sharply with operand count (and collapses
# completely when cast/reshape producers fuse into it — measured 8x on
# a 74-leaf tree, and 3x on a 4-leaf gradient pack fused into the
# local-step loop); a chain of static dynamic_update_slice writes stays
# at copy speed there. Accelerator backends vectorize wide concats
# fine, so they get the true single-op pack.
def _single_pass_pack(pieces, pad_shape):
    """Pack pre-reshaped pieces along the trailing axis: one
    concatenate on accelerator backends, an in-place
    ``dynamic_update_slice`` chain on CPU (see note above).
    ``pad_shape``: shape of the zero tail piece (trailing dim 0 to
    skip it)."""
    if pad_shape[-1]:
        pieces = pieces + [jnp.zeros(pad_shape, jnp.float32)]
    if len(pieces) == 1:
        return pieces[0]
    if jax.default_backend() != "cpu":
        return jnp.concatenate(pieces, axis=-1)
    width = sum(p.shape[-1] for p in pieces)
    buf = jnp.zeros(pad_shape[:-1] + (width,), jnp.float32)
    off = 0
    for p in pieces:
        buf = jax.lax.dynamic_update_slice(
            buf, p, (0,) * (len(pad_shape) - 1) + (off,))
        off += p.shape[-1]
    return buf


def flatten(params, layout: FlatLayout | None = None):
    """Pack a node-stacked pytree into a ``(K, P)`` float32 buffer.

    Returns ``(buf, layout)``. Tail padding is zero so reductions over
    the buffer (disagreement, norms) are unaffected by it. The pack is
    a single pass over the pre-reshaped leaves (see
    :func:`_single_pass_pack` for the backend-specific lowering).
    """
    if layout is None:
        layout = make_layout(params)
    k = layout.num_nodes
    pieces = [leaf.reshape(k, -1).astype(jnp.float32)
              for leaf in jax.tree.leaves(params)]
    buf = _single_pass_pack(pieces, (k, layout.padded - layout.total))
    return buf, layout


def pack_node(tree, layout: FlatLayout) -> jax.Array:
    """Pack ONE node's pytree (leaves with the layout's trailing shapes,
    no K dim) into a lane-padded ``(P,)`` f32 vector, tail zero.

    This is the per-local-step gradient pack of the flat-resident round
    pipeline: inside the per-node vmapped local step the gradients come
    back as a pytree and are flattened ONCE into the (P,) vector the
    fused flat-Adam update consumes. Works with a shared K-node layout
    (only the trailing shapes/offsets are read)."""
    pieces = [leaf.reshape(-1).astype(jnp.float32)
              for leaf in jax.tree.leaves(tree)]
    return _single_pass_pack(pieces, (layout.padded - layout.total,))


def _leaf_pieces(buf: jax.Array, layout: FlatLayout, cast: bool):
    """Split the trailing buffer axis at the static leaf offsets (one
    pass of ``jnp.split``), restore trailing shapes and (optionally)
    dtypes. Leading buffer axes (the K dim, or none) pass through."""
    lead = buf.shape[:-1]
    splits = list(layout.offsets[1:])
    if layout.padded > layout.total:
        splits.append(layout.total)          # drop the zero tail piece
    pieces = jnp.split(buf, splits, axis=-1)[:len(layout.sizes)]
    leaves = []
    for piece, shape, dtype in zip(pieces, layout.shapes, layout.dtypes):
        piece = piece.reshape(lead + shape)
        leaves.append(piece.astype(dtype) if cast else piece)
    return leaves


def unflatten(buf: jax.Array, layout: FlatLayout, cast: bool = True):
    """Exact inverse of :func:`flatten`: restore shapes and dtypes in a
    single split pass over the buffer.

    ``cast=False`` keeps the buffer dtype (used for optimizer moments,
    which are always f32 regardless of the param dtypes the layout
    recorded)."""
    return jax.tree.unflatten(layout.treedef,
                              _leaf_pieces(buf, layout, cast))


def unflatten_views(buf: jax.Array, layout: FlatLayout):
    """Leaf VIEWS of the buffer for in-jit consumers (the local-step
    forward/backward of the flat-resident pipeline).

    Same computation as :func:`unflatten` — the distinct name documents
    INTENT: call this inside a jit'd closure, where XLA fuses each
    slice into its consumer instead of materializing leaf copies (the
    params never leave the flat buffer between rounds), and call
    ``unflatten`` at API boundaries where a materialized pytree is the
    point. Outside jit both materialize."""
    return unflatten(buf, layout)


def make_layout_one(params) -> FlatLayout:
    """Layout of a SINGLE node's pytree (no leading K dim).

    Shapes record the full leaf shapes and ``num_nodes`` is 1; pack with
    :func:`flatten_one`, unpack with :func:`unflatten_one`. This is the
    mesh-mode layout: inside ``shard_map`` each fed shard holds ONE
    node's params, and the ring exchange moves the single ``(P,)``
    vector — one collective, not one per leaf.
    """
    return make_layout(jax.tree.map(lambda l: l[None], params))


def flatten_one(params, layout: FlatLayout | None = None):
    """Pack a single-node pytree into a lane-padded ``(P,)`` f32 vector
    (tail padding zero). Inverse: :func:`unflatten_one`."""
    buf, layout = flatten(jax.tree.map(lambda l: l[None], params), layout)
    return buf[0], layout


def unflatten_one(vec: jax.Array, layout: FlatLayout, cast: bool = True):
    """Single-node unpack: (P,) -> pytree with the trailing shapes (no K
    dim). Used inside per-node vmapped compute (loss/grad on one node's
    slice of the flat buffer); like :func:`unflatten_views`, under jit
    the slices fuse into the forward pass instead of copying."""
    leaves = _leaf_pieces(vec, layout, cast)
    return jax.tree.unflatten(layout.treedef, leaves)


def prefix_length(layout: FlatLayout, fraction: float) -> int:
    """Flat-buffer prefix covering the first ``fraction`` of leaves.

    C-DFA(M) mixes only the first ``n_mix = max(1, round(f * n_leaves))``
    leaves (paper Sec. 5.3); on the flat buffer that is a contiguous
    column prefix. Returns a static element count.
    """
    n_leaves = len(layout.sizes)
    n_mix = max(1, int(round(fraction * n_leaves)))
    if n_mix >= n_leaves:
        return layout.total
    return layout.offsets[n_mix]


# --------------------------------------------------------------------------
# Fused consensus operations on the flat buffer
# --------------------------------------------------------------------------

def _use_kernel(use_kernel: bool | None) -> bool:
    """Kernel selection for the flat mixes: ``None`` -> the Pallas kernel
    on TPU, the XLA form elsewhere; an explicit bool wins (``True`` off
    TPU runs the kernel body in interpret mode — correctness tests).
    The kernels tile whole 128-lane columns, so a selected kernel on an
    unaligned width fails loudly; the one caller with unaligned widths,
    the C-DFA(M) column prefix, picks the XLA form itself
    (:func:`prefix_use_kernel`)."""
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return use_kernel


def prefix_use_kernel(prefix: int, use_kernel: bool | None = None):
    """``use_kernel`` for a mix over the first ``prefix`` columns
    (C-DFA(M), paper Sec. 5.3): a prefix that ends inside a 128-lane
    tile cannot be tiled by the Pallas kernels, so it takes the XLA form
    (``False``) — the only place an unaligned width leaves the kernel."""
    return False if prefix % LANE else use_kernel


# Above this node count the K-term broadcast-sum expansion of the
# (K,K)@(K,P) mix stops paying for itself and the real matmul wins.
_BSUM_MAX_NODES = 16


def matmul_nodes(matrix: jax.Array, buf: jax.Array) -> jax.Array:
    """``A @ BUF`` over the node axis, robust to XLA:CPU layout choices.

    For the paper-scale node counts (K <= ~16) the matmul is expanded
    into K broadcast-scaled row sums: pure elementwise work that fuses
    with neighbors and never triggers the layout-conversion transpose
    XLA:CPU inserts around a (K,K)@(K,P) ``dot`` composed with pack /
    unpack (measured 6-20x on the composite one-shot step). Larger K
    falls back to the real matmul (MXU/gemm-bound regime)."""
    a = matrix.astype(buf.dtype)
    k = buf.shape[0]
    if k <= _BSUM_MAX_NODES:
        return sum(a[:, i:i + 1] * buf[i] for i in range(k))
    return jnp.einsum("ki,ip->kp", a, buf)


def apply_matrix_flat(buf: jax.Array, matrix: jax.Array,
                      use_kernel: bool | None = None) -> jax.Array:
    """``A @ BUF``: one (K,K)@(K,P) operation applies any linear
    consensus operator to every parameter of every node at once."""
    if _use_kernel(use_kernel):
        from repro.kernels import ops
        # an EXPLICIT use_kernel=True off-TPU still runs the Pallas body
        # (interpret mode — correctness tests); auto never does
        return ops.flat_consensus(matrix.astype(buf.dtype), buf,
                                  force_kernel=use_kernel is True)
    return matmul_nodes(matrix, buf)


def mix_flat(buf: jax.Array, eta: jax.Array, gamma,
             self_weight: float = 1.0,
             use_kernel: bool | None = None,
             wire: jax.Array | None = None,
             wire_self: jax.Array | None = None) -> jax.Array:
    """Paper eq. (5) on the flat buffer, one fused operation:

        phi_k = sw * W_k + gamma * sum_i eta_ki (W_i - W_k)

    The delta form (neighbor matmul minus row-sum rescale) keeps the
    cancellation error at the f32 noise floor — the precomposed-matrix
    form ``A @ W`` loses ~1 decimal digit when ``gamma * row_sum`` is
    close to 1.

    ``wire`` is the buffer as it traveled the network (defaults to
    ``buf``): pass a bf16 cast to halve exchanged bytes, or a stale
    gossip snapshot for bounded-delay rounds. Only the difference terms
    see the wire precision — they vanish at consensus — while ``buf``
    stays the f32 master copy. ``wire_self`` (default ``wire``) is each
    node's own payload in the self-cancellation term: under fault
    injection the neighbor frames diverge from it.
    """
    eta32 = eta.astype(buf.dtype)
    g = jnp.asarray(gamma, buf.dtype)
    w = buf if wire is None else wire
    if _use_kernel(use_kernel):
        # the whole delta form (matmul + row-sum rescale + master add)
        # fuses into ONE Pallas pass; the wire slab is read at its wire
        # dtype and upcast in VMEM, so a bf16 wire halves neighbor-read
        # bytes too. Off TPU this kernel runs only on an EXPLICIT
        # use_kernel=True (interpret-mode correctness tests).
        from repro.kernels import ops
        out = ops.flat_mix(eta32, buf, w, g, wire_self,
                           force_kernel=use_kernel is True)
        if self_weight == 1.0:
            return out
        return out + jnp.asarray(self_weight - 1.0, buf.dtype) * buf
    row = eta32.sum(axis=1)
    w32 = w.astype(buf.dtype)
    ws32 = w32 if wire_self is None else wire_self.astype(buf.dtype)
    mixed = matmul_nodes(eta32, w32)
    out = g * (mixed - row[:, None] * ws32)
    if self_weight == 1.0:
        return buf + out
    return jnp.asarray(self_weight, buf.dtype) * buf + out


def sparse_neighbor_sum(idx: jax.Array, val: jax.Array,
                        w: jax.Array) -> jax.Array:
    """``sum_d val[k,d] * W[idx[k,d]]`` — the neighbor term of eq. (5)
    on a top-D sparse eta: D fused gather-axpy passes over the (K, P)
    buffer, O(K·D·P) instead of the dense O(K²P) matmul. Zero-weight
    slots (isolated nodes, degree padding) gather a row and multiply it
    away — no masking, no NaN.

    The D axis is unrolled in Python (D is static): each slot lowers to
    one row gather fused with a multiply-accumulate — a streaming pass
    XLA vectorizes cleanly. The batched-gemv lowering of the equivalent
    ``einsum('kd,kdp->kp', val, W[idx])`` materializes the (K, D, P)
    gather and runs K tiny dots — measured ~8x slower on XLA:CPU at
    K=1024, D=8."""
    w32 = w.astype(jnp.float32)
    val32 = val.astype(jnp.float32)
    acc = val32[:, 0:1] * w32[idx[:, 0]]
    for dd in range(1, idx.shape[1]):
        acc = acc + val32[:, dd:dd + 1] * w32[idx[:, dd]]
    return acc


def sparse_mix_flat(buf: jax.Array, idx: jax.Array, val: jax.Array,
                    gamma, use_kernel: bool | None = None,
                    wire: jax.Array | None = None,
                    wire_self: jax.Array | None = None) -> jax.Array:
    """Paper eq. (5) on the flat buffer with top-D sparse weights:

        phi_k = W_k + gamma * (sum_d val_kd W_{idx_kd} - rowsum_k W_k)

    The sparse twin of :func:`mix_flat` — same delta form (cancellation
    at the f32 noise floor), same ``wire``/``wire_self`` convention
    (difference terms at wire precision, ``buf`` the f32 master). All-zero rows reduce to
    a pure self-update. Dispatches to the Pallas gather-mix kernel on
    TPU (or on an explicit ``use_kernel=True``, interpret mode); the
    XLA ``take`` + ``einsum`` path is the auto-selected path off-TPU.
    """
    g = jnp.asarray(gamma, buf.dtype)
    w = buf if wire is None else wire
    if _use_kernel(use_kernel):
        from repro.kernels import ops
        return ops.sparse_mix(idx, val, buf, w, g, wire_self,
                              force_kernel=use_kernel is True)
    val32 = val.astype(buf.dtype)
    w32 = w.astype(buf.dtype)
    ws32 = w32 if wire_self is None else wire_self.astype(buf.dtype)
    row = val32.sum(axis=1)
    mixed = sparse_neighbor_sum(idx, val32, w32)
    return buf + g * (mixed - row[:, None] * ws32)


def cluster_mix_flat(buf: jax.Array, idx: jax.Array, val: jax.Array,
                     gamma_node: jax.Array,
                     use_kernel: bool | None = None,
                     wire: jax.Array | None = None,
                     wire_self: jax.Array | None = None) -> jax.Array:
    """Eq. (5) with a PER-NODE step size — the intra-cluster tier of
    hierarchical mixing:

        phi_k = W_k + g_k * (sum_d val_kd W_{idx_kd} - rowsum_k W_k)

    ``gamma_node`` is a (K,) vector: each mobility cluster mixes at its
    OWN stability bound instead of the global one (the index table only
    points at co-cluster members, making the implied operator
    block-diagonal). ``wire``/``wire_self`` follow the fault-path
    convention of the dense transport: the neighbor term reads ``wire``
    (possibly a fault-overridden, codec'd payload), the self rescale
    reads ``wire_self`` (default ``wire``), and ``buf`` stays the f32
    master. Dispatches to the Pallas ``cluster_mix`` kernel on
    TPU (or on explicit ``use_kernel=True``, interpret mode); off-TPU
    the auto path is the same D-pass gather-axpy as
    :func:`sparse_mix_flat`."""
    g = gamma_node.astype(buf.dtype)
    w = buf if wire is None else wire
    ws = w if wire_self is None else wire_self
    if _use_kernel(use_kernel):
        from repro.kernels import ops
        return ops.cluster_mix(idx, val, buf, ws, w, g,
                               force_kernel=use_kernel is True)
    val32 = val.astype(buf.dtype)
    w32 = w.astype(buf.dtype)
    ws32 = ws.astype(buf.dtype)
    row = val32.sum(axis=1)
    mixed = sparse_neighbor_sum(idx, val32, w32)
    return buf + g[:, None] * (mixed - row[:, None] * ws32)


def partial_mix_flat(buf: jax.Array, eta: jax.Array, gamma, prefix: int,
                     use_kernel: bool | None = None) -> jax.Array:
    """Eq. (5) on the first ``prefix`` buffer columns only (C-DFA(M):
    federated optimization on Q <= N layers). ``eta`` may be dense
    (K, K) or a ``topology.SparseEta`` (duck-typed on ``.idx`` to keep
    this module free of repro imports). An unaligned prefix takes the
    XLA form (:func:`prefix_use_kernel`)."""
    use_kernel = prefix_use_kernel(prefix, use_kernel)
    if hasattr(eta, "idx"):
        head = sparse_mix_flat(buf[:, :prefix], eta.idx, eta.val, gamma,
                               use_kernel=use_kernel)
    else:
        head = mix_flat(buf[:, :prefix], eta, gamma, use_kernel=use_kernel)
    return jnp.concatenate([head, buf[:, prefix:]], axis=1)


def column_shards(padded: int, shards: int) -> int:
    """Largest shard count <= ``shards`` that splits a ``padded``-wide
    buffer into equal LANE-aligned column chunks. The ring transport
    ppermutes chunk j+1 while mixing chunk j; unshardable widths fall
    back to 1 (one transfer, no overlap)."""
    shards = max(int(shards), 1)
    while shards > 1 and (padded % shards or (padded // shards) % LANE):
        shards -= 1
    return shards


def disagreement_flat(buf: jax.Array, total: int) -> jax.Array:
    """Mean squared node deviation from the node-mean, computed in one
    pass over the buffer. ``total`` is the unpadded per-node element
    count (tail padding is zero on every node, contributing nothing)."""
    mu = buf.mean(axis=0, keepdims=True)
    ss = jnp.sum((buf - mu) ** 2)
    return ss / (buf.shape[0] * total)
