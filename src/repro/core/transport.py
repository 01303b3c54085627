"""Pluggable consensus transport layer — how the flat buffer moves.

The paper's eq. 5 exchange is the only part of C-DFL that touches the
network. Everything upstream (CND weights, local Adam, the scan driver)
is transport-agnostic once params live in the flat ``(K, P)`` buffer
(repro.core.flatten), so the three comms-scaling directions — compressed
wire formats, ring-sharded collectives, bounded-delay async gossip — are
all implementations of ONE protocol:

    state        = transport.init_state(buf)
    buf', state' = transport.exchange(buf, eta, gamma, state, rnd)

Transports are **plugins**: ``repro.registry.transports`` maps a name to
a ``fed -> Transport`` factory, and :func:`make_transport` is nothing
but that lookup. The built-ins:

* :class:`DenseTransport` — the fused ``(K,K)@(K,P)`` mix (XLA einsum or
  the Pallas ``flat_mix`` kernel on TPU).
* :class:`RingShardTransport` — neighbor exchange restricted to the ring
  ``{k-1, k+1}``: two shifted copies of the wire buffer instead of a
  dense matmul. In simulation (node-stacked buffer) the shift is
  ``jnp.roll`` on the K axis; under ``shard_map`` over the fed mesh axes
  it is ONE ``lax.ppermute`` per direction per round on the flat vector
  (see :func:`ring_exchange_shard`) — the seed path issued one per leaf.
* :class:`GossipTransport` — bounded-delay (stale-neighbor) exchange:
  neighbors read a snapshot of the buffer ``staleness`` rounds old, kept
  in a circular double buffer inside the transport state.
  ``staleness=0`` bypasses the state and reproduces synchronous C-DFL
  bit-exactly (mobility/async-DFL comparisons, arXiv:2503.06443).

What travels the wire is a second, orthogonal plugin axis: a
:class:`WireCodec` (``repro.registry.wire_codecs``) encodes the f32
master buffer into its wire representation and decodes what a receiver
reconstructs. ``bf16`` (halves consensus bytes; delta-form mixing keeps
the wire precision on the neighbor *differences*, which vanish at
consensus) is just the first registered codec — an int8+per-column-scales
codec plugs in WITHOUT touching any transport, because every transport
routes its wire traffic through ``codec.encode``/``codec.decode``. A
codec may return a pytree from ``encode`` (e.g. values + scales); every
leaf must keep the node axis leading so neighbor shifts apply leaf-wise.

Transports are frozen dataclasses (hashable, jit-static); their state is
a pytree that rides the trainer's scan carry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import flatten
from repro.core.topology import SparseEta
from repro.registry import transports, wire_codecs


# --------------------------------------------------------------------------
# Wire codecs: the buffer's on-the-wire representation.
# --------------------------------------------------------------------------

class WireCodec:
    """f32 flat buffer <-> wire representation.

    ``encode(buf)`` returns the wire pytree (every leaf with the node
    axis leading); ``decode(wire, dtype)`` reconstructs the buffer as
    the receiver sees it. ``cast_dtype`` advertises that ``encode`` is a
    pure dtype cast — transports with a fused mix kernel may then feed
    the encoded array straight into the kernel (which upcasts in VMEM)
    instead of decode()ing first. Codecs with side information (scales,
    sparsity masks) leave it ``None``.
    """

    name: str = "?"
    cast_dtype = None            # non-None => encode is astype(cast_dtype)

    def encode(self, buf: jax.Array):
        raise NotImplementedError

    def decode(self, wire, dtype=jnp.float32) -> jax.Array:
        raise NotImplementedError

    def wire_bytes(self, layout: flatten.FlatLayout) -> int:
        """Bytes one node sends over one link per round."""
        raise NotImplementedError

    def roundtrip(self, buf: jax.Array) -> jax.Array:
        """``buf`` as it survives the wire, back in ``buf``'s dtype."""
        return self.decode(self.encode(buf), buf.dtype)


@dataclasses.dataclass(frozen=True)
class CastCodec(WireCodec):
    """Pure-dtype-cast codec: encode is ``astype``, decode is the upcast
    back. ``f32`` (identity) and ``bf16`` are the registered instances."""

    name: str = "f32"
    dtype: Any = jnp.float32

    @property
    def cast_dtype(self):
        return self.dtype

    def encode(self, buf: jax.Array) -> jax.Array:
        return buf.astype(self.dtype)

    def decode(self, wire, dtype=jnp.float32) -> jax.Array:
        return wire.astype(dtype)

    def wire_bytes(self, layout: flatten.FlatLayout) -> int:
        return layout.padded * jnp.dtype(self.dtype).itemsize


wire_codecs.register("f32", CastCodec("f32", jnp.float32))
wire_codecs.register("bf16", CastCodec("bf16", jnp.bfloat16))

# Back-compat view of the pre-registry module dict (name -> jnp dtype;
# None for codecs that are not a pure cast).
WIRE_DTYPES = wire_codecs.view(lambda c: c.cast_dtype)


def wire_codec(name: str) -> WireCodec:
    """Look up a registered :class:`WireCodec` (listing names on miss)."""
    return wire_codecs.get(name)


def _wire_dtype(name: str):
    """Legacy helper: the jnp dtype of a pure-cast codec."""
    codec = wire_codec(name)
    if codec.cast_dtype is None:
        raise ValueError(f"wire codec {name!r} is not a pure dtype cast")
    return codec.cast_dtype


# --------------------------------------------------------------------------
# Transports.
# --------------------------------------------------------------------------

class _FlatTransport:
    """Shared transport behavior: one full wire-codec payload per link
    per round, and no state unless a subclass says otherwise."""

    wire_dtype: str = "f32"

    @property
    def codec(self) -> WireCodec:
        return wire_codec(self.wire_dtype)

    @property
    def stateful(self) -> bool:
        """False skips the init-time buffer pack init_state would need."""
        return False

    def init_state(self, buf: jax.Array) -> Any:
        return ()

    def wire_bytes(self, layout: flatten.FlatLayout) -> int:
        """Bytes one node sends over one link per round."""
        return self.codec.wire_bytes(layout)


def _fused_wire(codec: WireCodec, buf: jax.Array):
    """The ``wire`` argument for :func:`flatten.mix_flat`: ``None`` for
    the identity codec and for a cast to the buffer's own dtype, the raw
    cast for other pure-cast codecs (the fused kernel reads the narrow
    wire slab and upcasts in VMEM), the decoded roundtrip otherwise."""
    if codec.cast_dtype is not None:
        if _cast_noops(codec, buf):
            return None
        return codec.encode(buf)
    return codec.roundtrip(buf)


def _cast_noops(codec: WireCodec, buf: jax.Array) -> bool:
    """Whether a pure-cast codec's roundtrip is the identity for this
    buffer: its cast dtype is the buffer's own."""
    return (codec.cast_dtype is not None
            and jnp.dtype(codec.cast_dtype) == buf.dtype)


def _wire_pair(codec: WireCodec, buf: jax.Array, sent):
    """``(wire, wire_self)`` for :func:`_mix`. Fault-free (``sent`` is
    None): the fused wire and no separate self payload. Fault-injected:
    per-node wire payloads (``sent``) diverge from the master buffer, so
    the neighbor terms read the codec'd payloads while the
    self-cancellation term keeps each node's OWN clean buffer (a node
    never receives itself). The codec applies per GATHERED row on the
    sparse path: the gather reads the codec'd payload matrix."""
    if sent is None:
        return _fused_wire(codec, buf), None
    if _cast_noops(codec, buf):
        return sent, buf
    return codec.roundtrip(sent), codec.roundtrip(buf)


def _mix(buf, eta, gamma, use_kernel, wire, wire_self):
    """Eq. 5 delta mix in the format of ``eta``: the dense
    :func:`flatten.mix_flat` or the sparse top-D gather
    :func:`flatten.sparse_mix_flat` (Pallas kernels on TPU)."""
    if isinstance(eta, SparseEta):
        return flatten.sparse_mix_flat(buf, eta.idx, eta.val, gamma,
                                       use_kernel=use_kernel, wire=wire,
                                       wire_self=wire_self)
    return flatten.mix_flat(buf, eta, gamma, use_kernel=use_kernel,
                            wire=wire, wire_self=wire_self)


@dataclasses.dataclass(frozen=True)
class DenseTransport(_FlatTransport):
    """Fused dense exchange: every node mixes every neighbor in one
    ``(K,K)@(K,P)`` operation (the eta matrix encodes the topology)."""

    wire_dtype: str = "f32"
    use_kernel: bool | None = None      # None -> auto (TPU)

    def exchange(self, buf, eta, gamma, state=(), rnd=None, sent=None):
        wire, wire_self = _wire_pair(self.codec, buf, sent)
        return _mix(buf, eta, gamma, self.use_kernel, wire,
                    wire_self), state


@dataclasses.dataclass(frozen=True)
class RingShardTransport(_FlatTransport):
    """Eq. 5 on the ring ``{k-1, k+1}`` — two shifted wire buffers, no
    dense matmul. Requires K >= 3 (on K=2 both shifts alias the single
    neighbor and its weight would be double-counted).

    ``shards`` is the column-shard count for the mesh path: the flat
    vector is ppermuted in ``shards`` chunks so the mix of chunk j
    overlaps the transfer of chunk j+1 (XLA async collective-permute).
    Simulation mode has no transfer to hide and ignores it.
    """

    wire_dtype: str = "f32"
    shards: int = 1

    def exchange(self, buf, eta, gamma, state=(), rnd=None, sent=None):
        k = buf.shape[0]
        if k < 3:
            raise ValueError(f"ring transport needs K >= 3 nodes, got {k}")
        if isinstance(eta, SparseEta):
            raise ValueError(
                "ring transport is physically degree-2 (the {k-1, k+1} "
                "shifts ARE its topology) — sparse top-D eta has nothing "
                "to gather here; use the dense or gossip transport with "
                "mixing_format='sparse'")
        idx = jnp.arange(k)
        eta32 = eta.astype(buf.dtype)
        ep = eta32[idx, (idx - 1) % k][:, None]     # weight for k-1
        en = eta32[idx, (idx + 1) % k][:, None]     # weight for k+1
        # fault injection swaps the payload the ring shifts move (the
        # self-cancellation term stays the node's own clean buffer)
        src = buf if sent is None else sent
        codec = self.codec
        if _cast_noops(codec, buf):
            w_self = buf
            w_prev = jnp.roll(src, 1, axis=0)
            w_next = jnp.roll(src, -1, axis=0)
            g = jnp.asarray(gamma, buf.dtype)
            out = buf + g * (ep * (w_prev - w_self)
                             + en * (w_next - w_self))
            return out, state
        enc = codec.encode(src)
        # neighbor shifts apply to the ENCODED payload leaf-wise (side
        # information such as per-node scales shifts with its values)
        w_self = codec.roundtrip(buf)
        w_prev = codec.decode(
            jax.tree.map(lambda a: jnp.roll(a, 1, axis=0), enc), buf.dtype)
        w_next = codec.decode(
            jax.tree.map(lambda a: jnp.roll(a, -1, axis=0), enc), buf.dtype)
        g = jnp.asarray(gamma, buf.dtype)
        out = buf + g * (ep * (w_prev - w_self) + en * (w_next - w_self))
        return out, state


@dataclasses.dataclass(frozen=True)
class GossipTransport(_FlatTransport):
    """Bounded-delay gossip: neighbor terms read a buffer snapshot
    ``staleness`` rounds old (a circular buffer of ENCODED snapshots in
    the transport state — stored at wire size, whatever the codec).
    ``staleness=0`` is stateless and bit-identical to
    :class:`DenseTransport`."""

    # the snapshot STATE is stored at wire size, and the s > 0 exchange
    # pays the codec roundtrip on its self term too, so staleness -> 0
    # recovers the synchronous form term by term
    staleness: int = 0
    wire_dtype: str = "f32"
    use_kernel: bool | None = None      # None -> auto (TPU)

    @property
    def stateful(self) -> bool:
        return self.staleness > 0

    def init_state(self, buf: jax.Array) -> Any:
        if self.staleness == 0:
            return ()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[None], (self.staleness,) + a.shape).copy(),
            self.codec.encode(buf))

    def exchange(self, buf, eta, gamma, state=(), rnd=None, sent=None):
        codec = self.codec
        if self.staleness == 0:
            wire, wire_self = _wire_pair(codec, buf, sent)
            return _mix(buf, eta, gamma, self.use_kernel, wire,
                        wire_self), state
        if rnd is None:
            raise ValueError("stale gossip needs the round index (rnd)")
        # slot r % s was last written at round r - s: exactly s rounds old
        slot = jnp.mod(jnp.asarray(rnd, jnp.int32), self.staleness)
        stale_enc = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, slot, 0,
                                                   keepdims=False), state)
        # fault injection snapshots the (guard-scrubbed) wire payload —
        # poisoned rows were already replaced by the sender's clean
        # buffer upstream (the retransmission model), so the snapshot
        # ring never stores NaN/Inf for a stale round to replay
        new_state = jax.tree.map(
            lambda a, fresh: jax.lax.dynamic_update_index_in_dim(
                a, fresh[None], slot, 0),
            state, codec.encode(buf if sent is None else sent))
        # neighbor terms from the stale snapshot, self term from the
        # CURRENT buffer at wire precision (so staleness->0 recovers the
        # synchronous delta form term by term); the sparse path gathers
        # its D stale rows from the decoded snapshot — stale-snapshot
        # bookkeeping is format-independent
        stale = codec.decode(stale_enc, buf.dtype)
        return _mix(buf, eta, gamma, self.use_kernel, stale,
                    codec.roundtrip(buf)), new_state


# --------------------------------------------------------------------------
# Registration + config factory.
# --------------------------------------------------------------------------

@transports.register("dense")
def _make_dense(fed) -> DenseTransport:
    return DenseTransport(wire_dtype=getattr(fed, "wire_dtype", "f32"))


@transports.register("ring")
def _make_ring(fed) -> RingShardTransport:
    if fed.num_nodes < 3:
        raise ValueError("ring transport needs num_nodes >= 3")
    if fed.topology != "ring":
        raise ValueError(
            f"ring transport moves data only between ring neighbors; "
            f"topology={fed.topology!r} needs the dense transport")
    return RingShardTransport(wire_dtype=getattr(fed, "wire_dtype", "f32"))


@transports.register("gossip")
def _make_gossip(fed) -> GossipTransport:
    return GossipTransport(staleness=getattr(fed, "staleness", 0),
                           wire_dtype=getattr(fed, "wire_dtype", "f32"))


# Back-compat view of the pre-registry tuple (iterates names).
TRANSPORTS = transports.view()


def make_transport(fed) -> Any:
    """Build the transport a :class:`repro.configs.base.FedConfig` asks
    for — a pure ``repro.registry.transports`` lookup; registering a new
    transport factory makes it constructible here (and selectable from
    the CLI) with no edits."""
    wire_codec(getattr(fed, "wire_dtype", "f32"))     # validate early
    return transports.get(getattr(fed, "transport", "dense"))(fed)


# --------------------------------------------------------------------------
# Mesh mode: the ring transport inside shard_map (one node per fed shard).
# --------------------------------------------------------------------------

def ring_exchange_shard(vec: jax.Array, eta_prev: jax.Array,
                        eta_next: jax.Array, gamma,
                        axis: str | Sequence[str], *,
                        wire_dtype: str = "f32", shards: int = 1,
                        perms=None) -> jax.Array:
    """Eq. 5 on the physical ring for ONE node's flat ``(P,)`` vector
    (inside ``shard_map`` over the fed mesh axes).

    The vector is split into LANE-aligned column chunks and every chunk
    is ppermuted in both directions up front — XLA lowers these to async
    collective-permute pairs, so the Pallas/VPU mix of chunk j overlaps
    the transfer of chunk j+1. ``shards=1`` degenerates to ONE ppermute
    per direction per round (vs. one per pytree leaf in the seed path).

    The mesh path currently supports pure-cast wire codecs (the chunked
    ppermute moves one array per chunk; codecs with side information
    need a packed representation — see ROADMAP).

    ``perms``: optional precomputed (fwd, bwd) (src, dst) pairs from
    :func:`repro.launch.mesh.fed_ring_perms`; derived from the axis
    sizes when omitted.
    """
    from repro.core.consensus import ring_neighbors

    wire = vec.astype(_wire_dtype(wire_dtype))
    n = flatten.column_shards(wire.shape[-1], shards)
    chunks = jnp.split(wire, n, axis=-1) if n > 1 else [wire]
    # issue every transfer before any mix so they can all be in flight
    moved = [ring_neighbors(c, axis, perms=perms) for c in chunks]
    g = jnp.asarray(gamma, vec.dtype)
    ep = eta_prev.astype(vec.dtype)
    en = eta_next.astype(vec.dtype)
    outs = []
    for c, (w_prev, w_next) in zip(jnp.split(vec, n, axis=-1)
                                   if n > 1 else [vec], moved):
        w_self = (c.astype(_wire_dtype(wire_dtype))
                  .astype(vec.dtype))
        outs.append(c + g * (ep * (w_prev.astype(vec.dtype) - w_self)
                             + en * (w_next.astype(vec.dtype) - w_self)))
    return outs[0] if n == 1 else jnp.concatenate(outs, axis=-1)
