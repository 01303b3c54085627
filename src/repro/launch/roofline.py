"""Roofline terms of a compiled program, per chip.

    compute term    = HLO_FLOPs / peak_FLOP/s
    memory term     = HLO_bytes / HBM_bw
    collective term = wire_bytes / link_bw

Collective bytes are weighted by a wire factor (ring all-reduce moves
~2x the buffer; the others ~1x). The per-chip peaks come from
:data:`PEAKS`, keyed by the device's ``device_kind``; a kind without
published peaks is an error, never a default.

The CONSENSUS share of the collective term is transport-aware: the
selected ``repro.core.transport`` backend may put half the bytes on the
wire (bf16) or restrict links to the physical ring —
:func:`transport_consensus_bytes` prices the exchange from the
transport's own ``wire_bytes(layout)`` (see ``Roofline.with_consensus``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from dataclasses import replace as dataclass_replace


@dataclass(frozen=True)
class Peaks:
    flops: float                 # bf16 FLOP/s per chip
    hbm_bw: float                # HBM bytes/s per chip
    ici_bw: float                # interconnect bytes/s per link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" (TPU v5e) — Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect
# over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of one chip of ``device_kind``; raises for a
    kind that is not in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {', '.join(sorted(PEAKS))})") from None


_WIRE_FACTOR = {"all-reduce": 2.0}          # ring AR ~2x; others ~1x


@dataclass
class CollectiveStats:
    bytes_by_op: dict = field(default_factory=dict)
    count_by_op: dict = field(default_factory=dict)

    @property
    def wire_bytes(self) -> float:
        return sum(_WIRE_FACTOR.get(op, 1.0) * b
                   for op, b in self.bytes_by_op.items())


def transport_consensus_bytes(transport, layout, adj) -> float:
    """Per-NODE per-round bytes the eq. 5 exchange puts on the wire for
    the selected transport backend.

    ``transport.wire_bytes(layout)`` is the per-link payload at the wire
    dtype (bf16 halves it; the ring transport's shifted-copy exchange
    and the dense matmul both move one payload per link); the graph's
    worst-node degree gives the link count. This replaces the dense-f32
    assumption baked into the compiled HLO's collective-permute bytes.
    """
    import numpy as np
    degree = float(np.asarray(adj).sum(axis=1).max())
    return degree * transport.wire_bytes(layout)


@dataclass
class Roofline:
    flops: float                 # per device
    hbm_bytes: float             # per device
    wire_bytes: float            # per device
    collectives: CollectiveStats
    model_flops: float           # analytic useful flops per device
    device_kind: str             # key into PEAKS

    def with_consensus(self, transport, layout, adj,
                       devices_per_node: int) -> "Roofline":
        """Re-price the consensus share of the collective term for the
        selected transport backend.

        The measured collective-permute bytes (the consensus ring's
        neighbour shifts) are swapped for :func:`transport_consensus_bytes` spread over
        the node's device group. Non-consensus collectives (TP
        all-reduce/all-gather) are untouched.
        """
        measured = self.collectives.bytes_by_op.get("collective-permute", 0)
        analytic = (transport_consensus_bytes(transport, layout, adj)
                    / max(devices_per_node, 1))
        return dataclass_replace(
            self, wire_bytes=self.wire_bytes - measured + analytic)

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(self.device_kind).flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / peaks(self.device_kind).ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)
