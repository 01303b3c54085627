"""The consensus ring over a federated mesh.

A federated mesh has axes ("fed","dp","tp") — one federated node (paper:
base station) per fed index, internally data-parallel (dp) and
tensor-parallel (tp) — or ("pod","fed","dp","tp"), with the consensus
ring spanning the (pod, fed) product so neighbor exchange crosses the
DCN exactly twice per round (ring wrap).

Functions, not module constants: importing this module never touches jax
device state.
"""
from __future__ import annotations

from jax.sharding import Mesh


def fed_ring_perms(mesh: Mesh) -> tuple[list, list]:
    """Forward/backward (src, dst) pairs for the consensus ring over the
    fed axes product — precomputed host-side once per mesh so shard_map
    bodies (consensus.ring_neighbors / transport.ring_exchange_shard)
    don't rebuild them on every call. The ring wraps across pods on the
    multi-pod mesh, crossing the DCN exactly twice per round."""
    n = fed_size(mesh)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def fed_size(mesh: Mesh) -> int:
    f = mesh.shape["fed"]
    if "pod" in mesh.axis_names:
        f *= mesh.shape["pod"]
    return f
