"""Vehicular mobility subsystem: time-varying consensus topologies.

C-DFL targets connected vehicles, but a frozen ring cannot express the
paper's actual setting — vehicles moving in and out of radio range
between rounds. This package closes that gap in three host-side stages,
each usable on its own:

    positions  = traces.trace(kind, R, K, ...)          # (R, K, 2) kinematics
    adj_stack  = links.radio_adjacency(positions, rng)  # (R, K, K) link weights
    etas       = mixing.eta_stack(adj_stack, rule, ...) # (R, K, K) mixing

:func:`scenario_stacks` composes them from a
:class:`repro.configs.base.MobilityConfig` and is what
``Trainer.run_rounds`` calls when ``FedConfig.mobility`` is set: the
returned eta/gamma stacks ride the round scan as per-round inputs (one
``(K, K)`` slice consumed per scanned step) instead of the hoisted
round-invariant weights of the static path.

Ring-transport caveat: ``RingShardTransport`` physically moves data only
along the ring, so under mobility its per-round graph is the RING GATED
BY RADIO RANGE — pass ``mask=topology.adjacency("ring", k)`` (done
automatically by the trainer) so out-of-range ring links drop but no
phantom non-ring links appear that the transport could never carry.
"""
from __future__ import annotations

import numpy as np
from jax import monitoring

from repro.configs.base import MobilityConfig
from repro.mobility import links, mixing, traces
from repro.mobility.links import (degree_stats, handover_stats,
                                  num_components, radio_adjacency,
                                  sparse_radio_stack)
from repro.mobility.mixing import (constant_sparse_stacks, constant_stacks,
                                   eta_stack, gamma_stack,
                                   masked_sparse_stack, sparse_eta_stack,
                                   sparse_gamma_stack,
                                   stack_variant_stacks)
from repro.mobility.traces import trace

__all__ = [
    "MobilityConfig", "adjacency_stack", "scenario_stacks",
    "sparse_scenario_stacks", "trace", "trace_from_zero", "radio_adjacency",
    "sparse_radio_stack", "handover_stats", "degree_stats",
    "num_components", "eta_stack", "gamma_stack", "sparse_eta_stack",
    "sparse_gamma_stack", "constant_stacks", "constant_sparse_stacks",
    "masked_sparse_stack", "stack_variant_stacks", "links", "mixing",
    "traces",
]


def trace_from_zero(mob: MobilityConfig, rounds: int, k: int,
                    start: int = 0) -> np.ndarray:
    """(start + rounds, K, 2) positions of rounds ``[0, start + rounds)``.

    A trace is deterministic per seed and keeps no state to resume from,
    so a window starting at ``start`` is regenerated from t=0 and the
    caller slices it. The rounds generated and the rounds the window
    uses are reported as the ``jax.monitoring`` scalars
    ``/cdfl/mobility/rounds_built`` and ``/cdfl/mobility/rounds_used``.
    """
    monitoring.record_scalar("/cdfl/mobility/rounds_built",
                             int(start) + int(rounds))
    monitoring.record_scalar("/cdfl/mobility/rounds_used", int(rounds))
    return trace(mob.kind, start + rounds, k,
                 speed=mob.speed, speed_jitter=mob.speed_jitter,
                 area=mob.area, dt=mob.dt, seed=mob.seed)


def adjacency_stack(mob: MobilityConfig, rounds: int, k: int,
                    mask: np.ndarray | None = None,
                    start: int = 0) -> np.ndarray:
    """(R, K, K) link-weight stack for a mobility scenario.

    ``mask``: optional static 0/1 adjacency intersected with every
    round's radio graph (the ring-transport physical constraint).
    ``start``: first round of the window — the trace is regenerated
    from t=0 (:func:`trace_from_zero`) and sliced, so a resumed run
    continues the same trajectory it left.
    """
    positions = trace_from_zero(mob, rounds, k, start)[start:]
    adj = radio_adjacency(positions, mob.radio_range,
                          link_quality=mob.link_quality,
                          min_quality=mob.min_quality)
    if mask is not None:
        adj = adj * np.asarray(mask, np.float32)[None]
    return adj


def scenario_stacks(mob: MobilityConfig, rounds: int, k: int, *,
                    rule: str, gamma_cap: float,
                    ratios=None, sizes=None,
                    mask: np.ndarray | None = None, start: int = 0):
    """Compose trace -> links -> mixing for one training run.

    Returns ``(etas (R, K, K), gammas (R,))`` device arrays ready to
    ride the ``run_rounds`` scan, covering rounds
    ``[start, start + rounds)`` of the scenario.
    """
    adj = adjacency_stack(mob, rounds, k, mask=mask, start=start)
    etas = eta_stack(adj, rule, ratios=ratios, sizes=sizes)
    return etas, gamma_stack(etas, gamma_cap)


def sparse_scenario_stacks(mob: MobilityConfig, rounds: int, k: int, *,
                           rule: str, gamma_cap: float, degree: int,
                           ratios=None, sizes=None,
                           mask: np.ndarray | None = None,
                           start: int = 0):
    """Sparse twin of :func:`scenario_stacks`: trace -> top-``degree``
    link rows -> sparse mixing, never materializing an ``(R, K, K)``
    stack (only one round's ``(K, K)`` distances are transient on the
    host). Returns ``(SparseEta (R, K, D), gammas (R,))`` ready to ride
    the ``run_rounds`` scan at O(R·K·D) memory.
    """
    positions = trace_from_zero(mob, rounds, k, start)[start:]
    idx, val = sparse_radio_stack(positions, mob.radio_range, degree,
                                  link_quality=mob.link_quality,
                                  min_quality=mob.min_quality, mask=mask)
    sp = sparse_eta_stack(idx, val, rule, ratios=ratios, sizes=sizes)
    return sp, sparse_gamma_stack(sp, gamma_cap)
