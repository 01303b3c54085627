"""Two-tier hierarchical mixing: dense intra-cluster consensus with a
cluster-local gamma + sparse inter-cluster leader consensus.

Why: ``topology.stable_gamma`` bounds the eq. 5 step size by the GLOBAL
densest neighborhood (gamma < 0.99/∇ with ∇ the max row sum), so at
city scale the whole fleet pays for its worst intersection. Hierarchy
breaks the coupling:

* **intra tier** — each mobility cluster (``repro.hierarchy.clustering``)
  mixes densely among its members under the cluster's OWN stability
  bound: ``gamma_c = min(cap, 0.99/∇_c)`` with ``∇_c`` the max row sum
  inside cluster c only. A sparse suburb cluster no longer shrinks its
  step because a downtown cluster is dense — the property the tests
  assert.
* **inter tier** — each cluster's elected leader
  (``repro.hierarchy.leaders``) mixes its post-intra aggregate with the
  leaders of radio-adjacent clusters, lowered onto the existing
  ``topology.SparseEta`` top-D path (non-leader rows are all-zero: the
  partition-safe pure-self-update convention). The inter tier runs at
  full precision — leader-to-leader exchange models the V2I backhaul,
  not the lossy V2V wire the codec prices.
* **re-merge bursts** — rounds where the cluster count DROPS (groups
  rejoined after a partition) run ``burst`` extra intra passes under
  ``lax.cond``, the scan-resident form of the
  ``consensus.simulate_rounds`` post-partition catch-up; non-burst
  rounds pay nothing (only the taken branch executes).

Everything is compiled once per run into a :class:`HierEta` pytree of
``(R, ...)`` stacks that ride the round scan as per-round xs exactly
like the mobility and fault stacks — zero per-round Python dispatch.
The device mix (:func:`hier_mix_flat`) is two gather-mix passes: the
per-node-gamma cluster mix (Pallas ``kernels/cluster_mix`` on TPU, the
``sparse_neighbor_sum`` XLA fallback elsewhere) and the standard sparse
leader mix.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: repro.mobility.mixing is imported lazily inside the functions
# that need it — importing it here closes the cycle
# mobility.mixing -> repro.core -> cdfl -> hierarchy.mixing when an
# entry point imports repro.mobility first.
from repro.core import flatten, topology
from repro.hierarchy import clustering, leaders

__all__ = [
    "HierEta", "hier_geometry", "build_hier_stacks", "hier_static_stacks",
    "hier_scenario_stacks", "constant_hier_stacks", "hier_mix_flat",
    "masked_hier_stack", "hier_gamma_stack",
]


class HierEta(NamedTuple):
    """Per-round two-tier mixing weights (a JAX pytree: ``(R, ...)``
    stacks slice per scanned round like :class:`topology.SparseEta`).

    The intra tier is "block-dense": every co-member link is kept
    (``Di`` = largest cluster size - 1), so within a cluster the sparse
    gather reproduces the dense mixing rule exactly — the block
    structure lives in the index table, which never points outside the
    member's cluster."""

    cluster: jax.Array          # (..., K) int32 cluster id per node
    intra: topology.SparseEta   # (..., K, Di) co-member weights
    gamma_node: jax.Array       # (..., K) f32 cluster-local step size
    inter: topology.SparseEta   # (..., K, Dx) leader rows, others zero
    burst: jax.Array            # (...,) f32 re-merge burst flag


# ---------------------------------------------------------------------------
# Host-side geometry: clusters, leaders, index tables (compiled once).
# ---------------------------------------------------------------------------

def hier_geometry(adj_stack: np.ndarray,
                  positions: np.ndarray | None, *,
                  max_cluster_size: int, leader_policy: str,
                  inter_degree: int, hysteresis: bool = True):
    """(R, K, K) link weights -> the round-stacked index geometry.

    Returns ``(cluster (R,K), leader_of (R,K), burst (R,), intra_idx,
    intra_w (R,K,Di), inter_idx, inter_w (R,K,Dx))`` — everything the
    jax-side :func:`build_hier_stacks` needs that does NOT depend on
    the (possibly traced) CND ratios. Like the mobility traces and
    fault plans this is computed for the full horizon and sliced by the
    caller, so resumed segments see the same clusters (hysteresis
    chains round to round)."""
    adj_stack = np.asarray(adj_stack, np.float32)
    rounds, k = adj_stack.shape[:2]
    cluster = clustering.cluster_stack(
        adj_stack, positions, max_cluster_size=max_cluster_size,
        hysteresis=hysteresis)
    leader_of = leaders.elect_leaders(cluster, adj_stack, positions,
                                      policy=leader_policy)
    burst = clustering.remerge_flags(cluster)
    largest = max(int(np.bincount(c).max()) for c in cluster)
    di = int(min(max(largest - 1, 1), k - 1))
    dx = int(min(max(int(inter_degree), 1), k - 1))
    intra_idx = np.zeros((rounds, k, di), np.int32)
    intra_w = np.zeros((rounds, k, di), np.float32)
    inter_idx = np.zeros((rounds, k, dx), np.int32)
    inter_w = np.zeros((rounds, k, dx), np.float32)
    eye = np.eye(k, dtype=bool)
    for t in range(rounds):
        c = cluster[t]
        # intra: keep every co-member radio link (di bounds the count
        # by construction, so this tier is dense within the block)
        w = adj_stack[t] * (c[:, None] == c[None, :])
        w[eye] = 0.0
        score = np.where(w > 0, w, -np.inf)
        idx = np.argpartition(score, -di, axis=1)[:, -di:]
        val = np.take_along_axis(w, idx, axis=1)
        intra_idx[t] = idx.astype(np.int32)
        intra_w[t] = val
        # inter: clusters are adjacent when ANY cross-member link is
        # up; the leader edge carries the strongest such link
        cmax_t = int(c.max()) + 1
        cw = np.zeros((cmax_t, cmax_t), np.float32)
        ii, jj = np.nonzero(adj_stack[t] > 0)
        cross = c[ii] != c[jj]
        np.maximum.at(cw, (c[ii[cross]], c[jj[cross]]),
                      adj_stack[t][ii[cross], jj[cross]])
        ldr = np.array([leader_of[t][np.flatnonzero(c == lab)[0]]
                        for lab in range(cmax_t)])
        for lab in range(cmax_t):
            nb = np.flatnonzero(cw[lab] > 0)
            if nb.size == 0:
                continue
            order = nb[np.argsort(-cw[lab, nb], kind="stable")][:dx]
            led = ldr[lab]
            inter_idx[t, led, :order.size] = ldr[order]
            inter_w[t, led, :order.size] = cw[lab, order]
    return (cluster, leader_of, burst, intra_idx, intra_w,
            inter_idx, inter_w)


# ---------------------------------------------------------------------------
# JAX-side weight construction (traceable: composes with traced ratios).
# ---------------------------------------------------------------------------

def _build_round(cluster, intra_idx, intra_w, inter_idx, inter_w, *,
                 rule: str, ratios, sizes, gamma_cap: float):
    """One round's weights from the index geometry.

    Intra weights apply the run's mixing rule on the cluster-restricted
    link rows (the same ``_sparse_rule`` the sparse format uses, so a
    cluster covering a node's whole neighborhood reproduces the dense
    rule exactly); the per-cluster gamma is ``topology.stable_gamma``
    restricted to each cluster's rows via a segment max. Inter rows
    row-normalize the cross-cluster link mass over the kept leaders."""
    from repro.mobility.mixing import _sparse_rule

    k = cluster.shape[0]
    intra_val = _sparse_rule(intra_idx, intra_w, rule, ratios, sizes)
    rowsum = intra_val.sum(axis=-1)
    maxrow = jax.ops.segment_max(rowsum, cluster, num_segments=k)
    gamma_c = jnp.minimum(jnp.asarray(gamma_cap, jnp.float32),
                          0.99 / jnp.maximum(maxrow, 1e-6))
    gamma_node = gamma_c[cluster]
    s = inter_w.sum(axis=-1, keepdims=True)
    inter_val = jnp.where(s > 0, inter_w / jnp.maximum(s, 1e-12), 0.0)
    intra = topology.SparseEta(intra_idx, intra_val)
    inter = topology.SparseEta(inter_idx, inter_val)
    return intra, gamma_node, inter, topology.stable_gamma(inter, gamma_cap)


def build_hier_stacks(geometry, *, rule: str, ratios, sizes,
                      gamma_cap: float):
    """Geometry stacks -> ``(HierEta (R, ...), gammas (R,))``.

    The returned ``gammas`` is the INTER-tier step-size stack — it
    rides the scan's existing ``(R,)`` gamma slot (and the ``gamma``
    metric); the intra tier's per-node gammas travel inside the
    :class:`HierEta`."""
    cluster, _, burst, intra_idx, intra_w, inter_idx, inter_w = geometry
    cluster = jnp.asarray(cluster, jnp.int32)
    intra, gamma_node, inter, gammas = jax.vmap(
        lambda c, i1, w1, i2, w2: _build_round(
            c, i1, w1, i2, w2, rule=rule, ratios=ratios, sizes=sizes,
            gamma_cap=gamma_cap)
    )(cluster, jnp.asarray(intra_idx), jnp.asarray(intra_w, jnp.float32),
      jnp.asarray(inter_idx), jnp.asarray(inter_w, jnp.float32))
    h = HierEta(cluster=cluster, intra=intra, gamma_node=gamma_node,
                inter=inter, burst=jnp.asarray(burst, jnp.float32))
    return h, gammas


def hier_static_stacks(adj, *, rule: str, ratios, sizes, gamma_cap: float,
                       max_cluster_size: int, leader_policy: str,
                       inter_degree: int, hysteresis: bool = True):
    """One static (K, K) graph -> a single-round ``(HierEta, gamma)``
    (no leading R axis; broadcast with :func:`constant_hier_stacks`).
    Traceable in ``ratios``/``sizes`` — the geometry depends only on
    the concrete adjacency, so this runs under jit (the per-round
    driver's ``_mixing``)."""
    geo = hier_geometry(np.asarray(adj)[None], None,
                        max_cluster_size=max_cluster_size,
                        leader_policy=leader_policy,
                        inter_degree=inter_degree, hysteresis=hysteresis)
    cluster, _, _, intra_idx, intra_w, inter_idx, inter_w = geo
    intra, gamma_node, inter, gamma = _build_round(
        jnp.asarray(cluster[0], jnp.int32), jnp.asarray(intra_idx[0]),
        jnp.asarray(intra_w[0], jnp.float32), jnp.asarray(inter_idx[0]),
        jnp.asarray(inter_w[0], jnp.float32), rule=rule, ratios=ratios,
        sizes=sizes, gamma_cap=gamma_cap)
    h = HierEta(cluster=jnp.asarray(cluster[0], jnp.int32), intra=intra,
                gamma_node=gamma_node, inter=inter,
                burst=jnp.zeros((), jnp.float32))
    return h, gamma


def hier_scenario_stacks(mob, rounds: int, k: int, *, rule: str,
                         gamma_cap: float, ratios, sizes,
                         max_cluster_size: int, leader_policy: str,
                         inter_degree: int, hysteresis: bool = True,
                         start: int = 0):
    """Compose trace -> links -> clusters -> leaders -> two-tier
    weights for one run: the hierarchical twin of
    ``mobility.scenario_stacks``. The trace AND the cluster assignment
    are computed from round 0 and sliced at ``start`` (hysteresis and
    re-merge flags chain round to round), so a resumed segment sees the
    same clusters an unsegmented run would."""
    from repro.mobility import links, trace_from_zero
    pos = trace_from_zero(mob, rounds, k, start)
    adj = links.radio_adjacency(pos, mob.radio_range,
                                link_quality=mob.link_quality,
                                min_quality=mob.min_quality)
    geo = hier_geometry(adj, pos, max_cluster_size=max_cluster_size,
                        leader_policy=leader_policy,
                        inter_degree=inter_degree, hysteresis=hysteresis)
    geo = tuple(g[start:] for g in geo)
    return build_hier_stacks(geo, rule=rule, ratios=ratios, sizes=sizes,
                             gamma_cap=gamma_cap)


def constant_hier_stacks(h: HierEta, gamma, rounds: int):
    """Broadcast a single-round :class:`HierEta` / scalar gamma to
    ``(R, ...)`` stacks — the static-topology case of the scan."""
    stack = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (rounds,) + a.shape), h)
    return stack, jnp.broadcast_to(jnp.asarray(gamma, jnp.float32),
                                   (rounds,))


def hier_gamma_stack(h: HierEta, gamma_cap: float) -> jax.Array:
    """(R,) inter-tier step sizes from a hierarchical stack (the
    ``run_rounds`` default when an explicit stack omits gammas)."""
    return jax.vmap(
        lambda i, v: topology.stable_gamma(topology.SparseEta(i, v),
                                           gamma_cap)
    )(h.inter.idx, h.inter.val)


# ---------------------------------------------------------------------------
# Device mix + fault composition.
# ---------------------------------------------------------------------------

def hier_mix_flat(buf: jax.Array, h: HierEta, gamma_inter, *,
                  wire=None, wire_self=None, use_kernel=None,
                  burst_passes: int = 1) -> jax.Array:
    """One round's two-tier consensus on the flat (K, P) buffer.

    1. intra: per-node-gamma cluster gather-mix over co-member wire
       payloads (``wire``/``wire_self`` carry the codec'd — possibly
       fault-overridden — payloads, like the dense transport's fault
       path; None mixes the clean buffer);
    2. inter: leaders sparse-mix their post-intra aggregates (full
       precision — see module docstring); non-leader rows are all-zero,
       an exact self-update;
    3. re-merge burst: ``burst_passes`` extra intra passes when this
       round's flag is set (``lax.cond`` — untaken branches cost
       nothing inside the scan).
    """
    out = flatten.cluster_mix_flat(buf, h.intra.idx, h.intra.val,
                                   h.gamma_node, use_kernel=use_kernel,
                                   wire=wire, wire_self=wire_self)
    out = flatten.sparse_mix_flat(out, h.inter.idx, h.inter.val,
                                  gamma_inter, use_kernel=use_kernel)
    if burst_passes > 0:
        def extra(b):
            for _ in range(burst_passes):
                b = flatten.cluster_mix_flat(
                    b, h.intra.idx, h.intra.val, h.gamma_node,
                    use_kernel=use_kernel)
            return b
        out = jax.lax.cond(h.burst > 0, extra, lambda b: b, out)
    return out


def masked_hier_stack(h: HierEta, link_mask) -> HierEta:
    """Compose a fault-plan ``(R, K, K)`` link mask into BOTH tiers
    (the hierarchical twin of ``mobility.mixing.masked_sparse_stack``):
    a crashed node's intra row drains to zero (pure self-update), its
    columns vanish from co-members' rows with mass-preserving renorm,
    and a crashed LEADER additionally drops out of the inter tier —
    its cluster simply skips inter-cluster mixing for the outage."""
    from repro.mobility.mixing import masked_sparse_stack

    return h._replace(intra=masked_sparse_stack(h.intra, link_mask),
                      inter=masked_sparse_stack(h.inter, link_mask))
