"""C-DFL trainer (paper Algorithm 2) — model-agnostic.

One federated **round** =
  1. exchange (params, CND bitmaps) with graph neighbors,
  2. consensus-mix with CND-derived weights (eqs. 5-7) — one fused
     flat-buffer operation (repro.core.flatten), not one einsum per leaf,
  3. ``local_steps`` Adam updates on local minibatches (eq. 8, ModelUpdate).

The trainer is generic over the model: it takes ``loss_fn(params, batch)``
and a per-node initializer. A loss returns a scalar, or ``(loss, aux)``
with ``aux`` a dict of counters that the scan sums over local steps and
nodes into each round's ``metrics["counters"]``. Node-stacked pytrees
(leading K dim) make the same code run vmapped on one host (simulation /
tests / paper repro) or under shard_map on a mesh (see
repro.launch.train).

Two drivers:
  * ``Trainer.round`` — one jit'd round on host-fed batches (seed path);
  * ``Trainer.run_rounds`` — device-resident multi-round scan: per-round
    batch indices pre-sampled with ``jax.random``, batches gathered on
    device from the resident datasets, the round-invariant mixing weights
    hoisted out of the loop, and the full round loop run under ONE
    ``jax.lax.scan`` with donated state buffers — no per-round jit
    dispatch and no host-numpy batch transfer.

The round pipeline is FLAT-RESIDENT for every algorithm (dpsgd
included): params and Adam moments live in lane-padded ``(K, P)``
buffers (``FedState.opt`` is a :class:`repro.optim.FlatAdamState`), the
consensus exchange and the scan carry operate on the buffers directly,
and params are packed once per run — not once per round. The LOCAL
STEPS run in flat space too, on every backend: one fused flat-Adam
pass a step updates the buffer and its moments, and only the
forward/backward reads pytree slice views of the buffer. dpsgd — which
gossips every SGD step, not once per round — mixes the resident buffer
between flat Adam steps.

Mixing weights come in two FORMATS (``FedConfig.mixing_format``):
dense ``(K, K)`` eta matrices (default, bit-identical to previous
builds) or sparse top-D ``topology.SparseEta`` idx/val pairs
(``(K, D)`` per round) — the city-scale representation. The sparse
stacks ride the same scan as per-round xs (SparseEta is a pytree), the
dense/gossip transports gather D neighbor rows instead of running the
(K,K)@(K,P) matmul, and fault link masks compile to sparse row edits.

How the exchange moves between nodes is pluggable: both drivers route
the flat-buffer mix through a ``repro.core.transport`` Transport (dense
fused matmul, ring-sharded neighbor shift, or bounded-delay gossip; any
registered wire codec), selected by ``FedConfig.transport`` or passed
explicitly to :func:`build_trainer`. The algorithm itself is a
``repro.registry.algorithms`` plugin: its spec names the mixing policy
the exchange uses and whether it routes through a transport at all.

Batch sampling is keyed on the ABSOLUTE round index (``state.round``):
round r's minibatch indices derive from ``fold_in(rng, r)`` regardless
of how the run is segmented, so checkpoint/resume through the
``repro.experiment`` Session reproduces an unsegmented run exactly.

WHAT graph the exchange runs on may change every round: the scan driver
consumes a precomputed ``(R, K, K)`` eta stack and ``(R,)`` gamma stack
as per-round scan inputs (``repro.mobility`` derives them from vehicle
kinematics when ``FedConfig.mobility`` is set; the static case
broadcasts the one hoisted graph, numerically identical to scanning a
round-invariant constant). All three transports consume the per-round
slice — gossip's stale snapshots mix with the CURRENT round's weights,
so a link that dropped since the snapshot was taken contributes nothing.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring
from jax.profiler import TraceAnnotation, annotate_function

from repro import registry
from repro.configs.base import FedConfig, HierarchyConfig, TrainConfig
from repro.core import flatten, sketch, topology
from repro.core import transport as transport_lib
from repro.faults import models as faults_lib
from repro.faults import robust as robust_lib
from repro.hierarchy import mixing as hier_lib
from repro.ingest import scenarios as ingest_scenarios
from repro.ingest import sketches as ingest_sketches
from repro.ingest import weighting as ingest_weighting
from repro.optim import flat_adam


# fleets whose parameters (all nodes) take more bytes than this run their
# local steps without unrolling (see ``build_trainer``)
UNROLL_BYTES = 256 * 2 ** 20


class FedState(NamedTuple):
    params: object            # pytree, leaves (K, ...)
    opt: object               # FlatAdamState with (K, P) moment buffers
    ratios: jax.Array         # (K,) CND distinct ratios Ë_k
    sizes: jax.Array          # (K,) raw dataset sizes E_k
    round: jax.Array          # int32
    tstate: Any = ()          # transport state (e.g. gossip snapshots)
    # fault-subsystem state: the previous round's entry buffer when a
    # straggle schedule may replay it, else () — an empty pytree, so
    # fault-free FedStates keep their pre-fault leaf layout (checkpoint
    # compatibility both ways)
    fstate: Any = ()
    # ingest-subsystem state: the per-node streaming sketches
    # (repro.ingest.sketches.SketchState) when a redundancy scenario is
    # active, else () — same empty-pytree convention as fstate, so
    # ingest-free FedStates keep their pre-ingest leaf layout
    istate: Any = ()


class Trainer(NamedTuple):
    init: Callable
    round: Callable           # (state, batches) -> (state, metrics)
    eta_fn: Callable          # state -> (K, K) mixing weights
    run_rounds: Callable      # (state, data, num_rounds[, rng]) -> (state, metrics)
    # (state, num_rounds) -> ((R, K, K) eta | SparseEta (R, K, D),
    # (R,) gamma): the per-round mixing stacks the scan driver consumes
    # (mobility-derived when FedConfig.mobility is set, broadcast
    # static weights otherwise; sparse under mixing_format='sparse')
    mixing_stack: Callable = None
    # batched fleet driver: V whole runs — (V,)-stacked FedState, shared
    # data, per-variant rng/eta/gamma/lr — under ONE vmapped scan (see
    # run_rounds_batch in build_trainer); None only on hand-built stubs
    run_rounds_batch: Callable = None
    # the same two drivers' programs, lowered and not run (compiled-HLO
    # inspection: which kernels a round runs); None on hand-built stubs
    lower_rounds: Callable = None
    lower_rounds_batch: Callable = None
    # (rng, start, ratios, sizes, num_rounds=, static_graph=) ->
    # (round keys, static stacks | None): the one compiled program that
    # builds both drivers' round-invariant inputs; None on stubs
    prepare: Callable = None


def _node_sketches(node_items, fed: FedConfig):
    """CND sketch per node: node_items (K, n, f) int feature tokens."""
    bitmaps = jax.vmap(
        lambda it: sketch.build_bitmaps(it, fed.cnd_hashes, fed.cnd_bits)
    )(node_items)
    ests = jax.vmap(lambda bm: sketch.cardinality(bm, fed.cnd_estimator))(
        bitmaps)
    totals = jnp.full((node_items.shape[0],), node_items.shape[1],
                      jnp.float32)
    ratios = jnp.clip(ests / jnp.maximum(totals, 1.0), 1e-6, 1.0)
    return ratios, totals


def build_trainer(loss_fn: Callable, fed: FedConfig, train: TrainConfig,
                  eval_fn: Optional[Callable] = None,
                  transport: Any = None) -> Trainer:
    """loss_fn(params, batch) -> scalar loss, or (loss, aux) with aux a
    dict of counters (summed over local steps and nodes into each round's
    ``metrics["counters"]``; a scalar loss traces the same program as
    ``(loss, {})``). batch leaves have no K dim (the trainer vmaps over
    nodes).

    The non-deprecated trainer constructor — what the algorithm plugins
    (``repro.core.baselines``) and the ``repro.experiment`` façade call.
    ``fed.algorithm`` selects a registered
    :class:`repro.registry.AlgorithmSpec`, whose ``mixing`` policy and
    ``uses_transport`` flag drive the assembly below.

    ``transport``: a ``repro.core.transport`` instance overriding the one
    ``fed.transport``/``fed.wire_dtype``/``fed.staleness`` select.
    fedavg (centralized server average) and dpsgd (per-step gossip)
    bypass the transport; see ``mix_buf``/``round_body``.

    The local steps run on the flat buffer: params and Adam moments
    never leave the (K, P) buffers, and gradients are packed once per
    step. Sub-f32 param leaves (bf16) read their slice views at leaf
    dtype while the buffer keeps the f32 master between steps; the
    FedState's params are cast to leaf dtype only at unpack.
    """
    registry.ensure_plugins()
    spec = registry.algorithms.get(fed.algorithm)
    adj = jnp.asarray(topology.adjacency(fed.topology, fed.num_nodes))
    if fed.algorithm == "fedavg":
        adj = jnp.asarray(topology.adjacency("full", fed.num_nodes))
    uses_transport = spec.uses_transport
    mix_rule = spec.mixing
    mobile = fed.mobility is not None and fed.mobility.kind != "static"
    # Fault injection / robust mixing operate on the once-per-round
    # full-buffer wire exchange, which fedavg (server average), dpsgd
    # (per-step gossip) and cdfa_m (prefix-only wire) don't have.
    fault_capable = uses_transport and fed.algorithm != "cdfa_m"
    if fed.faults is not None and fed.faults.active and not fault_capable:
        raise ValueError(
            f"{fed.algorithm} has no full-buffer wire exchange to "
            f"inject faults into (fault injection supports the "
            f"transport-routed algorithms: cdfl, cfa, metropolis, ...)")
    # ``faulty`` drives the trainer ASSEMBLY: a FaultConfig whose every
    # selected kind has zero rate compiles to a guaranteed no-op, and
    # the trainer then builds the exact fault-free graph (bit-identical
    # runs) — the decision is config-static so every resumed segment of
    # a run agrees on the scan-carry structure.
    faulty = (fed.faults is not None
              and faults_lib.config_active(fed.faults))
    has_byz, has_corrupt, has_straggle = (
        faults_lib.wire_kinds(fed.faults) if faulty
        else (False, False, False))
    if mobile and fed.algorithm == "fedavg":
        # fedavg is the centralized reference: a server average has no
        # inter-vehicle links to churn
        raise ValueError("fedavg (centralized server average) does not "
                         "model a vehicular topology; mobility requires "
                         "a decentralized algorithm")
    if transport is None:
        if uses_transport:
            transport = transport_lib.make_transport(fed)
        else:
            # these algorithms have no once-per-round buffer exchange to
            # route; reject non-default transport config rather than
            # silently running something else than what was asked for
            cfg = (fed.transport, fed.wire_dtype, fed.staleness)
            if cfg != ("dense", "f32", 0):
                raise ValueError(
                    f"{fed.algorithm} does not use the consensus "
                    f"transport (fedavg: server average; dpsgd: per-step "
                    f"gossip) — got transport={fed.transport}/"
                    f"{fed.wire_dtype}/staleness={fed.staleness}")
            transport = transport_lib.DenseTransport()
    # Byzantine-robust mixing replaces the eq. 5 exchange with a
    # coordinate-wise order statistic over neighbor rows — it needs
    # every neighbor's payload materialized, which only the dense
    # transport provides (ring shifts / gossip snapshots don't).
    robust_fn = robust_lib.make_robust(fed)
    if robust_fn is not None:
        if not fault_capable:
            raise ValueError(
                f"{fed.algorithm} has no full-buffer wire exchange for "
                f"robust aggregation to replace")
        if not isinstance(transport, transport_lib.DenseTransport):
            raise ValueError(
                "robust aggregation needs every neighbor row "
                "materialized: use the dense transport "
                f"(got {type(transport).__name__})")
    # Redundancy-aware ingest: like ``faulty`` above, the decision is
    # config-static — ``scenario="none"`` (or ingest=None) builds the
    # exact pre-ingest graph, bit-identical runs.
    ingest_cfg = fed.ingest
    ingest_on = ingest_cfg is not None and ingest_cfg.active
    ingest_plans: dict = {}       # max_items -> (src_node, src_slot, hashes)

    @jax.jit
    def _ingest_gather(data, src_node, src_slot):
        # the device half of the ingest plan, named like its host span
        with jax.named_scope("cdfl.ingest_plan"):
            return jax.tree.map(lambda a: a[src_node, src_slot], data)

    if ingest_on and (ingest_cfg.reweight_mixing or ingest_cfg.drift_on):
        # both the redundancy reweight and the drift-detection column
        # discount rescale eta inside the scan — same composition rules
        if fed.algorithm == "fedavg":
            raise ValueError(
                "fedavg (centralized server average) has no eta rows "
                "for the redundancy reweight / drift discount to scale; "
                "use IngestConfig(weighting='sampling', "
                "drift_threshold=0) or a decentralized algorithm")
        if robust_fn is not None:
            raise ValueError(
                "robust aggregation ranks neighbor rows by order "
                "statistics — the redundancy eta reweight / drift "
                "discount does not compose with it (use IngestConfig("
                "weighting='sampling'|'none', drift_threshold=0))")
    # Every algorithm runs the flat-resident pipeline: params AND Adam
    # moments live in (K, P) FedState buffers, and the consensus
    # exchange, the local steps (dpsgd's per-step gossip included) and
    # the scan carry all operate on them.
    fopt = flat_adam(train.learning_rate, train.beta1, train.beta2,
                     train.eps, train.weight_decay, train.grad_clip)
    fmt = getattr(fed, "mixing_format", "dense")
    sparse_fmt = fmt == "sparse"
    hier_fmt = fmt == "hierarchical"
    # hierarchy knobs default when the format is selected bare; the
    # intra tier inherits the algorithm's mixing rule unless pinned
    hier_cfg = ((fed.hierarchy or HierarchyConfig()) if hier_fmt
                else None)
    hier_rule = (hier_cfg.intra_rule or mix_rule) if hier_fmt else None
    if hier_fmt and not isinstance(transport, transport_lib.DenseTransport):
        raise ValueError(
            "mixing_format='hierarchical' needs the dense transport's "
            "resident buffer (co-member + leader gathers); got "
            f"{type(transport).__name__}")
    # Partially unrolling the local-step scan lets XLA build larger fusion
    # clusters (fewer per-op dispatches) without decode-time blowup;
    # unroll 4 measures ~10% over unroll 2 on the flat-resident loop
    # (the slice-view/grad-pack ops of adjacent steps fuse). A fleet
    # whose parameters pass UNROLL_BYTES steps one at a time: its steps
    # are long, and each unrolled step keeps parameter-sized gradients
    # and packs alive (3.8 GB more at K = 2 x 267M parameters, compiled
    # for a v5e).
    local_unroll = max(1, min(4, fed.local_steps))

    def unroll_for(params) -> int:
        size = sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(params))
        return local_unroll if size <= UNROLL_BYTES else 1

    def eta_fn(state: FedState) -> jax.Array:
        return topology.mixing_weights(adj, mix_rule,
                                       ratios=state.ratios,
                                       sizes=state.sizes)

    def init(rng: jax.Array, init_params_fn: Callable,
             node_items: jax.Array, same_init: bool = True) -> FedState:
        k = fed.num_nodes
        if same_init:
            p0 = init_params_fn(rng)
            params = jax.tree.map(
                lambda l: jnp.broadcast_to(l, (k,) + l.shape).copy(), p0)
        else:
            params = jax.vmap(init_params_fn)(jax.random.split(rng, k))
        ratios, sizes = _node_sketches(node_items, fed)
        tstate = ()
        fstate = ()
        # ONE pack serves both the flat Adam moments and (when the
        # transport keeps state, e.g. gossip snapshots) init_state
        buf, layout = flatten.flatten(params)
        opt_state = fopt.init(buf)
        if uses_transport and getattr(transport, "stateful", True):
            wire = buf
            if fed.algorithm == "cdfa_m":
                prefix = flatten.prefix_length(layout,
                                               fed.cdfa_fraction)
                wire = buf[:, :prefix]
            tstate = transport.init_state(wire)
        if has_straggle:
            # a round-0 straggler replays the init broadcast; rides
            # the FedState so checkpoint/resume replays the same
            # stale payloads as an unbroken run
            fstate = buf
        istate = (ingest_sketches.init_state(k, ingest_cfg)
                  if ingest_on else ())
        return FedState(params, opt_state, ratios, sizes,
                        jnp.zeros((), jnp.int32), tstate, fstate, istate)

    def _loss_aux(p, batch):
        out = loss_fn(p, batch)
        return out if isinstance(out, tuple) else (out, {})

    value_and_grad = jax.value_and_grad(_loss_aux, has_aux=True)

    # ``lr=None`` throughout the step machinery keeps the TrainConfig
    # rate baked in at trace time (the single-run path — bit-identical
    # to previous builds); a traced scalar overrides it at runtime so
    # the batched driver can vmap V learning rates through ONE program.

    def _local_step(vec, ost, batch, layout, lr=None):
        """One local Adam step with params resident in the flat (P,)
        vector: the forward/backward reads pytree slice VIEWS of the
        buffer, the gradient pytree is flattened ONCE, and the fused
        flat-Adam pass updates vector and moments in place."""
        with jax.named_scope("cdfl.grad"):
            p = flatten.unflatten_one(vec, layout)
            (loss, aux), grads = value_and_grad(p, batch)
            gvec = flatten.pack_node(grads, layout)
        with jax.named_scope("cdfl.adam"):
            vec, ost = fopt.update(gvec, ost, vec, lr=lr)
        return vec, ost, loss, aux

    # ONE loop scaffold serves both batch sources: step3(vec, ost, batch)
    # -> (vec, ost, loss, aux); the loop returns each node's mean loss and
    # its aux summed over steps.

    def _run_local_steps(step3, p0, o0, batches):
        """vmap over nodes of a scan over local steps.
        batches: pytree, leaves (K, S, B, ...)."""
        def one_node(p, o, bs):
            def step(carry, batch):
                p, o, loss, aux = step3(*carry, batch)
                return (p, o), (loss, aux)
            (p, o), (losses, aux) = jax.lax.scan(step, (p, o), bs,
                                                 unroll=unroll_for(p0))
            return p, o, losses.mean(), jax.tree.map(
                lambda a: a.sum(0), aux)
        return jax.vmap(one_node)(p0, o0, batches)

    def _run_local_steps_from_idx(step3, p0, o0, data, idx):
        """Like :func:`_run_local_steps`, but gathers each minibatch on
        device from the resident datasets one step at a time
        (idx: (K, S, B)) — no (K, S, B, ...) round-batch intermediate is
        ever materialized."""
        def one_node(p, o, nd, ni):
            def step(carry, i):
                batch = jax.tree.map(lambda a: a[i], nd)
                p, o, loss, aux = step3(*carry, batch)
                return (p, o), (loss, aux)
            (p, o), (losses, aux) = jax.lax.scan(step, (p, o), ni,
                                                 unroll=unroll_for(p0))
            return p, o, losses.mean(), jax.tree.map(
                lambda a: a.sum(0), aux)
        return jax.vmap(one_node)(p0, o0, data, idx)

    def local_updates(buf, opt_state, layout, batches):
        return _run_local_steps(
            lambda v, o, b: _local_step(v, o, b, layout),
            buf, opt_state, batches)

    def local_updates_from_idx(buf, opt_state, layout, data, idx,
                               lr=None):
        return _run_local_steps_from_idx(
            lambda v, o, b: _local_step(v, o, b, layout, lr=lr),
            buf, opt_state, data, idx)

    # -- dpsgd (Lian et al. 17): gossip-average every SGD step ---------------
    # The per-step mix couples the nodes, so dpsgd cannot vmap a
    # per-node scan like the scaffolds above: it scans over STEPS with
    # the node axis inside (mix across nodes, then one vmapped Adam
    # step), mixing the resident (K, P) buffer between fused flat-Adam
    # steps.

    def _dpsgd_mix(buf, eta, gamma):
        """Per-step gossip on the (K, P) buffer — dense delta-form
        mix, the sparse top-D gather, or the two-tier hierarchical mix,
        matching the wire format."""
        if hier_fmt:
            # no re-merge burst per STEP: dpsgd already mixes
            # local_steps times a round, which IS the catch-up
            return hier_lib.hier_mix_flat(buf, eta, gamma,
                                          burst_passes=0)
        if isinstance(eta, topology.SparseEta):
            return flatten.sparse_mix_flat(buf, eta.idx, eta.val, gamma)
        return flatten.mix_flat(buf, eta, gamma)

    def _dpsgd_steps(step_all, p0, o0, xs):
        def step(carry, x):
            p, o, loss = step_all(*carry, x)
            return (p, o), loss
        (p, o), losses = jax.lax.scan(step, (p0, o0), xs,
                                      unroll=unroll_for(p0))
        return p, o, losses.mean() * jnp.ones((fed.num_nodes,))

    def _dpsgd_step(buf, ost, batch, eta, gamma, layout, lr=None):
        buf = _dpsgd_mix(buf, eta, gamma)
        buf, ost, losses, _ = jax.vmap(
            lambda v, o, b: _local_step(v, o, b, layout, lr=lr)
        )(buf, ost, batch)
        return buf, ost, losses.mean()

    def dpsgd_updates(buf, opt_state, layout, eta, gamma, batches):
        """One dpsgd round on host-fed batches (leaves (K, S, B, ...))."""
        bt = jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), batches)
        return _dpsgd_steps(
            lambda v, o, b: _dpsgd_step(v, o, b, eta, gamma, layout),
            buf, opt_state, bt)

    def dpsgd_updates_from_idx(buf, opt_state, layout, eta, gamma,
                               data, idx, lr=None):
        """Scan-driver dpsgd round: each step gathers its minibatches
        on device from the resident datasets (idx: (K, S, B))."""
        def batch_of(i):  # i: (K, B) this step's per-node indices
            return jax.tree.map(
                lambda a: jax.vmap(lambda ad, j: ad[j])(a, i), data)
        return _dpsgd_steps(
            lambda v, o, i: _dpsgd_step(v, o, batch_of(i), eta,
                                             gamma, layout, lr=lr),
            buf, opt_state, jnp.swapaxes(idx, 0, 1))

    def mix_buf(buf, sizes, eta, gamma, layout, tstate, rnd, sent=None):
        """The round's consensus exchange on the flat (K, P) buffer,
        routed through the selected transport. ``sent`` (fault
        injection) overrides the per-node wire payloads — ``None`` means
        every node broadcasts its clean buffer, the fault-free path.
        Returns (buf, tstate)."""
        if fed.algorithm == "fedavg":
            # centralized reference: server average, weights E_i/sum E —
            # not a decentralized exchange, so no transport
            w = sizes / sizes.sum()
            a = jnp.broadcast_to(w[None, :],
                                 (fed.num_nodes, fed.num_nodes))
            return flatten.apply_matrix_flat(buf, a), tstate
        if fed.algorithm == "cdfa_m":
            # C-DFA(M): only the leaf-prefix columns travel the wire; a
            # prefix ending inside a lane tile takes the XLA mix
            prefix = flatten.prefix_length(layout, fed.cdfa_fraction)
            tr = transport
            if hasattr(transport, "use_kernel"):
                tr = dataclasses.replace(
                    transport, use_kernel=flatten.prefix_use_kernel(
                        prefix, transport.use_kernel))
            head, tstate = tr.exchange(buf[:, :prefix], eta, gamma,
                                       tstate, rnd)
            return jnp.concatenate([head, buf[:, prefix:]], axis=1), tstate
        if hier_fmt:
            # two-tier cluster consensus: codec the wire payloads the
            # way the dense transport's fault path does (neighbor terms
            # read the — possibly fault-overridden — codec'd frames,
            # the self-cancellation keeps the node's own clean payload),
            # then run intra + leader tiers + re-merge burst in one shot
            w_nb, w_self = transport_lib._wire_pair(transport.codec, buf,
                                                    sent)
            mixed = hier_lib.hier_mix_flat(
                buf, eta, gamma, wire=w_nb, wire_self=w_self,
                use_kernel=getattr(transport, "use_kernel", None),
                burst_passes=hier_cfg.remerge_burst)
            return mixed, tstate
        if robust_fn is not None:
            # order-statistic consensus over the neighborhood payloads
            # (codec'd like any wire traffic) instead of eq. 5
            payload = buf if sent is None else sent
            codec = transport.codec
            if not transport_lib._cast_noops(codec, buf):
                payload = codec.roundtrip(payload)
            return robust_fn(buf, payload, eta, gamma), tstate
        # cdfl, cfa, metropolis — eq. (5)
        return transport.exchange(buf, eta, gamma, tstate, rnd, sent=sent)

    def _flat_metrics(buf, layout, loss, gamma, aux=None):
        """Round metrics straight off the resident buffer — the
        disagreement is one pass over (K, P), and eval reads the params
        through slice views (no materialized unpack). The loss's
        counters (``aux``, per node) are summed over the nodes."""
        metrics = {
            "loss": loss,
            "disagreement": flatten.disagreement_flat(buf, layout.total),
            "gamma": gamma,
        }
        if aux:
            metrics["counters"] = jax.tree.map(lambda a: a.sum(0), aux)
        if eval_fn is not None:
            metrics["eval"] = jax.vmap(eval_fn)(
                flatten.unflatten_views(buf, layout))
        return metrics

    def round_body(state: FedState, batches, eta, gamma):
        """One full round given precomputed mixing weights. The consensus
        exchange runs on the flat buffer (one fused (K,K)@(K,P) mix)."""
        # flat-resident round: ONE pack at entry, the mix and the local
        # Adam steps on the (K, P) buffer, ONE unpack into the returned
        # FedState
        layout = flatten.make_layout(state.params)
        buf, _ = flatten.flatten(state.params, layout)
        aux = {}
        if fed.algorithm == "dpsgd":
            tstate = state.tstate
            buf, opt_state, loss = dpsgd_updates(buf, state.opt, layout,
                                                 eta, gamma, batches)
        else:
            mixed, tstate = mix_buf(buf, state.sizes, eta, gamma, layout,
                                    state.tstate, state.round)
            buf, opt_state, loss, aux = local_updates(
                mixed, state.opt, layout, batches)
        metrics = _flat_metrics(buf, layout, loss, gamma, aux)
        new_state = FedState(flatten.unflatten(buf, layout), opt_state,
                             state.ratios, state.sizes,
                             state.round + 1, tstate, state.fstate,
                             state.istate)
        return new_state, metrics

    def _mixing(ratios, sizes, cap: Optional[float] = None):
        cap = fed.gamma if cap is None else cap
        if hier_fmt:
            # the index geometry depends only on the concrete static
            # adjacency (a trace constant), so this is jit-traceable in
            # the CND ratios like the dense rule
            return hier_lib.hier_static_stacks(
                adj, rule=hier_rule, ratios=ratios, sizes=sizes,
                gamma_cap=cap,
                max_cluster_size=hier_cfg.max_cluster_size,
                leader_policy=hier_cfg.leader_policy,
                inter_degree=hier_cfg.inter_degree,
                hysteresis=hier_cfg.hysteresis)
        eta = topology.mixing_weights(adj, mix_rule, ratios=ratios,
                                      sizes=sizes)
        gamma = topology.stable_gamma(eta, cap)
        if sparse_fmt:
            # sparsify AFTER the stability bound: the top-D renorm
            # preserves row sums, so the bound computed on the dense
            # matrix is the bound of the sparse one
            return topology.sparsify_eta(eta, fed.degree), gamma
        return eta, gamma

    def _static_stacks(ratios, sizes, num_rounds: int, cap: float):
        """The static graph's eta and gamma broadcast to ``num_rounds``
        rounds, in the trainer's mixing format."""
        from repro import mobility as mobility_lib
        eta, gamma = _mixing(ratios, sizes, cap)
        if hier_fmt:
            return hier_lib.constant_hier_stacks(eta, gamma, num_rounds)
        if sparse_fmt:
            return mobility_lib.constant_sparse_stacks(eta, gamma,
                                                       num_rounds)
        return mobility_lib.constant_stacks(eta, gamma, num_rounds)

    def round_fn(state: FedState, batches):
        if mobile:
            raise ValueError(
                "FedConfig.mobility is set but Trainer.round trains on "
                "the frozen static graph — time-varying topologies ride "
                "the run_rounds scan")
        if faulty:
            raise ValueError(
                "FedConfig.faults is set but Trainer.round drives one "
                "round at a time — fault schedules (and the in-scan "
                "self-healing guard) ride the run_rounds scan")
        if ingest_on:
            raise ValueError(
                "FedConfig.ingest is set but Trainer.round drives one "
                "round at a time — the streaming-redundancy sketches "
                "ride the run_rounds scan")
        eta, gamma = _mixing(state.ratios, state.sizes)
        return round_body(state, batches, eta, gamma)

    @partial(annotate_function, name="cdfl.mixing_stack")
    def mixing_stack(state: FedState, num_rounds: int, start: int = 0,
                     *, mobility="config",
                     gamma_cap: Optional[float] = None):
        """Per-round mixing for the scan driver: ``(R, K, K)`` eta and
        ``(R,)`` gamma — or, under ``mixing_format='sparse'``, a
        ``topology.SparseEta`` with ``(R, K, D)`` stacks (built straight
        from the radio-range graphs; no dense ``(R, K, K)`` intermediate
        is ever materialized). Static topology broadcasts the one
        hoisted graph; a mobility scenario re-derives radio-range links
        every round (ring transport: gated to the physical ring — links
        the transport cannot carry never appear). ``start`` offsets into
        the kinematic trace: a run resumed at round r continues the SAME
        trajectory, so a segmented run equals an unsegmented one.

        ``mobility`` / ``gamma_cap`` override the config's own scenario
        and step-size cap for THIS stack only — how batched sweeps build
        per-variant stacks against one shared trainer (the sentinel
        ``"config"`` keeps ``fed.mobility``; pass ``None`` to force the
        static graph)."""
        from repro import mobility as mobility_lib
        mob = fed.mobility if mobility == "config" else mobility
        cap = fed.gamma if gamma_cap is None else float(gamma_cap)
        if mob is None or mob.kind == "static":
            return _static_stacks(state.ratios, state.sizes, num_rounds,
                                  cap)
        if hier_fmt:
            return hier_lib.hier_scenario_stacks(
                mob, num_rounds, fed.num_nodes, rule=hier_rule,
                gamma_cap=cap, ratios=state.ratios,
                sizes=state.sizes,
                max_cluster_size=hier_cfg.max_cluster_size,
                leader_policy=hier_cfg.leader_policy,
                inter_degree=hier_cfg.inter_degree,
                hysteresis=hier_cfg.hysteresis, start=start)
        if sparse_fmt:
            # ring+sparse is rejected at config validation, so no mask
            return mobility_lib.sparse_scenario_stacks(
                mob, num_rounds, fed.num_nodes, rule=mix_rule,
                gamma_cap=cap, degree=fed.degree,
                ratios=state.ratios, sizes=state.sizes, start=start)
        mask = None
        if isinstance(transport, transport_lib.RingShardTransport):
            mask = topology.adjacency("ring", fed.num_nodes)
        return mobility_lib.scenario_stacks(
            mob, num_rounds, fed.num_nodes, rule=mix_rule,
            gamma_cap=cap, ratios=state.ratios, sizes=state.sizes,
            mask=mask, start=start)

    def _freeze_rows(new, old, keep):
        """Per-node where over a pytree whose every leaf has the node
        axis leading: frozen nodes keep their round-entry values."""
        return jax.tree.map(
            lambda n, o: jnp.where(
                keep.reshape((keep.shape[0],) + (1,) * (n.ndim - 1)),
                n, o),
            new, old)

    def _scan_rounds_impl(state: FedState, data, round_keys: jax.Array,
                          num_rounds: int, max_items: int, node_sizes,
                          etas, gammas, fault_xs, slot_hashes, lr=None):
        # Each phase runs under a ``cdfl.<phase>`` named scope, which
        # reaches the compiled program only as op metadata: the device
        # trace can then charge every operation to its phase.
        #
        # (R, K, S, B) minibatch indices for ALL rounds, sampled on
        # device from per-round keys folded on the ABSOLUTE round index
        # (run_rounds derives them) — segmenting a run cannot change
        # which batches any round sees.
        with jax.named_scope("cdfl.sample"):
            shape = (fed.num_nodes, fed.local_steps, train.batch_size)
            if ingest_on and ingest_cfg.correct_sampling:
                # multiplicity-corrected sampling: pre-sample UNIFORMS
                # with the same absolute-round keying and transform them
                # inside the body through the CURRENT sketch's
                # inverse-multiplicity CDF (the weights evolve with the
                # stream, so the transform cannot be hoisted out of the
                # scan)
                idx = jax.vmap(
                    lambda k: jax.random.uniform(k, shape))(round_keys)
            elif node_sizes is None:
                idx = jax.vmap(
                    lambda k: jax.random.randint(k, shape, 0, max_items)
                )(round_keys)
            else:
                # ragged per-node datasets (padded to a common N):
                # uniform over each node's true item count
                u = jax.vmap(
                    lambda k: jax.random.uniform(k, shape))(round_keys)
                idx = jnp.minimum(
                    (u * node_sizes[None, :, None, None]).astype(jnp.int32),
                    node_sizes.astype(jnp.int32)[None, :, None, None] - 1)
        # The mixing weights ride the scan as PER-ROUND inputs: slice r
        # of the (R, K, K) eta stack (and (R,) gamma) is consumed by
        # round r's exchange. A constant stack (static topology) is
        # numerically identical to the hoisted round-invariant weights;
        # a mobility stack changes the graph under the scan for free.

        # The scan carry is flat end to end: the (K, P) param buffer,
        # the Adam moments, and the transport state (e.g. gossip
        # snapshots) — all donated. Params are packed ONCE before the
        # scan and unpacked ONCE after it; the post-local-step
        # write-back IS the buffer the next round's mix consumes (no
        # per-round pack/unpack pass). The moments ride the carry as
        # (K, P) buffers and only the forward/backward reads pytree
        # slice views.
        with jax.named_scope("cdfl.pack"):
            layout = flatten.make_layout(state.params)
            buf0, _ = flatten.flatten(state.params, layout)
        # ``fault_xs`` is () on the fault-free path (the scan carry and
        # body then trace to exactly the pre-fault graph) or the
        # per-round (health, byz, corrupt, straggle) stacks — the
        # structure is config-static, so every segment of a run agrees.
        use_faults = fault_xs != ()
        prev0 = ()
        if use_faults and has_straggle:
            prev0 = (buf0 if isinstance(state.fstate, tuple)
                     else state.fstate)
        # the streaming sketches ride the carry like the transport
        # state; () on the ingest-free path (structure is config-static,
        # so every resumed segment agrees — same gating as fault_xs)
        ing0 = state.istate if ingest_on else ()

        def body(carry, xs):
            idx_r, eta_r, gamma_r, f_r = xs
            buf, opt_state, rnd, tstate, prev, ist = carry
            entry_buf, entry_opt = buf, opt_state
            est = ()
            novelty = ()
            if ingest_on:
                with jax.named_scope("cdfl.ingest"):
                    mult = None
                    if ingest_cfg.correct_sampling:
                        # weights from the ENTRY sketch (round 0: empty
                        # counters -> uniform), then fold this round's
                        # samples in — no same-round feedback loop
                        mult = ingest_sketches.multiplicity(
                            ist.cm, slot_hashes.buckets)
                        w = ingest_weighting.sampling_weights(
                            mult, node_sizes, max_items)
                        idx_r = ingest_weighting.weighted_indices(idx_r, w)
                    if ingest_cfg.drift_on:
                        # drift signal: fraction of the FINAL sampled
                        # slots the ENTRY (decayed) sketch has never
                        # seen. Gated on the sketch having streamed
                        # anything, so the empty round-0 counters don't
                        # read as a regime change on every node at once.
                        if mult is None:
                            mult = ingest_sketches.multiplicity(
                                ist.cm, slot_hashes.buckets)
                        novelty = jnp.where(
                            ist.seen > 0,
                            ingest_weighting.drift_novelty(mult, idx_r),
                            0.0)
                    ist = ingest_sketches.update(ist, slot_hashes, idx_r,
                                                 decay=ingest_cfg.decay)
                    est = ingest_sketches.hll_cardinality(ist.hll)
                    if ingest_cfg.reweight_mixing:
                        eta_r = ingest_weighting.reweight_eta(
                            eta_r, est, ingest_cfg.spread_gate)
                    if ingest_cfg.drift_on:
                        # drifted nodes' columns are discounted/zeroed
                        # with mass-preserving renorm; untriggered rounds
                        # pass eta through bit-exactly
                        disc = (0.0 if ingest_cfg.drift_mode == "reset"
                                else ingest_cfg.drift_discount)
                        scale = jnp.where(
                            novelty > ingest_cfg.drift_threshold, disc,
                            1.0)
                        eta_r = ingest_weighting.scale_eta_columns(
                            eta_r, scale)
            sent = None
            if use_faults:
                health_r, byz_r, corrupt_r, straggle_r = f_r
                with jax.named_scope("cdfl.wire"):
                    # what each node puts on the wire this round: its
                    # fresh buffer, a straggler's stale replay, an
                    # attacker's flipped/scaled version, a corrupted
                    # frame — in that order (an attacker corrupts what
                    # it would have sent)
                    sent = buf
                    if has_straggle:
                        sent = jnp.where(straggle_r[:, None] > 0, prev,
                                         sent)
                    if has_byz:
                        sent = sent * byz_r[:, None]
                    if has_corrupt:
                        sent = faults_lib.corrupt_rows(
                            sent, corrupt_r, fed.faults.corrupt_mode)
                    # receive-side self-healing: drop non-finite /
                    # blown-up payloads (zero the sender's eta column,
                    # partition-safe renorm, scrub the rows) before
                    # anything mixes
                    sent, eta_r, quarantined = faults_lib.wire_guard(
                        sent, buf, eta_r, fed.faults.guard_threshold)
            aux = {}
            if fed.algorithm == "dpsgd":
                # no once-per-round exchange: the gossip runs INSIDE the
                # step loop (dpsgd is fault-incapable, so sent is None),
                # so under dpsgd the mix is part of ``cdfl.local``
                with jax.named_scope("cdfl.local"):
                    buf, opt_state, loss = dpsgd_updates_from_idx(
                        buf, opt_state, layout, eta_r, gamma_r, data,
                        idx_r, lr=lr)
            else:
                with jax.named_scope("cdfl.mix"):
                    mixed, tstate = mix_buf(buf, state.sizes, eta_r,
                                            gamma_r, layout, tstate, rnd,
                                            sent=sent)
                with jax.named_scope("cdfl.local"):
                    buf, opt_state, loss, aux = local_updates_from_idx(
                        mixed, opt_state, layout, data, idx_r, lr=lr)
            with jax.named_scope("cdfl.metrics"):
                metrics = _flat_metrics(buf, layout, loss, gamma_r, aux)
                if hier_fmt:
                    # intra-tier telemetry: the gamma metric already
                    # carries the inter-tier step, this one shows what
                    # the clusters actually ran at (the gamma-decoupling
                    # the format buys)
                    metrics["gamma_intra"] = eta_r.gamma_node.mean()
                    metrics["clusters"] = (
                        jnp.zeros((fed.num_nodes,), jnp.float32)
                        .at[eta_r.cluster].set(1.0).sum())
                if ingest_on:
                    metrics["est_distinct"] = est
                    if ingest_cfg.drift_on:
                        metrics["drift"] = novelty
            if use_faults:
                with jax.named_scope("cdfl.heal"):
                    # post-round self-healing: crashed nodes freeze for
                    # the outage (their eta row/column was already
                    # zeroed at compile time, so the mix was a bit-exact
                    # self-update); nodes whose buffer went non-finite
                    # (local divergence on a poisoned mix) roll back to
                    # last-good values
                    finite = jnp.isfinite(buf).all(axis=1)
                    keep = (health_r > 0) & finite
                    buf = jnp.where(keep[:, None], buf, entry_buf)
                    opt_state = _freeze_rows(opt_state, entry_opt, keep)
                    metrics["health"] = health_r
                    metrics["quarantined"] = quarantined
                    metrics["frozen"] = ((health_r > 0) & ~finite).astype(
                        jnp.float32)
                if has_straggle:
                    # next round's stale replay is THIS round's entry
                    # buffer (what the node broadcast this round)
                    prev = entry_buf
            return (buf, opt_state, rnd + 1, tstate, prev, ist), metrics

        (buf, opt_state, rnd, tstate, prev, ist), metrics = jax.lax.scan(
            body, (buf0, state.opt, state.round, state.tstate, prev0, ing0),
            (idx, etas, gammas, fault_xs))
        with jax.named_scope("cdfl.pack"):
            final = FedState(flatten.unflatten(buf, layout), opt_state,
                             state.ratios, state.sizes, rnd, tstate, prev,
                             ist)
        return final, metrics

    # single-run scan: the exact pre-batching entry point (lr defaults
    # to None, so the TrainConfig rate stays a trace constant and the
    # jaxpr is bit-identical to previous builds)
    _scan_rounds = partial(jax.jit,
                           static_argnames=("num_rounds", "max_items"),
                           donate_argnums=(0,))(_scan_rounds_impl)

    @partial(annotate_function, name="cdfl.ingest_plan")
    def _ingest_inputs(data, max_items: int):
        """``data`` as the redundancy scenario streams it, and the slot
        hashes the in-scan sketch update reads. The scenario compiles
        into the round-invariant slot -> item map and every slot's
        sketch coordinates are pre-hashed (the in-scan update then does
        zero hashing). Both are deterministic in (cfg, K, N) — resumed
        segments rebuild the SAME streams — so they are cached on the
        trainer: repeated segments pay only the jitted data gather, not
        the host-side plan compile + hashing."""
        if max_items not in ingest_plans:
            plan = ingest_scenarios.compile_plan(ingest_cfg, fed.num_nodes,
                                                 max_items)
            ingest_plans[max_items] = (
                jnp.asarray(plan.src_node), jnp.asarray(plan.src_slot),
                ingest_sketches.slot_hashes(jnp.asarray(plan.item_ids),
                                            ingest_cfg))
        src_node, src_slot, slot_hashes = ingest_plans[max_items]
        return _ingest_gather(data, src_node, src_slot), slot_hashes

    @partial(annotate_function, name="cdfl.fault_plan")
    def _fault_inputs(etas, num_rounds: int, start: int,
                      per_variant: bool = False):
        """Compile the fault schedules for the absolute rounds
        ``[start, start + num_rounds)`` (same slicing invariant as the
        kinematic trace) and fold the surviving-link mask into the eta
        stack host-side — into each variant's stack when
        ``per_variant``. Rows only ever lose mass, so the gamma
        stability bound computed on the unmasked stack stays valid.
        Returns ``(etas, fault_xs)``."""
        from repro.mobility import mixing as mobility_mixing
        plan = faults_lib.compile_plan(fed.faults, num_rounds,
                                       fed.num_nodes, start=start)
        mask = jnp.asarray(plan.link_mask)

        def fold(e):
            if isinstance(e, hier_lib.HierEta):
                # the link mask edits BOTH tiers' kept idx/val pairs — a
                # crashed leader's cluster skips inter mixing
                return hier_lib.masked_hier_stack(e, mask)
            if isinstance(e, topology.SparseEta):
                # the (R, K, K) link mask compiles to per-edge edits of
                # the kept idx/val pairs — the dense mask matrix never
                # meets the mixing math
                return mobility_mixing.masked_sparse_stack(e, mask)
            return mobility_mixing.masked_eta_stack(e, mask)

        etas = jax.vmap(fold)(etas) if per_variant else fold(etas)
        return etas, (jnp.asarray(plan.health), jnp.asarray(plan.byz),
                      jnp.asarray(plan.corrupt),
                      jnp.asarray(plan.straggle))

    # batched (vmapped) scan drivers, built lazily per sharing mode:
    # variant-invariant inputs (the resident datasets, fault schedules,
    # slot hashes, and — when every variant runs the same scenario —
    # the eta stacks) ride in with in_axes=None, so a 32-seed sweep
    # never materializes 32 copies of the data or the (R, K, K) graphs.
    _batched_cache: dict = {}

    def _batched_scan(shared_etas: bool, lr_mapped: bool,
                      num_rounds: int, max_items: int):
        key = (shared_etas, lr_mapped, num_rounds, max_items)
        if key not in _batched_cache:
            def run(state, data, round_keys, node_sizes, etas, gammas,
                    fault_xs, slot_hashes, lr):
                return _scan_rounds_impl(state, data, round_keys,
                                         num_rounds, max_items,
                                         node_sizes, etas, gammas,
                                         fault_xs, slot_hashes, lr)
            axes = (0, None, 0, None, None if shared_etas else 0, 0,
                    None, None, 0 if lr_mapped else None)
            _batched_cache[key] = jax.jit(jax.vmap(run, in_axes=axes),
                                          donate_argnums=(0,))
        return _batched_cache[key]

    @partial(jax.jit, static_argnames=("num_rounds", "static_graph"))
    def _prepare(rng, start, ratios, sizes, num_rounds: int,
                 static_graph: bool):
        """A call's round-invariant device inputs, built by one program.

        Returns the round keys folded from ``rng`` on the absolute
        rounds ``[start, start + num_rounds)`` — ``(R, 2)`` keys, or
        ``(V, R, 2)`` for a ``(V, 2)`` stack of keys, whose ``(V, K)``
        ratios and sizes then come from variant 0 — and, when
        ``static_graph``, the static graph's eta/gamma stacks (else
        None). ``start`` is traced, so a continuing run reuses the
        program instead of compiling one per start round."""
        with jax.named_scope("cdfl.prepare"):
            rounds = start + jnp.arange(num_rounds, dtype=jnp.int32)

            def fold(key):
                return jax.vmap(lambda r: jax.random.fold_in(key, r))(
                    rounds)

            if rng.ndim > 1:
                round_keys = jax.vmap(fold)(rng)
                ratios, sizes = ratios[0], sizes[0]
            else:
                round_keys = fold(rng)
            if not static_graph:
                return round_keys, None
            return round_keys, _static_stacks(ratios, sizes, num_rounds,
                                              fed.gamma)

    def _prepare_call(rng, start: int, ratios, sizes, num_rounds: int,
                      static_graph: bool):
        """:func:`_prepare` for one call; a static graph's rounds are
        counted as ``/cdfl/prepare/rounds_compiled``."""
        out = _prepare(rng, np.int32(start), ratios, sizes,
                       num_rounds=num_rounds, static_graph=static_graph)
        if static_graph:
            monitoring.record_scalar("/cdfl/prepare/rounds_compiled",
                                     num_rounds)
        return out

    @partial(annotate_function, name="cdfl.prepare")
    def _batch_call(states: FedState, data, num_rounds: int, *,
                    rngs: Optional[jax.Array] = None,
                    n_items: Optional[jax.Array] = None,
                    eta_stacks=None, gamma_stacks=None, lrs=None):
        """Batched multi-round driver: V whole runs under ONE compiled
        ``vmap(scan)`` — the fleet-sweep twin of :func:`run_rounds`.

        states: a (V,)-stacked FedState (every leaf gains a leading
               variant axis; stack V ``init`` results, or broadcast one)
               — donated, like the single-run scan. All variants must
               sit at the same round.
        data:  ONE node-stacked dataset pytree, SHARED by every variant
               (vmapped with ``in_axes=None`` — no V-fold copy).
        rngs:  per-variant batch-sampling base keys, (V, 2) stacked (or
               one key, broadcast); per-round keys fold on the ABSOLUTE
               round index per variant, so a batched run reproduces V
               single runs exactly.
        eta_stacks: per-variant mixing stacks — dense ``(V, R, K, K)``
               or ``SparseEta`` with ``(V, R, K, D)`` stacks — or ONE
               shared ``(R, K, K)`` / ``(R, K, D)`` stack (kept
               variant-invariant on device); ``None`` derives the
               config's own shared stacks via :func:`mixing_stack`.
        gamma_stacks: ``(V, R)`` / ``(R,)`` per-round step sizes;
               derived from ``eta_stacks`` via the stability bound when
               omitted.
        lrs:   optional (V,) per-variant learning rates — promoted to a
               runtime argument of the shared program; ``None`` keeps
               the TrainConfig rate baked in.
        :func:`run_rounds_batch` returns ``(final_states, metrics)``
        with every leaf/metric stacked along a leading (V,) axis
        (metrics: ``(V, R, K)``); this returns the jitted program and
        its arguments.
        """
        from repro import mobility as mobility_lib
        from repro.mobility import mixing as mobility_mixing
        if hier_fmt:
            raise ValueError(
                "batched execution does not support mixing_format="
                "'hierarchical' yet — the two-tier HierEta stacks carry "
                "per-round cluster geometry that differs per variant "
                "(recorded ROADMAP follow-on); run hierarchical sweeps "
                "one variant at a time")
        k = fed.num_nodes
        import numpy as _np
        rounds_arr = _np.asarray(states.round)
        if rounds_arr.ndim != 1:
            raise ValueError(
                "run_rounds_batch needs a (V,)-stacked FedState — stack "
                f"init results along a leading variant axis (round "
                f"counter has shape {rounds_arr.shape})")
        v = rounds_arr.shape[0]
        if not (rounds_arr == rounds_arr[0]).all():
            raise ValueError(
                f"all variants must sit at the same round to share one "
                f"scan (got rounds {rounds_arr.tolist()})")
        start = int(rounds_arr[0])
        data = jax.tree.map(jnp.asarray, data)
        max_items = jax.tree.leaves(data)[0].shape[1]
        slot_hashes = ()
        if ingest_on:
            data, slot_hashes = _ingest_inputs(data, max_items)
        if n_items is not None:
            n_items = jnp.asarray(n_items)
        if rngs is None:
            rngs = jax.random.PRNGKey(train.seed + 1)
        rngs = jnp.asarray(rngs)
        if rngs.ndim == 1:
            rngs = jnp.broadcast_to(rngs[None], (v,) + rngs.shape)
        if rngs.shape[0] != v:
            raise ValueError(f"rngs leading dim {rngs.shape[0]} != "
                             f"V={v} variants")
        static_graph = eta_stacks is None and not mobile
        round_keys, stacks = _prepare_call(rngs, start, states.ratios,
                                           states.sizes, num_rounds,
                                           static_graph)
        # -- mixing stacks: shared (in_axes=None) or per-variant --------
        if static_graph:
            etas, gammas = stacks
            shared = True
        elif eta_stacks is None:
            state0 = jax.tree.map(lambda a: a[0], states)
            etas, gammas = mixing_stack(state0, num_rounds, start=start)
            shared = True
        elif isinstance(eta_stacks, topology.SparseEta):
            if not sparse_fmt:
                raise ValueError(
                    "a SparseEta stack needs mixing_format='sparse'")
            etas = topology.SparseEta(
                jnp.asarray(eta_stacks.idx, jnp.int32),
                jnp.asarray(eta_stacks.val, jnp.float32))
            shared = etas.idx.ndim == 3
            d = etas.idx.shape[-1]
            expect = ((num_rounds, k, d) if shared
                      else (v, num_rounds, k, d))
            if etas.idx.shape != expect or etas.val.shape != expect:
                raise ValueError(
                    f"sparse eta stacks idx={etas.idx.shape} "
                    f"val={etas.val.shape} != {expect}")
            gammas = gamma_stacks
            if gammas is None:
                fn = lambda e: mobility_mixing.sparse_gamma_stack(
                    e, fed.gamma)
                gammas = fn(etas) if shared else jax.vmap(fn)(etas)
        else:
            if sparse_fmt:
                raise ValueError(
                    "mixing_format='sparse' needs SparseEta stacks "
                    f"(got dense array {jnp.shape(eta_stacks)})")
            etas = jnp.asarray(eta_stacks, jnp.float32)
            shared = etas.ndim == 3
            expect = ((num_rounds, k, k) if shared
                      else (v, num_rounds, k, k))
            if etas.shape != expect:
                raise ValueError(f"eta stacks shape {etas.shape} != "
                                 f"{expect}")
            gammas = gamma_stacks
            if gammas is None:
                fn = lambda e: mobility_lib.gamma_stack(e, fed.gamma)
                gammas = fn(etas) if shared else jax.vmap(fn)(etas)
        # gammas are small — always normalized to a mapped (V, R) stack
        gammas = jnp.asarray(gammas, jnp.float32)
        if gammas.ndim == 1:
            gammas = jnp.broadcast_to(gammas[None], (v, num_rounds))
        if gammas.shape != (v, num_rounds):
            raise ValueError(f"gamma stacks shape {gammas.shape} != "
                             f"{(v, num_rounds)}")
        if lrs is not None:
            lrs = jnp.asarray(lrs, jnp.float32)
            if lrs.shape != (v,):
                raise ValueError(f"lrs shape {lrs.shape} != ({v},)")
        fault_xs = ()
        if faulty:
            # ONE fault plan shared by every variant (the schedule is
            # config-keyed), folded into each variant's eta stack
            etas, fault_xs = _fault_inputs(etas, num_rounds, start,
                                           per_variant=not shared)
        fn = _batched_scan(shared, lrs is not None, num_rounds,
                           max_items)
        return fn, (states, data, round_keys, n_items, etas, gammas,
                    fault_xs, slot_hashes, lrs)

    def run_rounds_batch(states: FedState, data, num_rounds: int, **kw):
        fn, args = _batch_call(states, data, num_rounds, **kw)
        with TraceAnnotation("cdfl.dispatch"):
            return fn(*args)

    run_rounds_batch.__doc__ = _batch_call.__doc__

    def lower_rounds_batch(states: FedState, data, num_rounds: int, **kw):
        """The :func:`run_rounds_batch` program for these inputs, lowered
        and not run (``.compile().as_text()`` shows what the device
        runs); the states are not donated."""
        fn, args = _batch_call(states, data, num_rounds, **kw)
        return fn.lower(*args)

    @partial(annotate_function, name="cdfl.prepare")
    def _rounds_args(state: FedState, data, num_rounds: int,
                     rng: Optional[jax.Array] = None,
                     n_items: Optional[jax.Array] = None,
                     eta_stack: Optional[jax.Array] = None,
                     gamma_stack: Optional[jax.Array] = None):
        """Device-resident multi-round driver.

        Runs ``num_rounds`` full C-DFL rounds (consensus + local steps)
        under a single ``jax.lax.scan``: batch indices for every round
        are pre-sampled with ``jax.random``, minibatches are gathered on
        device from the resident datasets, and the state buffers are
        donated — eliminating the per-round jit dispatch and host-numpy
        batch transfer the Python round loop pays.

        Sampling and (under mobility) the per-round graphs are keyed on
        the ABSOLUTE round index carried by ``state.round``: calling
        this twice for 10 rounds each reproduces one 20-round call with
        the same ``rng`` — the invariant the Session checkpoint/resume
        path relies on.

        state: FedState (donated — do not reuse after the call).
        data:  pytree of node-stacked dataset arrays, leaves (K, N, ...),
               with the same keys ``loss_fn`` expects in a batch
               (e.g. {"x": (K, N, 784), "y": (K, N)}).
        n_items: optional (K,) per-node valid item counts when the
               resident arrays are padded to a common N (ragged nodes,
               e.g. after CND dedup); sampling stays uniform over each
               node's true count.
        eta_stack: optional explicit per-round mixing weights overriding
               :func:`mixing_stack` (round r's exchange uses slice r —
               time-varying topologies): a dense (num_rounds, K, K)
               array, or a ``topology.SparseEta`` with (num_rounds, K, D)
               idx/val stacks.
        gamma_stack: optional (num_rounds,) per-round step sizes; derived
               from ``eta_stack`` rows via the paper's stability bound
               when omitted.
        :func:`run_rounds` returns (final_state, metrics) with every
        metric stacked along a leading (num_rounds,) axis; this returns
        the arguments of the jitted scan.
        """
        if rng is None:
            rng = jax.random.PRNGKey(train.seed + 1)
        data = jax.tree.map(jnp.asarray, data)
        max_items = jax.tree.leaves(data)[0].shape[1]
        slot_hashes = ()
        if ingest_on:
            data, slot_hashes = _ingest_inputs(data, max_items)
        if n_items is not None:
            n_items = jnp.asarray(n_items)
        start = int(state.round)
        static_graph = eta_stack is None and not mobile
        round_keys, stacks = _prepare_call(rng, start, state.ratios,
                                           state.sizes, num_rounds,
                                           static_graph)
        if eta_stack is None:
            etas, gammas = (stacks if static_graph else
                            mixing_stack(state, num_rounds, start=start))
            if gamma_stack is not None:
                gammas = jnp.asarray(gamma_stack, jnp.float32)
        else:
            from repro import mobility as mobility_lib
            from repro.mobility import mixing as mobility_mixing
            if isinstance(eta_stack, hier_lib.HierEta):
                etas = eta_stack
                gammas = (hier_lib.hier_gamma_stack(etas, fed.gamma)
                          if gamma_stack is None
                          else jnp.asarray(gamma_stack, jnp.float32))
            elif isinstance(eta_stack, topology.SparseEta):
                etas = topology.SparseEta(
                    jnp.asarray(eta_stack.idx, jnp.int32),
                    jnp.asarray(eta_stack.val, jnp.float32))
                gammas = (mobility_mixing.sparse_gamma_stack(etas,
                                                             fed.gamma)
                          if gamma_stack is None
                          else jnp.asarray(gamma_stack, jnp.float32))
            else:
                etas = jnp.asarray(eta_stack, jnp.float32)
                gammas = (mobility_lib.gamma_stack(etas, fed.gamma)
                          if gamma_stack is None
                          else jnp.asarray(gamma_stack, jnp.float32))
        k = fed.num_nodes
        if isinstance(etas, hier_lib.HierEta):
            if not hier_fmt:
                raise ValueError(
                    "a hierarchical eta stack needs "
                    "mixing_format='hierarchical' (the scan body "
                    "dispatches on the config-static format)")
            if (etas.cluster.shape != (num_rounds, k)
                    or etas.gamma_node.shape != (num_rounds, k)
                    or etas.burst.shape != (num_rounds,)):
                raise ValueError(
                    f"hierarchical stack shapes cluster="
                    f"{etas.cluster.shape} gamma_node="
                    f"{etas.gamma_node.shape} burst={etas.burst.shape} "
                    f"!= {(num_rounds, k)} / {(num_rounds,)}")
        elif hier_fmt:
            raise ValueError(
                "mixing_format='hierarchical' needs a HierEta stack "
                f"(got {type(etas).__name__}); build one with "
                "repro.hierarchy.mixing or omit eta_stack")
        elif isinstance(etas, topology.SparseEta):
            d = etas.degree
            if (etas.idx.shape != (num_rounds, k, d)
                    or etas.val.shape != (num_rounds, k, d)):
                raise ValueError(
                    f"sparse eta stack shapes idx={etas.idx.shape} "
                    f"val={etas.val.shape} != {(num_rounds, k, d)}")
        elif etas.shape != (num_rounds, k, k):
            raise ValueError(f"eta stack shape {etas.shape} != "
                             f"{(num_rounds, k, k)}")
        if gammas.shape != (num_rounds,):
            raise ValueError(f"gamma stack shape {gammas.shape} != "
                             f"{(num_rounds,)}")
        fault_xs = ()
        if faulty:
            etas, fault_xs = _fault_inputs(etas, num_rounds, start)
        return (state, data, round_keys, num_rounds, max_items, n_items,
                etas, gammas, fault_xs, slot_hashes)

    def run_rounds(state: FedState, data, num_rounds: int, *args, **kw):
        call = _rounds_args(state, data, num_rounds, *args, **kw)
        with TraceAnnotation("cdfl.dispatch"):
            return _scan_rounds(*call)

    run_rounds.__doc__ = _rounds_args.__doc__

    def lower_rounds(state: FedState, data, num_rounds: int, *args, **kw):
        """The :func:`run_rounds` scan for these inputs, lowered and not
        run (``.compile().as_text()`` shows what the device runs); the
        state is not donated."""
        return _scan_rounds.lower(*_rounds_args(state, data, num_rounds,
                                                *args, **kw))

    return Trainer(init=init, round=jax.jit(round_fn), eta_fn=eta_fn,
                   run_rounds=run_rounds, mixing_stack=mixing_stack,
                   run_rounds_batch=run_rounds_batch,
                   lower_rounds=lower_rounds,
                   lower_rounds_batch=lower_rounds_batch,
                   prepare=_prepare)
