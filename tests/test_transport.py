"""Consensus transport layer: dense/ring/gossip equivalence vs the
seed per-leaf oracle (kernels.ref), bf16 wire drift bounds, bounded-delay
gossip semantics, single-node pack round-trips, and the end-to-end
round-trip of every backend through Trainer.run_rounds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, TrainConfig
from repro.core import baselines, consensus, flatten, topology, transport
from repro.kernels import ops, ref


def _mlp_like(k=4, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"w1": jax.random.normal(ks[0], (k, 784, 30)),
            "b1": jax.random.normal(ks[1], (k, 30)),
            "w2": jax.random.normal(ks[2], (k, 30, 10)),
            "b2": jax.random.normal(ks[3], (k, 10))}


def _ragged_params(k=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "w": jax.random.normal(ks[0], (k, 7, 3)),
        "gain": jax.random.normal(ks[1], (k,)),
        "half": jax.random.normal(ks[2], (k, 1, 5, 2)).astype(jnp.bfloat16),
        "b": jax.random.normal(ks[3], (k, 13)),
    }


def _ring_eta(k=4, ratios=(0.3, 0.8, 0.6, 0.9)):
    adj = jnp.asarray(topology.adjacency("ring", k))
    return topology.cnd_mixing(adj, jnp.asarray(ratios))


# --- single-exchange equivalence vs the per-leaf oracle ---------------------

@pytest.mark.parametrize("topo", ["ring", "full"])
def test_dense_transport_matches_oracle(topo):
    params = _mlp_like()
    adj = jnp.asarray(topology.adjacency(topo, 4))
    eta = topology.cnd_mixing(adj, jnp.asarray([0.3, 0.8, 0.6, 0.9]))
    buf, layout = flatten.flatten(params)
    out, _ = transport.DenseTransport().exchange(buf, eta, 0.4)
    exp, _ = flatten.flatten(ref.consensus_step_pytree(params, eta, 0.4),
                             layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_ring_transport_matches_dense_on_ring_topology():
    params = _mlp_like(seed=2)
    eta = _ring_eta()
    buf, layout = flatten.flatten(params)
    ring_out, _ = transport.RingShardTransport().exchange(buf, eta, 0.4)
    exp, _ = flatten.flatten(ref.consensus_step_pytree(params, eta, 0.4),
                             layout)
    np.testing.assert_allclose(np.asarray(ring_out), np.asarray(exp),
                               atol=1e-5)


def test_ring_transport_rejects_two_nodes():
    buf = jnp.ones((2, 128))
    eta = jnp.asarray([[0.0, 1.0], [1.0, 0.0]])
    with pytest.raises(ValueError):
        transport.RingShardTransport().exchange(buf, eta, 0.4)


def test_gossip_staleness0_bit_identical_to_dense():
    buf, _ = flatten.flatten(_mlp_like(seed=3))
    eta = _ring_eta()
    d, _ = transport.DenseTransport().exchange(buf, eta, 0.4)
    g, _ = transport.GossipTransport(staleness=0).exchange(buf, eta, 0.4)
    assert (np.asarray(d) == np.asarray(g)).all()


def test_gossip_reads_snapshot_exactly_s_rounds_old():
    """With staleness=s, the neighbor terms at round r must come from the
    buffer written at round r-s (buf0 for the first s rounds)."""
    s = 2
    buf0, _ = flatten.flatten(_mlp_like(seed=4))
    eta = _ring_eta()
    t = transport.GossipTransport(staleness=s)
    state = t.init_state(buf0)
    g = 0.3
    eta32 = np.asarray(eta, np.float32)
    row = eta32.sum(axis=1)

    def expect(buf, stale):
        b, st = np.asarray(buf), np.asarray(stale)
        return b + g * (eta32 @ st - row[:, None] * b)

    history = [np.asarray(buf0)]    # history[r+1] = buffer seen at round r
    buf = buf0
    for rnd in range(5):
        out, state = t.exchange(buf, eta, g, state, jnp.int32(rnd))
        stale = history[rnd - s + 1] if rnd >= s else history[0]
        np.testing.assert_allclose(np.asarray(out), expect(buf, stale),
                                   rtol=1e-6, atol=1e-6)
        history.append(np.asarray(buf))          # what round rnd wrote
        buf = out + 0.01                         # perturb so rounds differ


def test_gossip_staleness_exceeding_rounds_reads_initial_buffer():
    """Edge case: staleness >= rounds run. Every slot of the snapshot
    ring still holds the INITIAL buffer (init_state broadcasts it), so
    every exchange must mix against buf0 — numpy oracle per round."""
    s = 8
    buf0, _ = flatten.flatten(_mlp_like(seed=5))
    eta = _ring_eta()
    t = transport.GossipTransport(staleness=s)
    state = t.init_state(buf0)
    g = 0.3
    eta32 = np.asarray(eta, np.float32)
    row = eta32.sum(axis=1)
    b0 = np.asarray(buf0)
    buf = buf0
    for rnd in range(5):                  # 5 rounds < staleness=8
        out, state = t.exchange(buf, eta, g, state, jnp.int32(rnd))
        b = np.asarray(buf)
        expect = b + g * (eta32 @ b0 - row[:, None] * b)
        np.testing.assert_allclose(np.asarray(out), expect,
                                   rtol=1e-6, atol=1e-6)
        buf = out + 0.01                  # perturb so rounds differ


def test_bf16_wire_halves_bytes_and_bounds_drift_over_20_rounds():
    params = _mlp_like(seed=5)
    buf, layout = flatten.flatten(params)
    eta = _ring_eta()
    f32 = transport.DenseTransport()
    b16 = transport.DenseTransport(wire_dtype="bf16")
    assert b16.wire_bytes(layout) * 2 == f32.wire_bytes(layout)
    a, b = buf, buf
    for _ in range(20):
        a, _ = f32.exchange(a, eta, 0.4)
        b, _ = b16.exchange(b, eta, 0.4)
    scale = float(jnp.abs(buf).max())
    drift = float(jnp.abs(a - b).max())
    # bf16 has ~3 decimal digits; delta-form mixing keeps the per-round
    # injection at the bf16 rounding of the *differences*, so 20 rounds
    # stay well under 1% of the data scale
    assert drift < 1e-2 * scale
    # and both reach the same consensus: disagreement decays identically
    da = float(flatten.disagreement_flat(a, layout.total))
    d0 = float(flatten.disagreement_flat(buf, layout.total))
    assert da < d0


# --- fused delta-mix kernel -------------------------------------------------

def test_flat_mix_kernel_matches_xla_delta_form():
    buf, _ = flatten.flatten(_mlp_like(seed=6))
    eta = _ring_eta()
    wire = buf.astype(jnp.bfloat16)
    # force_kernel: run the Pallas body (interpret mode off TPU) — the
    # auto dispatch would give us the XLA form this test compares with
    krn = ops.flat_mix(eta, buf, wire, jnp.float32(0.4),
                       force_kernel=True)
    row = eta.sum(axis=1)
    w32 = wire.astype(jnp.float32)
    exp = buf + 0.4 * (jnp.einsum("ki,ip->kp", eta, w32)
                       - row[:, None] * w32)
    np.testing.assert_allclose(np.asarray(krn), np.asarray(exp), atol=1e-6)


def test_mix_flat_kernel_path_with_wire_matches_xla_path():
    buf, _ = flatten.flatten(_mlp_like(seed=7))
    eta = _ring_eta()
    wire = buf.astype(jnp.bfloat16)
    k = flatten.mix_flat(buf, eta, 0.4, use_kernel=True, wire=wire)
    x = flatten.mix_flat(buf, eta, 0.4, use_kernel=False, wire=wire)
    np.testing.assert_allclose(np.asarray(k), np.asarray(x), atol=1e-6)


# --- single-node pack / column shards (mesh-mode substrate) -----------------

def test_flatten_one_roundtrip_ragged_bit_exact():
    one = jax.tree.map(lambda l: l[1], _ragged_params(seed=8))
    vec, layout = flatten.flatten_one(one)
    assert vec.shape == (layout.padded,)
    assert layout.padded % flatten.LANE == 0
    back = flatten.unflatten_one(vec, layout)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert (np.asarray(a, np.float32) == np.asarray(b,
                                                        np.float32)).all()


def test_column_shards_lane_aligned():
    assert flatten.column_shards(1024, 4) == 4
    assert flatten.column_shards(1024, 3) == 2      # 3 doesn't divide
    assert flatten.column_shards(128, 4) == 1       # chunks < LANE
    assert flatten.column_shards(640, 5) == 5
    assert flatten.column_shards(256, 0) == 1


def test_ring_exchange_shard_under_named_axis_matches_oracle():
    """The shard_map/mesh path (ppermute on the flat vector) validated
    via a vmapped named axis — same collective semantics, no mesh."""
    k = 4
    params = _mlp_like(k, seed=9)
    ratios = jnp.asarray([0.3, 0.8, 0.6, 0.9])
    r_prev, r_next = jnp.roll(ratios, 1), jnp.roll(ratios, -1)
    denom = jnp.maximum(r_prev + r_next, 1e-12)
    eta_prev, eta_next = r_prev / denom, r_next / denom

    def one_node(p, ep, en):
        return consensus.ring_consensus_shard(p, ep, en, 0.4, "fed",
                                              shards=2)

    out = jax.vmap(one_node, axis_name="fed")(params, eta_prev, eta_next)
    eta = _ring_eta(k, tuple(float(r) for r in ratios))
    exp = ref.consensus_step_pytree(params, eta, 0.4)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(exp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ring_exchange_shard_shards_equivalent():
    vec = jax.random.normal(jax.random.PRNGKey(10), (4, 1024))
    ep = jnp.full((4,), 0.5)
    en = jnp.full((4,), 0.5)

    def run(shards):
        def one(v, p, n):
            return transport.ring_exchange_shard(v, p, n, 0.4, "fed",
                                                 shards=shards)
        return jax.vmap(one, axis_name="fed")(vec, ep, en)

    np.testing.assert_allclose(np.asarray(run(1)), np.asarray(run(4)),
                               atol=1e-6)


# --- adaptive one-shot dispatch ---------------------------------------------

def test_adaptive_consensus_step_paths_agree():
    params = _mlp_like(seed=11)
    eta = _ring_eta()
    flat = consensus.consensus_step(params, eta, 0.4, use_flat=True)
    leaf = consensus.consensus_step(params, eta, 0.4, use_flat=False)
    auto = consensus.consensus_step(params, eta, 0.4)
    for a, b, c in zip(jax.tree.leaves(flat), jax.tree.leaves(leaf),
                       jax.tree.leaves(auto)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)


def test_adaptive_dispatch_never_packs_one_shot_on_cpu():
    """Recalibrated for the single-pass pack (PR 5): a one-shot
    consensus_step on CPU NEVER routes through a physically packed
    buffer — pack+mix+unpack is >= 3 full loop passes against the
    per-leaf path's one, regardless of leaf count/size (the flat engine
    itself virtualizes the buffer there). Accelerators always take the
    fused flat path."""
    if jax.default_backend() == "tpu":
        pytest.skip("CPU dispatch heuristic")
    big = {"w": jnp.ones((4, 1024, 1024))}          # 4 MB/node, 1 leaf
    many_small = {f"p{i}": jnp.ones((4, 8)) for i in range(64)}
    assert not consensus._prefer_flat(big)
    assert not consensus._prefer_flat(many_small)


# --- end-to-end: every backend through Trainer.run_rounds -------------------

def _trainer(**fed_kw):
    from repro.configs.paper_models import MLP_CONFIG
    from repro.data import pipeline, synthetic
    from repro.models import simple
    nodes = [synthetic.synthetic_mnist(seed=i, n=160) for i in range(4)]
    batcher = pipeline.FederatedBatcher(nodes, 32, 2)
    loss = simple.make_mlp_loss(MLP_CONFIG)
    fed = FedConfig(num_nodes=4, local_steps=2, **fed_kw)
    tr = baselines.ALGORITHMS[fed.algorithm](
        lambda p, b: loss(p, b), fed, TrainConfig(learning_rate=1e-3))
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: simple.mlp_init(r, MLP_CONFIG),
                    jnp.asarray(batcher.node_items()))
    data = {"x": jnp.asarray(np.stack([d.x for d in nodes])),
            "y": jnp.asarray(np.stack([d.y for d in nodes]))}
    return tr, state, data


def _final_leaf(fed_kw, rounds=5):
    tr, state, data = _trainer(**fed_kw)
    final, m = tr.run_rounds(state, data, rounds,
                             rng=jax.random.PRNGKey(7))
    assert np.isfinite(np.asarray(m["loss"])).all()
    return np.asarray(jax.tree.leaves(final.params)[0])


def test_run_rounds_ring_matches_dense_on_ring_topology():
    dense = _final_leaf({})
    ring = _final_leaf({"transport": "ring"})
    np.testing.assert_allclose(ring, dense, atol=1e-5)


def test_run_rounds_gossip_staleness0_bit_identical_to_dense():
    dense = _final_leaf({})
    gossip = _final_leaf({"transport": "gossip"})
    np.testing.assert_array_equal(gossip, dense)


def test_run_rounds_dense_full_topology_matches_oracle_reference():
    dense = _final_leaf({"topology": "full"})
    assert np.isfinite(dense).all()


def test_run_rounds_gossip_stale_trains():
    tr, state, data = _trainer(transport="gossip", staleness=2)
    final, m = tr.run_rounds(state, data, 8, rng=jax.random.PRNGKey(7))
    loss = np.asarray(m["loss"])
    assert np.isfinite(loss).all()
    assert loss[-1].mean() < loss[0].mean()
    # gossip state rode the scan carry: staleness snapshots present
    assert final.tstate.shape[0] == 2


def test_run_rounds_bf16_wire_close_to_f32():
    f32 = _final_leaf({})
    b16 = _final_leaf({"wire_dtype": "bf16"})
    scale = max(1.0, float(np.abs(f32).max()))
    assert np.abs(b16 - f32).max() < 1e-2 * scale


def test_run_rounds_ragged_n_items_stays_in_bounds():
    tr, state, data = _trainer()
    # mark most of two nodes' rows invalid; sampling must avoid them
    data = {"x": np.asarray(data["x"]).copy(),
            "y": np.asarray(data["y"]).copy()}
    data["x"][0, 40:] = np.nan
    data["x"][2, 100:] = np.nan
    n_items = jnp.asarray([40, 160, 100, 160])
    final, m = tr.run_rounds(state, data, 4, rng=jax.random.PRNGKey(3),
                             n_items=n_items)
    assert np.isfinite(np.asarray(m["loss"])).all()


@pytest.mark.parametrize("alg", ["fedavg", "dpsgd"])
def test_transportless_algorithms_reject_transport_config(alg):
    """fedavg/dpsgd have no once-per-round buffer exchange; asking for a
    non-default transport must error instead of being silently ignored."""
    from repro.core.cdfl import build_trainer
    loss = lambda p, b: jnp.sum(p["w"] ** 2)                 # noqa: E731
    with pytest.raises(ValueError):
        build_trainer(loss, FedConfig(algorithm=alg, transport="ring"),
                     TrainConfig())
    with pytest.raises(ValueError):
        build_trainer(loss, FedConfig(algorithm=alg, staleness=2),
                     TrainConfig())
    build_trainer(loss, FedConfig(algorithm=alg), TrainConfig())  # default ok


def test_make_transport_validates():
    with pytest.raises(ValueError):
        transport.make_transport(FedConfig(transport="carrier-pigeon"))
    with pytest.raises(ValueError):
        transport.make_transport(FedConfig(transport="ring",
                                           topology="full"))
    with pytest.raises(ValueError):
        transport.make_transport(FedConfig(wire_dtype="fp8"))
    assert isinstance(transport.make_transport(FedConfig()),
                      transport.DenseTransport)


def test_roofline_collective_term_reads_transport_wire_bytes():
    """Dry-run satellite: the roofline's consensus collective term must
    price the SELECTED backend (bf16 halves, links from the graph
    degree), replacing the dense-f32 collective-permute assumption."""
    from repro.launch import roofline
    params = _mlp_like()
    layout = flatten.make_layout(params)
    ring = topology.adjacency("ring", 4)
    full = topology.adjacency("full", 4)
    f32 = roofline.transport_consensus_bytes(
        transport.DenseTransport(), layout, ring)
    assert f32 == 2 * layout.padded * 4            # 2 links, f32
    b16 = roofline.transport_consensus_bytes(
        transport.RingShardTransport(wire_dtype="bf16"), layout, ring)
    assert b16 * 2 == f32                          # bf16 halves the wire
    assert roofline.transport_consensus_bytes(
        transport.DenseTransport(), layout, full) == 3 * layout.padded * 4
    stats = roofline.CollectiveStats(
        bytes_by_op={"collective-permute": 1000.0, "all-reduce": 500.0},
        count_by_op={"collective-permute": 2, "all-reduce": 1})
    rl = roofline.Roofline(flops=1.0, hbm_bytes=1.0,
                           wire_bytes=stats.wire_bytes, collectives=stats,
                           model_flops=1.0, device_kind="TPU v5 lite")
    rl2 = rl.with_consensus(transport.RingShardTransport(wire_dtype="bf16"),
                            layout, ring, devices_per_node=64)
    # non-consensus collectives (the 2x-weighted all-reduce) untouched
    assert rl2.wire_bytes == pytest.approx(2000.0 - 1000.0 + b16 / 64)


def test_roofline_peaks_are_keyed_by_device_kind():
    """The roofline terms divide by the peaks of the device kind the
    program was compiled for; a kind without published peaks raises
    instead of silently borrowing another chip's."""
    from repro.launch import roofline
    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    stats = roofline.CollectiveStats(bytes_by_op={}, count_by_op={})
    rl = roofline.Roofline(flops=197e12, hbm_bytes=819e9 / 2, wire_bytes=0.0,
                           collectives=stats, model_flops=1.0,
                           device_kind="TPU v5 lite")
    assert rl.t_compute == pytest.approx(1.0)
    assert rl.t_memory == pytest.approx(0.5)
    assert rl.bottleneck == "compute"
    unknown = dataclasses.replace(rl, device_kind="cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        unknown.t_compute


def test_fed_ring_perms_matches_axis_derived():
    from types import SimpleNamespace
    from repro.launch import mesh as meshlib
    m = SimpleNamespace(axis_names=("fed", "dp", "tp"),
                        shape={"fed": 4, "dp": 4, "tp": 16})
    fwd, bwd = meshlib.fed_ring_perms(m)
    assert fwd == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert bwd == [(0, 3), (1, 0), (2, 1), (3, 2)]


def test_bf16_wire_quantizes_the_exchange_on_every_backend():
    """A bf16 wire quantizes what every transport exchanges, on whatever
    backend runs it: the mix differs from the f32 wire's, by no more
    than bf16 rounding of the exchanged terms."""
    buf, _ = flatten.flatten(_mlp_like(seed=13))
    eta = _ring_eta()
    for name in ("dense", "ring", "gossip"):
        b16 = transport.make_transport(
            FedConfig(transport=name, wire_dtype="bf16"))
        f32 = transport.make_transport(FedConfig(transport=name))
        out_b16, _ = b16.exchange(buf, eta, 0.4, b16.init_state(buf))
        out_f32, _ = f32.exchange(buf, eta, 0.4, f32.init_state(buf))
        gap = np.abs(np.asarray(out_b16) - np.asarray(out_f32)).max()
        assert 0 < gap < 1e-2 * float(jnp.abs(buf).max()), name
