"""Sparse top-D consensus mixing (city-scale representation).

The contract under test: the sparse gather-mix — per-node top-D
neighbor ``idx``/``val`` pairs driving
``buf + gamma * (sum_d val_d * buf[idx_d] - rowsum(val) * buf)`` —
equals the dense ``(K, K)`` eq. 5 mix to 1e-5 whenever D covers every
positive neighbor, on ARBITRARY bounded-degree graphs: random masks,
isolated nodes (all-zero sparse row => pure self-update, never NaN),
and crash-fault link masks. Runs under hypothesis when installed (CI);
falls back to a seeded numpy fuzz sweep locally.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FaultConfig, FedConfig, MobilityConfig, TrainConfig
from repro.core import cdfl, flatten, topology
from repro.kernels import ops, ref
from repro.mobility import (adjacency_stack, constant_sparse_stacks,
                            degree_stats, eta_stack, gamma_stack,
                            masked_sparse_stack, sparse_eta_stack,
                            sparse_gamma_stack, sparse_radio_stack,
                            sparse_scenario_stacks, trace)
from repro.mobility.mixing import masked_eta_stack


def _dense_mix(buf, eta, gamma):
    """Reference: eq. 5 through the dense consensus matrix A @ W."""
    a = topology.consensus_matrix(jnp.asarray(eta), gamma)
    return np.asarray(flatten.matmul_nodes(a, jnp.asarray(buf)))


def _bounded_degree_eta(rng, k, d):
    """Random row-normalized weights with at most d positive neighbors
    per row; some rows fully drained (isolated nodes)."""
    eta = np.zeros((k, k), np.float32)
    for i in range(k):
        deg = int(rng.integers(0, d + 1))
        if deg == 0:
            continue                          # isolated node
        nbrs = rng.choice([j for j in range(k) if j != i],
                          size=min(deg, k - 1), replace=False)
        w = rng.random(len(nbrs)).astype(np.float32) + 0.1
        eta[i, nbrs] = w / w.sum() * rng.uniform(0.3, 1.0)
    return eta


def _check_sparse_vs_dense(rng, k, d, p=256):
    eta = _bounded_degree_eta(rng, k, d)
    buf = rng.standard_normal((k, p)).astype(np.float32)
    gamma = float(rng.uniform(0.05, 0.45))
    sp = topology.sparsify_eta(jnp.asarray(eta), d)
    got = np.asarray(flatten.sparse_mix_flat(jnp.asarray(buf), sp.idx,
                                             sp.val, gamma))
    want = _dense_mix(buf, eta, gamma)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5)
    # isolated rows are exact self-updates
    iso = eta.sum(axis=1) == 0
    if iso.any():
        np.testing.assert_array_equal(got[iso], buf[iso])
    # under a crash-fault mask (row+col of crashed nodes zeroed), the
    # sparse edit path must equal masking the dense matrix first
    crashed = rng.random(k) < 0.3
    mask = (np.outer(~crashed, ~crashed)).astype(np.float32)
    sp_m = masked_sparse_stack(
        topology.SparseEta(sp.idx[None], sp.val[None]),
        jnp.asarray(mask[None]))
    eta_m = np.asarray(masked_eta_stack(jnp.asarray(eta[None]),
                                        mask[None]))[0]
    got_m = np.asarray(flatten.sparse_mix_flat(
        jnp.asarray(buf), sp_m.idx[0], sp_m.val[0], gamma))
    np.testing.assert_allclose(got_m, _dense_mix(buf, eta_m, gamma),
                               atol=1e-5)
    assert np.isfinite(got_m).all()


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 10_000), st.integers(2, 12), st.integers(1, 6))
    def test_sparse_matches_dense_bounded_degree(seed, k, d):
        _check_sparse_vs_dense(np.random.default_rng(seed), k,
                               min(d, k - 1))

except ImportError:                          # hypothesis not installed
    def test_sparse_matches_dense_bounded_degree():
        rng = np.random.default_rng(0)
        for _ in range(40):
            k = int(rng.integers(2, 13))
            d = int(rng.integers(1, min(6, k - 1) + 1))
            _check_sparse_vs_dense(rng, k, d)


def test_sparsify_densify_roundtrip_preserves_row_mass():
    rng = np.random.default_rng(1)
    eta = _bounded_degree_eta(rng, 10, 4)
    sp = topology.sparsify_eta(jnp.asarray(eta), 4)
    dense = np.asarray(topology.densify_eta(sp, 10))
    np.testing.assert_allclose(dense, eta, atol=1e-6)
    # truncating D below the true degree keeps the row mass (renorm over
    # the kept top-D edges) — the stable_gamma bound stays valid
    sp2 = topology.sparsify_eta(jnp.asarray(eta), 2)
    np.testing.assert_allclose(np.asarray(sp2.val.sum(axis=1)),
                               eta.sum(axis=1), atol=1e-6)
    assert float(topology.stable_gamma(sp2, 0.4)) == pytest.approx(
        float(topology.stable_gamma(jnp.asarray(eta), 0.4)), rel=1e-5)


def test_degree_validation_rejects_out_of_range():
    with pytest.raises(ValueError, match="1 <= degree"):
        topology.validate_degree(0, 8)
    with pytest.raises(ValueError, match="clamp"):
        topology.validate_degree(8, 8)
    with pytest.raises(ValueError, match="mixing_format"):
        FedConfig(num_nodes=4, mixing_format="sparse", degree=2,
                  transport="ring")
    with pytest.raises(ValueError, match="robust"):
        FedConfig(num_nodes=4, mixing_format="sparse", degree=2,
                  robust="median")
    with pytest.raises(ValueError):
        FedConfig(num_nodes=4, mixing_format="nope")


def test_mixing_weights_degree_kwarg_returns_sparse():
    adj = jnp.asarray(topology.adjacency("full", 6))
    sp = topology.mixing_weights(adj, "uniform", degree=3)
    assert isinstance(sp, topology.SparseEta)
    assert sp.idx.shape == (6, 3)
    dense = topology.mixing_weights(adj, "uniform")
    np.testing.assert_allclose(np.asarray(sp.val.sum(axis=1)),
                               np.asarray(dense.sum(axis=1)), atol=1e-6)


def test_kernel_interpret_matches_oracle():
    rng = np.random.default_rng(2)
    k, d, p = 8, 3, 256                       # p % 128 == 0 (kernel gate)
    eta = _bounded_degree_eta(rng, k, d)
    sp = topology.sparsify_eta(jnp.asarray(eta), d)
    buf = jnp.asarray(rng.standard_normal((k, p)).astype(np.float32))
    got = np.asarray(ops.sparse_mix(sp.idx, sp.val, buf, buf,
                                    jnp.float32(0.3), force_kernel=True))
    want = ref.sparse_mix(np.asarray(sp.idx), np.asarray(sp.val),
                          np.asarray(buf), np.asarray(buf), 0.3)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_sparse_radio_stack_matches_dense_adjacency():
    mob = MobilityConfig(kind="platoon", speed=25.0, radio_range=100.0,
                         seed=7)
    pos = trace(mob.kind, 5, 6, speed=mob.speed,
                speed_jitter=mob.speed_jitter, area=mob.area, dt=mob.dt,
                seed=mob.seed)
    adj = adjacency_stack(mob, 5, 6)
    stats = degree_stats(adj)
    d = int(stats["max_degree_overall"])
    assert d >= 1
    assert stats["max_degree"].shape == (5,)
    assert stats["isolated"].shape == (5,)
    idx, val = sparse_radio_stack(pos, mob.radio_range, d,
                                  link_quality=mob.link_quality,
                                  min_quality=mob.min_quality)
    assert idx.shape == (5, 6, d) and val.shape == (5, 6, d)
    # every sparse stack row reconstructs the dense adjacency row
    dense = np.zeros_like(np.asarray(adj))
    for t in range(5):
        np.put_along_axis(dense[t], idx[t], val[t], axis=1)
    np.testing.assert_allclose(dense, np.asarray(adj), atol=1e-6)
    # eta/gamma built from the sparse stack match the dense pipeline
    sp = sparse_eta_stack(jnp.asarray(idx), jnp.asarray(val), "metropolis")
    etas = eta_stack(jnp.asarray(adj), "metropolis")
    np.testing.assert_allclose(
        np.asarray(jax.vmap(topology.densify_eta, in_axes=(0, None))(sp, 6)),
        np.asarray(etas), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sparse_gamma_stack(sp, 0.4)),
                               np.asarray(gamma_stack(etas, 0.4)),
                               atol=1e-6)


def _mini_problem(k=6, n=48):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(k, n, 4)).astype(np.float32)
    w = rng.normal(size=(4,)).astype(np.float32)
    y = (x @ w).astype(np.float32)
    items = np.arange(k * 16 * 2).reshape(k, 16, 2) % 53

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    def init_params(rng_):
        return {"w": jax.random.normal(rng_, (4,)) * 0.1}

    return loss_fn, init_params, {"x": x, "y": y}, jnp.asarray(items)


def _final_params(fed, rounds=3, **kw):
    loss_fn, init_params, data, items = _mini_problem(fed.num_nodes)
    tr = cdfl.build_trainer(loss_fn, fed,
                            TrainConfig(batch_size=8, learning_rate=1e-2,
                                        seed=0), **kw)
    st = tr.init(jax.random.PRNGKey(0), init_params, items)
    final, metrics = tr.run_rounds(st, data, rounds)
    return np.asarray(final.params["w"]), metrics


@pytest.mark.parametrize("algorithm", ["cdfl", "dpsgd"])
def test_sparse_training_matches_dense_when_degree_covers(algorithm):
    # ring topology: true degree 2, so D=2 makes sparse == dense
    fed = FedConfig(num_nodes=6, topology="ring", algorithm=algorithm,
                    local_steps=2)
    w_dense, md = _final_params(fed)
    w_sparse, ms = _final_params(
        dataclasses.replace(fed, mixing_format="sparse", degree=2))
    np.testing.assert_allclose(w_sparse, w_dense, atol=1e-5)
    assert np.isfinite(np.asarray(ms["loss"])).all()


def _dpsgd_oracle(loss_fn, train_cfg, params, data, eta, gamma, rounds,
                  rng, local_steps, batch_size):
    """D-PSGD from primitives: every local step mixes each parameter
    leaf by the eq. 5 delta form, then takes one pytree-Adam step per
    node; a round's minibatch indices come from ``fold_in(rng, r)``."""
    from repro.optim import adam
    opt = adam(train_cfg.learning_rate, train_cfg.beta1, train_cfg.beta2,
               train_cfg.eps, train_cfg.weight_decay, train_cfg.grad_clip)
    opt_state = jax.vmap(opt.init)(params)
    eta = jnp.asarray(eta, jnp.float32)
    row = eta.sum(axis=1)
    k, n = data["x"].shape[:2]

    def mix(leaf):
        flat = leaf.reshape(k, -1)
        return (flat + gamma * (eta @ flat - row[:, None] * flat)
                ).reshape(leaf.shape)

    for r in range(rounds):
        idx = jax.random.randint(jax.random.fold_in(rng, r),
                                 (k, local_steps, batch_size), 0, n)
        for t in range(local_steps):
            params = jax.tree.map(mix, params)
            batch = jax.tree.map(
                lambda a: jnp.stack([a[i][idx[i, t]] for i in range(k)]),
                data)
            grads = jax.vmap(jax.grad(loss_fn))(params, batch)
            params, opt_state = jax.vmap(opt.update)(grads, opt_state,
                                                     params)
    return params


def test_dpsgd_matches_per_step_gossip_oracle():
    loss_fn, init_params, data, items = _mini_problem()
    fed = FedConfig(num_nodes=6, topology="ring", algorithm="dpsgd",
                    local_steps=3)
    train_cfg = TrainConfig(batch_size=8, learning_rate=1e-2, seed=0)
    tr = cdfl.build_trainer(loss_fn, fed, train_cfg)
    st = tr.init(jax.random.PRNGKey(0), init_params, items,
                 same_init=False)
    eta = tr.eta_fn(st)
    gamma = topology.stable_gamma(eta, fed.gamma)
    rounds, rng = 3, jax.random.PRNGKey(11)
    data = jax.tree.map(jnp.asarray, data)
    expect = _dpsgd_oracle(loss_fn, train_cfg, st.params, data, eta,
                           gamma, rounds, rng, fed.local_steps,
                           train_cfg.batch_size)
    final, _ = tr.run_rounds(st, data, rounds, rng=rng)
    np.testing.assert_allclose(np.asarray(final.params["w"]),
                               np.asarray(expect["w"]), atol=1e-6)


def test_dpsgd_opt_state_is_flat_resident():
    loss_fn, init_params, data, items = _mini_problem()
    fed = FedConfig(num_nodes=6, topology="ring", algorithm="dpsgd",
                    local_steps=2)
    tr = cdfl.build_trainer(loss_fn, fed,
                            TrainConfig(batch_size=8, learning_rate=1e-2,
                                        seed=0))
    st = tr.init(jax.random.PRNGKey(0), init_params, items)
    final, _ = tr.run_rounds(st, data, 4)
    assert final.opt.m.ndim == 2              # (K, P) moment buffers
    np.testing.assert_array_equal(np.asarray(final.opt.step),
                                  4 * 2 * np.ones(6))


def test_sparse_run_with_crash_faults_stays_finite():
    fed = FedConfig(
        num_nodes=6, topology="full", algorithm="cdfl", local_steps=2,
        mobility=MobilityConfig(kind="platoon", radio_range=120.0, seed=2),
        faults=FaultConfig(kinds=("crash",), crash_rate=0.3,
                           recover_rate=0.5, seed=4),
        mixing_format="sparse", degree=3)
    w, metrics = _final_params(fed, rounds=4)
    assert np.isfinite(w).all()
    assert np.isfinite(np.asarray(metrics["loss"])).all()
    assert metrics["health"].shape == (4, 6)


def test_constant_sparse_stacks_broadcast():
    eta = topology.mixing_weights(
        jnp.asarray(topology.adjacency("ring", 5)), "uniform")
    sp = topology.sparsify_eta(eta, 2)
    etas, gammas = constant_sparse_stacks(sp, jnp.float32(0.3), 7)
    assert etas.idx.shape == (7, 5, 2)
    assert gammas.shape == (7,)
    np.testing.assert_array_equal(np.asarray(etas.val[3]),
                                  np.asarray(sp.val))


def test_sparse_scenario_stacks_shapes():
    mob = MobilityConfig(kind="platoon", radio_range=150.0, seed=9)
    sp, gammas = sparse_scenario_stacks(mob, 6, 8, rule="uniform",
                                        gamma_cap=0.4, degree=3)
    assert isinstance(sp, topology.SparseEta)
    assert sp.idx.shape == (6, 8, 3)
    assert gammas.shape == (6,)
    assert np.isfinite(np.asarray(sp.val)).all()
