"""C-DFL trainer (Alg. 2) integration: all algorithms, CND weighting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig, TrainConfig
from repro.core import baselines
from repro.core.cdfl import build_trainer
from repro.data import pipeline, redundancy, synthetic
from repro.models import simple
from repro.configs.paper_models import MLP_CONFIG


def _quadratic_setup(alg, rounds=25):
    targets = jnp.asarray([1.0, 2.0, 3.0, 4.0])

    def loss_fn(params, batch):
        return jnp.sum((params["w"] - batch) ** 2)

    fed = FedConfig(num_nodes=4, gamma=0.5, local_steps=2, algorithm=alg)
    train = TrainConfig(learning_rate=0.05)
    tr = build_trainer(loss_fn, fed, train)
    items = jax.random.randint(jax.random.PRNGKey(1), (4, 64, 4), 0, 40)
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: {"w": jax.random.normal(r, (3,))}, items)
    batch = jnp.broadcast_to(targets[:, None], (4, 2))
    for _ in range(rounds):
        state, m = tr.round(state, batch)
    return state, m


@pytest.mark.parametrize("alg", sorted(baselines.ALGORITHMS))
def test_all_algorithms_decrease_loss(alg):
    state, m = _quadratic_setup(alg)
    # nodes pulled toward neighborhood consensus: finite + bounded loss
    loss = np.asarray(m["loss"])
    assert np.isfinite(loss).all()
    w = np.asarray(state.params["w"])
    assert np.isfinite(w).all()
    assert float(m["disagreement"]) < 1.0


def test_cnd_ratios_reflect_injected_redundancy():
    nodes = [redundancy.inject_duplicates(
        synthetic.synthetic_mnist(seed=i, n=320), ratio, seed=i)
        for i, ratio in enumerate([0.25, 0.5, 0.75, 1.0])]
    batcher = pipeline.FederatedBatcher(nodes, 32, 2)
    fed = FedConfig(num_nodes=4)
    train = TrainConfig(learning_rate=1e-3)
    loss = simple.make_mlp_loss(MLP_CONFIG)
    tr = build_trainer(lambda p, b: loss(p, b), fed, train)
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: simple.mlp_init(r, MLP_CONFIG),
                    jnp.asarray(batcher.node_items()))
    ratios = np.asarray(state.ratios)
    assert (np.diff(ratios) > 0).all()       # ordered by distinctness
    np.testing.assert_allclose(ratios, [0.25, 0.5, 0.75, 1.0], atol=0.08)


def test_mlp_federated_training_learns():
    nodes = [synthetic.synthetic_mnist(seed=i, n=160) for i in range(4)]
    test = synthetic.synthetic_mnist(seed=99, n=200)
    batcher = pipeline.FederatedBatcher(nodes, 32, 5, seed=0)
    loss = simple.make_mlp_loss(MLP_CONFIG)
    fed = FedConfig(num_nodes=4, local_steps=5)
    train = TrainConfig(learning_rate=1e-3)

    def eval_fn(p):
        return simple.accuracy(
            simple.mlp_forward(p, jnp.asarray(test.x)), jnp.asarray(test.y))

    tr = baselines.cdfl(lambda p, b: loss(p, b), fed, train, eval_fn=eval_fn)
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: simple.mlp_init(r, MLP_CONFIG),
                    jnp.asarray(batcher.node_items()))
    accs = []
    for r in range(10):
        rb = batcher.next_round()
        state, m = tr.round(state, {"x": jnp.asarray(rb["x"]),
                                    "y": jnp.asarray(rb["y"])})
        accs.append(float(np.asarray(m["eval"]).mean()))
    assert accs[-1] > 0.9                    # separable synthetic task
    assert float(m["disagreement"]) < 1e-2


def test_dpsgd_gossips_every_step():
    state, m = _quadratic_setup("dpsgd", rounds=10)
    assert float(m["disagreement"]) < 0.5


def test_fedavg_reaches_exact_agreement():
    state, m = _quadratic_setup("fedavg", rounds=5)
    # server average => all nodes identical after every round's consensus
    w = np.asarray(state.params["w"])
    # nodes then take local steps, so allow small divergence
    assert float(m["disagreement"]) < 0.2


# --- device-resident multi-round scan driver --------------------------------

def _mnist_trainer(alg="cdfl", local_steps=5, eval_fn=None):
    nodes = [synthetic.synthetic_mnist(seed=i, n=160) for i in range(4)]
    batcher = pipeline.FederatedBatcher(nodes, 32, local_steps)
    loss = simple.make_mlp_loss(MLP_CONFIG)
    fed = FedConfig(num_nodes=4, local_steps=local_steps, algorithm=alg)
    train = TrainConfig(learning_rate=1e-3)
    tr = baselines.ALGORITHMS[alg](lambda p, b: loss(p, b), fed, train,
                                   eval_fn=eval_fn)
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: simple.mlp_init(r, MLP_CONFIG),
                    jnp.asarray(batcher.node_items()))
    data = {"x": jnp.asarray(np.stack([d.x for d in nodes])),
            "y": jnp.asarray(np.stack([d.y for d in nodes]))}
    return tr, state, data, nodes


def test_run_rounds_trains_and_stacks_metrics():
    tr, state, data, _ = _mnist_trainer()
    final, m = tr.run_rounds(state, data, 12)
    loss = np.asarray(m["loss"])
    assert loss.shape == (12, 4)
    assert np.isfinite(loss).all()
    assert loss[-1].mean() < loss[0].mean()
    assert int(final.round) == 12
    assert np.asarray(m["disagreement"]).shape == (12,)
    # Adam stepped local_steps times per round on every node
    assert (np.asarray(final.opt.step) == 12 * 5).all()


def test_run_rounds_deterministic_in_rng():
    tr, state, data, _ = _mnist_trainer()
    f1, m1 = tr.run_rounds(state, data, 4, rng=jax.random.PRNGKey(3))
    tr2, state2, data2, _ = _mnist_trainer()
    f2, m2 = tr2.run_rounds(state2, data2, 4, rng=jax.random.PRNGKey(3))
    for a, b in zip(jax.tree.leaves(f1.params), jax.tree.leaves(f2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m1["loss"]),
                                  np.asarray(m2["loss"]))


@pytest.mark.parametrize("alg", sorted(baselines.ALGORITHMS))
def test_run_rounds_all_algorithms(alg):
    tr, state, data, _ = _mnist_trainer(alg=alg, local_steps=2)
    final, m = tr.run_rounds(state, data, 3)
    assert np.isfinite(np.asarray(m["loss"])).all()
    assert np.isfinite(
        np.asarray(jax.tree.leaves(final.params)[0])).all()


def test_run_rounds_with_eval_fn():
    test = synthetic.synthetic_mnist(seed=99, n=200)

    def eval_fn(p):
        return simple.accuracy(
            simple.mlp_forward(p, jnp.asarray(test.x)), jnp.asarray(test.y))

    tr, state, data, _ = _mnist_trainer(eval_fn=eval_fn)
    final, m = tr.run_rounds(state, data, 10)
    accs = np.asarray(m["eval"])
    assert accs.shape == (10, 4)
    assert accs[-1].mean() > 0.9              # separable synthetic task


# --- flat-resident Adam: parity against the pytree-Adam oracle --------------
#
# FedState carries the Adam moments as (K, P) buffers and the local
# steps run entirely on the flat buffer. The oracle below re-implements
# a round from primitives — transport exchange on the flat buffer, then
# per-node pytree Adam on the f32 master params — with the scan
# driver's documented batch-sampling contract, and must agree to
# <=1e-6 over 20 rounds for every transport and under a mobility stack.

from repro.core import flatten, topology, transport as transport_lib
from repro.configs.base import MobilityConfig
from repro.optim import adam as make_adam


def _oracle_run(fed, train_cfg, state, data, rounds, rng, etas, gammas,
                trans, loss=None):
    """Pytree-Adam reference: flat mix via the transport, leaf-space
    local steps on f32 master params (the gradient is taken at the
    params' own dtype), sampling keyed on the absolute round index.
    Returns the params at their own dtype and the Adam state."""
    loss = loss or simple.make_mlp_loss(MLP_CONFIG)
    opt = make_adam(train_cfg.learning_rate, train_cfg.beta1,
                    train_cfg.beta2, train_cfg.eps,
                    train_cfg.weight_decay, train_cfg.grad_clip)
    dtypes = jax.tree.map(lambda l: l.dtype, state.params)
    layout = flatten.make_layout(state.params)
    params = flatten.unflatten(flatten.flatten(state.params, layout)[0],
                               layout, cast=False)
    opt_state = jax.vmap(opt.init)(params)
    tstate = state.tstate
    max_items = data["x"].shape[1]
    k, s, b = fed.num_nodes, fed.local_steps, train_cfg.batch_size
    for r in range(rounds):
        key = jax.random.fold_in(rng, r)
        idx = jax.random.randint(key, (k, s, b), 0, max_items)
        buf, _ = flatten.flatten(params, layout)
        buf, tstate = trans.exchange(buf, etas[r], gammas[r], tstate,
                                     jnp.int32(r))
        params = flatten.unflatten(buf, layout, cast=False)

        def one_node(p, o, nd, ni):
            for t in range(s):
                batch = jax.tree.map(lambda a: a[ni[t]], nd)
                view = jax.tree.map(lambda l, d: l.astype(d), p, dtypes)
                _, grads = jax.value_and_grad(loss)(view, batch)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32),
                                     grads)
                p, o = opt.update(grads, o, p)
            return p, o

        ps, os_ = [], []
        for i in range(k):
            p_i = jax.tree.map(lambda l: l[i], params)
            o_i = jax.tree.map(lambda l: l[i], opt_state)
            p_i, o_i = one_node(p_i, o_i,
                                jax.tree.map(lambda a: a[i], data),
                                idx[i])
            ps.append(p_i)
            os_.append(o_i)
        params = jax.tree.map(lambda *ls: jnp.stack(ls), *ps)
        opt_state = jax.tree.map(lambda *ls: jnp.stack(ls), *os_)
    return jax.tree.map(lambda l, d: l.astype(d), params, dtypes), opt_state


def _parity_setup(fed_kw, rounds=20, local_steps=2):
    nodes = [synthetic.synthetic_mnist(seed=i, n=96) for i in range(4)]
    batcher = pipeline.FederatedBatcher(nodes, 16, local_steps)
    loss = simple.make_mlp_loss(MLP_CONFIG)
    fed = FedConfig(num_nodes=4, local_steps=local_steps, **fed_kw)
    train_cfg = TrainConfig(learning_rate=1e-3, batch_size=16)
    tr = build_trainer(lambda p, b: loss(p, b), fed, train_cfg)
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: simple.mlp_init(r, MLP_CONFIG),
                    jnp.asarray(batcher.node_items()))
    data = {"x": jnp.asarray(np.stack([d.x for d in nodes])),
            "y": jnp.asarray(np.stack([d.y for d in nodes]))}
    return fed, train_cfg, tr, state, data


@pytest.mark.parametrize("fed_kw", [
    {},
    {"transport": "ring"},
    {"transport": "gossip", "staleness": 2},
], ids=["dense", "ring", "gossip_s2"])
def test_flat_adam_matches_pytree_oracle_per_transport(fed_kw):
    rounds, rng = 20, jax.random.PRNGKey(5)
    fed, train_cfg, tr, state, data = _parity_setup(fed_kw)
    trans = transport_lib.make_transport(fed)
    eta = tr.eta_fn(state)
    gamma = topology.stable_gamma(eta, fed.gamma)
    etas = jnp.broadcast_to(eta, (rounds,) + eta.shape)
    gammas = jnp.full((rounds,), gamma)
    # oracle first: run_rounds DONATES its state buffers
    exp_params, exp_opt = _oracle_run(fed, train_cfg, state, data,
                                      rounds, rng, etas, gammas, trans)
    final, _ = tr.run_rounds(state, data, rounds, rng=rng)
    for a, b in zip(jax.tree.leaves(final.params),
                    jax.tree.leaves(exp_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    # the flat (K, P) moments equal the oracle's pytree moments packed
    layout = flatten.make_layout(exp_params)
    exp_m, _ = flatten.flatten(exp_opt.m, layout)
    exp_v, _ = flatten.flatten(exp_opt.v, layout)
    np.testing.assert_allclose(np.asarray(final.opt.m), np.asarray(exp_m),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(final.opt.v), np.asarray(exp_v),
                               atol=1e-6)
    assert (np.asarray(final.opt.step) == rounds * fed.local_steps).all()


def test_flat_adam_matches_pytree_oracle_under_mobility():
    rounds, rng = 10, jax.random.PRNGKey(6)
    mob = MobilityConfig(kind="platoon", speed=20.0, radio_range=250.0,
                         seed=3)
    fed, train_cfg, tr, state, data = _parity_setup({"mobility": mob})
    etas, gammas = tr.mixing_stack(state, rounds)
    trans = transport_lib.make_transport(fed)
    exp_params, _ = _oracle_run(fed, train_cfg, state, data, rounds, rng,
                                etas, gammas, trans)
    final, _ = tr.run_rounds(state, data, rounds, rng=rng)
    for a, b in zip(jax.tree.leaves(final.params),
                    jax.tree.leaves(exp_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_bf16_params_keep_an_f32_master_between_local_steps():
    """A bf16-param model trains on the f32 master buffer: each local
    step reads bf16 views of it, and the Adam update lands in f32, so
    steps smaller than a bf16 ulp still accumulate. The oracle keeps the
    same f32 master between steps; a loop that requantized the params
    after every step would leave them where they started."""
    rounds, rng = 3, jax.random.PRNGKey(4)
    rs = np.random.default_rng(2)
    data = {"x": jnp.asarray(rs.normal(size=(4, 32, 6)), jnp.float32),
            "y": jnp.asarray(rs.normal(size=(4, 32)), jnp.float32)}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"].astype(jnp.float32) + p["b"]
                         - b["y"]) ** 2)

    def init(r):
        return {"w": (1.0 + jax.random.uniform(r, (6,))).astype(
                    jnp.bfloat16),
                "b": jnp.ones((1,), jnp.bfloat16)}

    fed = FedConfig(num_nodes=4, local_steps=3)
    train_cfg = TrainConfig(learning_rate=1e-3, batch_size=8)
    tr = build_trainer(loss, fed, train_cfg)
    items = jax.random.randint(jax.random.PRNGKey(1), (4, 32, 4), 0, 50)
    state = tr.init(jax.random.PRNGKey(0), init, items)
    eta = tr.eta_fn(state)
    gamma = topology.stable_gamma(eta, fed.gamma)
    etas = jnp.broadcast_to(eta, (rounds,) + eta.shape)
    gammas = jnp.full((rounds,), gamma)
    init_w = np.asarray(state.params["w"], np.float32)
    exp_params, exp_opt = _oracle_run(
        fed, train_cfg, state, data, rounds, rng, etas, gammas,
        transport_lib.make_transport(fed), loss=loss)
    final, _ = tr.run_rounds(state, data, rounds, rng=rng)
    for a, b in zip(jax.tree.leaves(final.params),
                    jax.tree.leaves(exp_params)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    layout = flatten.make_layout(exp_params)
    np.testing.assert_allclose(np.asarray(final.opt.m),
                               np.asarray(flatten.flatten(exp_opt.m,
                                                          layout)[0]),
                               atol=1e-6)
    # 9 steps of about lr each move the weights by more than a bf16 ulp
    # at 1.0-2.0 (2 ** -7), which a step of lr alone never does
    assert np.abs(np.asarray(final.params["w"], np.float32)
                  - init_w).max() >= 2 ** -7


# --- the compiled prepare program -------------------------------------------

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _static_fleet(fmt):
    """A static-graph trainer for eight nodes in ``fmt`` and its state,
    with CND ratios that differ across nodes."""
    k = 8

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    fed = FedConfig(num_nodes=k, local_steps=2, mixing_format=fmt,
                    degree=4 if fmt == "sparse" else None)
    tr = build_trainer(loss_fn, fed, TrainConfig(learning_rate=0.05,
                                                 batch_size=4))
    # node i draws its item tokens from 3 + i values: more duplicates,
    # a lower CND ratio, on the lower nodes
    items = jax.random.randint(jax.random.PRNGKey(1), (k, 48, 2), 0, 60)
    items = items % (3 + jnp.arange(k))[:, None, None]
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: {"w": jax.random.normal(r, (6,))}, items)
    return tr, state


@pytest.mark.parametrize("fmt,start,variants", [
    ("dense", 0, None), ("dense", 60, None), ("sparse", 0, None),
    ("sparse", 60, None), ("hierarchical", 0, None),
    ("hierarchical", 60, None), ("dense", 60, 3), ("sparse", 60, 3)])
def test_prepare_matches_eager_formulas(fmt, start, variants):
    """The compiled prepare gives the round keys the eager
    ``vmap(fold_in)`` gave and the static stacks that the eager
    ``mixing_stack`` gives, bit for bit: on XLA:CPU no rewrite of the
    compiled program (a division turned into a multiplication by a
    reciprocal, say) moves a float of the stacks, so no ulp of slack is
    allowed."""
    rounds = 60
    tr, state = _static_fleet(fmt)
    rng = jax.random.PRNGKey(7)
    ratios, sizes = state.ratios, state.sizes
    expect_keys = jax.vmap(lambda r: jax.random.fold_in(rng, r))(
        jnp.arange(start, start + rounds))
    if variants:
        rng = jax.random.split(rng, variants)
        expect_keys = jax.vmap(lambda key: jax.vmap(
            lambda r: jax.random.fold_in(key, r))(
                jnp.arange(start, start + rounds)))(rng)
        # a (V,)-stacked state: the stacks come from variant 0
        ratios = jnp.stack([ratios, ratios[::-1], ratios])
        sizes = jnp.stack([sizes, sizes[::-1], sizes])
    keys, stacks = tr.prepare(rng, np.int32(start), ratios, sizes,
                              num_rounds=rounds, static_graph=True)
    np.testing.assert_array_equal(np.asarray(keys),
                                  np.asarray(expect_keys))
    expect = tr.mixing_stack(state, rounds, start=start)
    got_leaves, treedef = jax.tree.flatten(stacks)
    assert treedef == jax.tree.structure(expect)
    for got, want in zip(got_leaves, jax.tree.leaves(expect)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert tr.prepare(rng, np.int32(start), ratios, sizes,
                      num_rounds=rounds, static_graph=False)[1] is None


def test_continuing_static_session_lowers_nothing_after_second_call():
    """Three continuing 60-round calls of a static-graph session: the
    first two may lower programs, the third lowers none (the prepare
    program takes its start round as an argument)."""
    from repro.experiment import EvalCallback, Experiment

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    rng = np.random.default_rng(0)
    data = {"x": jnp.asarray(rng.normal(size=(4, 40, 6)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(4, 40)), jnp.float32)}
    items = jnp.asarray(rng.integers(0, 60, (4, 40, 4)))
    session = Experiment.from_parts(
        loss_fn, lambda r: {"w": jax.random.normal(r, (6,))},
        fed=FedConfig(num_nodes=4, local_steps=2),
        train=TrainConfig(learning_rate=0.05, batch_size=4),
    ).compile(data, items)
    evaluate = EvalCallback(lambda p: jnp.sum(p["w"] ** 2))
    lowered = []

    def count(event, duration_secs=0.0, **kw):
        if event == COMPILE_EVENT:
            lowered[-1] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        for _ in range(3):
            lowered.append(0)
            session.run(60, callbacks=[evaluate])
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert lowered[0] > 0 and lowered[2] == 0, lowered
    assert session.rounds_completed == 180


@pytest.mark.parametrize("fmt", ["dense", "sparse"])
def test_scalar_loss_and_loss_with_empty_aux_lower_alike(fmt):
    """A loss may return ``(loss, aux)``; one that returns a scalar traces
    the very program that the same loss returning ``(loss, {})`` does, so
    models without counters run what they ran before."""
    loss = simple.make_mlp_loss(MLP_CONFIG)
    fed = FedConfig(num_nodes=4, local_steps=3, mixing_format=fmt,
                    degree=2)
    train = TrainConfig(batch_size=8)
    key = jax.random.PRNGKey(0)
    data = {"x": jax.random.normal(key, (4, 32, 784)),
            "y": jax.random.randint(key, (4, 32), 0, 10)}
    items = jax.random.randint(key, (4, 32, 16), 0, 4096)
    texts = []
    for fn in (loss, lambda p, b: (loss(p, b), {})):
        tr = build_trainer(fn, fed, train)
        state = tr.init(key, lambda r: simple.mlp_init(r, MLP_CONFIG), items)
        texts.append(tr.lower_rounds(state, data, 2, rng=key).as_text())
    assert texts[0] == texts[1]


def test_loss_counters_sum_into_round_metrics():
    """The aux counters of a loss are summed over local steps and nodes
    into each round's ``metrics["counters"]``."""
    def loss(p, b):
        return jnp.sum((p["w"] - b) ** 2), {"seen": jnp.int32(b.shape[0])}
    fed = FedConfig(num_nodes=4, local_steps=3)
    tr = build_trainer(loss, fed, TrainConfig(batch_size=2))
    items = jax.random.randint(jax.random.PRNGKey(1), (4, 16, 4), 0, 40)
    state = tr.init(jax.random.PRNGKey(0),
                    lambda r: {"w": jax.random.normal(r, (3,))}, items)
    data = jnp.ones((4, 16, 3))
    _, metrics = tr.run_rounds(state, data, 5)
    np.testing.assert_array_equal(metrics["counters"]["seen"],
                                  np.full(5, 4 * 3 * 2))
