"""The program's own observability: a profiler span at each layer
boundary of a run, ``jax.monitoring`` counters where host schedules are
generated, and a named scope on every phase of the round scan."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (FaultConfig, FedConfig, IngestConfig,
                                MobilityConfig, TrainConfig)
from repro.experiment import EvalCallback, Experiment, SweepAxes

K, ROUNDS = 8, 5
MOBILE = MobilityConfig(kind="manhattan", radio_range=500.0, speed=10.0,
                        area=1000.0, seed=0)
FAULTY = FaultConfig(kinds=("link_drop", "crash", "straggle"),
                     drop_rate=0.1, crash_rate=0.05, recover_rate=0.3,
                     straggle_rate=0.1, seed=0)
INGEST = IngestConfig(scenario="duplicate_heavy")
SCOPES = ("cdfl.sample", "cdfl.pack", "cdfl.ingest", "cdfl.wire",
          "cdfl.mix", "cdfl.local", "cdfl.grad", "cdfl.adam",
          "cdfl.metrics", "cdfl.heal")
# each host span and the span it sits in
PARENT = {"cdfl.run": None, "cdfl.callbacks": "cdfl.run",
          "cdfl.prepare": "cdfl.run", "cdfl.dispatch": "cdfl.run",
          "cdfl.wait": "cdfl.run", "cdfl.ingest_plan": "cdfl.prepare",
          "cdfl.mixing_stack": "cdfl.prepare",
          "cdfl.fault_plan": "cdfl.prepare"}


def _loss(p, b):
    return jnp.mean((b["x"] @ p["w"] - b["y"][:, None]) ** 2)


def _initp(r):
    return {"w": jax.random.normal(r, (6, 1)) * 0.1}


def _inputs():
    rng = np.random.default_rng(3)
    data = {"x": jnp.asarray(rng.normal(size=(K, 48, 6)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(K, 48)), jnp.float32)}
    items = jnp.asarray(rng.integers(0, 60, (K, 48, 4)))
    return data, items


def _experiment(fmt: str) -> Experiment:
    fed = FedConfig(num_nodes=K, local_steps=2, mixing_format=fmt,
                    degree=4, mobility=MOBILE, faults=FAULTY,
                    ingest=INGEST)
    return Experiment.from_parts(_loss, _initp, fed=fed,
                                 train=TrainConfig(learning_rate=0.05,
                                                   batch_size=4))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two ``Session.run(5)`` calls of a mobile, faulty, ingesting
    sparse fleet under the profiler: the host spans they left and every
    ``/cdfl/`` counter they recorded, in order."""
    from jax.profiler import ProfileData
    data, items = _inputs()
    session = _experiment("sparse").compile(data, items)
    counters = []

    def listen(event, value, **kw):
        if event.startswith("/cdfl/"):
            counters.append((event, value))

    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.monitoring.register_scalar_listener(listen)
    jax.profiler.start_trace(logdir)
    try:
        for _ in range(2):
            session.run(ROUNDS, callbacks=[EvalCallback(
                lambda p: jnp.sum(p["w"] ** 2))])
    finally:
        jax.profiler.stop_trace()
        jax.monitoring.unregister_scalar_listener(listen)
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("cdfl.")]
    return sorted(spans, key=lambda s: (s[1], -s[2])), counters


def _parent(span, spans):
    """Name of the shortest other span that contains ``span``."""
    _, s, e, _ = span
    outer = [(oe - os_, name) for name, os_, oe, _ in spans
             if (os_, oe) != (s, e) and os_ <= s and e <= oe]
    return min(outer)[1] if outer else None


def test_host_spans_nest_at_layer_boundaries(profiled):
    spans, _ = profiled
    names = [s[0] for s in spans]
    assert {name: names.count(name) for name in PARENT} == {
        "cdfl.run": 2, "cdfl.callbacks": 4, "cdfl.prepare": 2,
        "cdfl.dispatch": 2, "cdfl.wait": 2, "cdfl.ingest_plan": 2,
        "cdfl.mixing_stack": 2, "cdfl.fault_plan": 2}
    for span in spans:
        assert _parent(span, spans) == PARENT[span[0]], span[0]
    # within a run: the hooks, the inputs, the dispatch, the wait, hooks
    first = [s[0] for s in spans if s[2] <= spans[0][2]][1:]
    assert [n for n in first if PARENT[n] == "cdfl.run"] == [
        "cdfl.callbacks", "cdfl.prepare", "cdfl.dispatch", "cdfl.wait",
        "cdfl.callbacks"]


def test_run_span_carries_its_start_round(profiled):
    spans, _ = profiled
    runs = [s for s in spans if s[0] == "cdfl.run"]
    assert [s[3] for s in runs] == [{"start_round": 0},
                                    {"start_round": ROUNDS}]


def test_schedule_counters_built_and_used(profiled):
    _, counters = profiled
    got = {}
    for event, value in counters:
        got.setdefault(event, []).append(value)
    assert got == {"/cdfl/faults/rounds_built": [5, 10],
                   "/cdfl/faults/rounds_used": [5, 5],
                   "/cdfl/mobility/rounds_built": [5, 10],
                   "/cdfl/mobility/rounds_used": [5, 5]}


@pytest.mark.parametrize("fmt,batched", [
    ("sparse", False), ("dense", False), ("hierarchical", False),
    ("sparse", True), ("dense", True)])
def test_compiled_scan_carries_every_scope(fmt, batched):
    data, items = _inputs()
    exp = _experiment(fmt)
    session = (exp.compile_batch(data, items, SweepAxes(seeds=2))
               if batched else exp.compile(data, items))
    text = session.lower(ROUNDS).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    # a scope is a path element of an op's name, ``vmap(<scope>)`` when
    # it was entered under a vmap
    for scope in SCOPES:
        pat = rf"(^|[/(]){re.escape(scope)}[)/]"
        assert any(re.search(pat, name) for name in op_names), scope
    # the two halves of a local step sit inside the local phase
    for inner in ("cdfl.grad", "cdfl.adam"):
        pat = rf"cdfl\.local\)?/(.*/)?(vmap\()?{re.escape(inner)}[)/]"
        assert any(re.search(pat, name) for name in op_names), inner


def test_lower_takes_the_run_callbacks():
    """``Session.lower(rounds, callbacks)`` lowers the program that
    ``run(rounds, callbacks)`` dispatches: with an ``EvalCallback``, the
    one that also evaluates every round."""
    data, items = _inputs()
    session = _experiment("sparse").compile(data, items)
    evaluate = EvalCallback(lambda p: jnp.sum(p["w"] ** 2))
    _, plain = session.lower(ROUNDS).out_info
    _, with_eval = session.lower(ROUNDS, callbacks=[evaluate]).out_info
    assert "eval" not in plain
    assert with_eval["eval"].shape == (ROUNDS, K)


def test_prepare_counts_rounds_of_compiled_stacks(profiled):
    """``/cdfl/prepare/rounds_compiled`` counts the rounds whose mixing
    stack came out of the compiled prepare program: every round of a
    paper study's call (four stations on a static ring, 60 rounds with
    eval), none of a mobility run."""
    name = "/cdfl/prepare/rounds_compiled"
    _, mobile_counters = profiled
    assert sum(v for event, v in mobile_counters if event == name) == 0
    data, items = _inputs()
    data = jax.tree.map(lambda a: a[:4], data)
    session = Experiment.from_parts(
        _loss, _initp, fed=FedConfig(num_nodes=4, local_steps=2),
        train=TrainConfig(learning_rate=0.05, batch_size=4),
    ).compile(data, items[:4])
    counters = []

    def listen(event, value, **kw):
        if event.startswith("/cdfl/"):
            counters.append((event, value))

    jax.monitoring.register_scalar_listener(listen)
    try:
        for _ in range(2):
            session.run(60, callbacks=[EvalCallback(
                lambda p: jnp.sum(p["w"] ** 2))])
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    assert counters == [(name, 60), (name, 60)]


# -- the held expert layer: scopes and route counters -------------------------

MOE_SCOPES = ("cdfl.attn", "cdfl.moe.route", "cdfl.moe.experts",
              "cdfl.moe.combine")


@pytest.fixture(scope="module")
def mellum_session():
    """Mellum2's layer pattern at a small size, chip 1 of a 2-way split
    (4 of 8 experts at top-2), on 2 stations with 2 local steps of one
    16-token sequence."""
    import dataclasses
    from repro.configs import base
    from repro.configs import mellum2_12b_a2_5b as mellum
    from repro.models import transformer
    whole = dataclasses.replace(
        mellum.CONFIG, d_model=32, head_dim=8, d_ff=16, num_heads=4,
        num_kv_heads=2, num_experts=8, experts_held=8, experts_per_token=2,
        vocab_size=64)
    cfg = base.chip_share(whole, 2, 1, 4)
    fed = FedConfig(num_nodes=2, local_steps=2)
    exp = Experiment.from_parts(
        lambda p, b: transformer.loss_and_aux(p, cfg, b),
        lambda r: transformer.init_params(r, cfg), fed=fed,
        train=TrainConfig(batch_size=1))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 4, 17), 32, 64)
    data = {"tokens": tokens[..., :-1], "labels": tokens[..., 1:]}
    return exp.compile(data, tokens[..., :4])


def test_route_counters_recorded_once_per_run(mellum_session):
    got = []

    def listen(event, value, **kw):
        if event.startswith("/cdfl/moe/"):
            got.append((event, value))
    jax.monitoring.register_scalar_listener(listen)
    try:
        results = [mellum_session.run(3) for _ in range(2)]
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    # stations x local steps x layers x rounds x tokens x top-2
    routes = 2 * 2 * 4 * 3 * 16 * 2
    assert [e for e, _ in got] == ["/cdfl/moe/routes",
                                   "/cdfl/moe/routes_held"] * 2
    for i, result in enumerate(results):
        counters = result.metrics["counters"]
        assert got[2 * i][1] == routes
        assert got[2 * i + 1][1] == int(counters["moe/routes_held"].sum())
        assert 0 < got[2 * i + 1][1] < routes


def test_compiled_scan_carries_the_expert_scopes(mellum_session):
    text = mellum_session.lower(2).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in MOE_SCOPES:
        # under autodiff a scope reads jvp(<scope>) or transpose(...)
        inner = re.compile(rf"[/(]{re.escape(scope)}[)/]")
        assert any("cdfl.local/" in n and "cdfl.grad/" in n
                   and inner.search(n) for n in names), scope
