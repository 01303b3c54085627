"""A run whose timed path is broken underneath comes out not correct, for
each fault a training cell can have: a call that leaves the state as it
was, half of each minibatch left out (the loss the mean over the rest),
and the consensus exchange left out. The harness's look for a chip is
skipped; the rest of the run is the benchmark's own, at small sizes, in
both model families (the half batch is planted in the family's loss)."""
import jax
import jax.numpy as jnp
import pytest

import bench_tiny


def _state_unchanged(monkeypatch, p):
    from repro.experiment import BatchedSession, Session

    def frozen(cls, method, attr):
        run = getattr(cls, method)

        def call(self, *a, **kw):
            keep = jax.tree.map(lambda x: jnp.array(x, copy=True),
                                getattr(self, attr))
            result = run(self, *a, **kw)
            setattr(self, attr, keep)
            return result
        monkeypatch.setattr(cls, method, call)
    frozen(Session, "run", "_state")
    frozen(BatchedSession, "run_batch", "_states")


def _half_batch(monkeypatch, p):
    family = p["family"]
    make = family.model

    def half(config):
        loss, init = make(config)
        return (lambda params, b: loss(params, jax.tree.map(
            lambda a: a[: a.shape[0] // 2], b))), init
    monkeypatch.setattr(family, "model", half)


def _no_exchange(monkeypatch, p):
    from repro.core import transport
    monkeypatch.setattr(transport.DenseTransport, "exchange",
                        lambda self, buf, eta, gamma, state=(), rnd=None,
                        sent=None: (buf, state))


@pytest.mark.parametrize("cell, family", bench_tiny.CASES)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _no_exchange],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_timed_path_is_not_correct(fault, cell, family, monkeypatch):
    p = bench_tiny.parts(cell, family)
    fault(monkeypatch, p)
    result = bench_tiny.run(p)
    assert not result["correct"], result["check"]
