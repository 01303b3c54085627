"""Per-architecture smoke tests (spec deliverable f) + model correctness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, get_smoke_arch
from repro.models import stubs, transformer
from repro.optim import adam

B, S = 2, 32


def _batch(cfg, rng):
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.modality == "vision":
        batch["embeds"] = stubs.vision_patch_embeddings(rng, cfg, B)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_smoke_forward_and_train_step(arch):
    """Reduced variant: forward + one Adam step on CPU, shapes + no NaNs."""
    cfg = get_smoke_arch(arch)
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    batch = _batch(cfg, rng)
    logits, aux = transformer.forward(params, cfg, batch, group_size=B * S)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())

    opt = adam(1e-3)
    opt_state = opt.init(params)
    loss0, grads = jax.value_and_grad(
        lambda p: transformer.loss_fn(p, cfg, batch, group_size=B * S)
    )(params)
    params2, _ = opt.update(grads, opt_state, params)
    loss1 = transformer.loss_fn(params2, cfg, batch, group_size=B * S)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(loss1) < float(loss0)      # one step on same batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_smoke_decode_step(arch):
    cfg = get_smoke_arch(arch)
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    state = transformer.init_decode(cfg, B, S)
    tok = jax.random.randint(rng, (B,), 0, cfg.vocab_size)
    logits, state2 = transformer.decode_step(params, cfg, state, tok)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert int(state2.pos) == 1


@pytest.mark.parametrize("arch", ["granite-8b", "rwkv6-7b", "zamba2-1.2b",
                                  "qwen3-1.7b", "musicgen-medium"])
def test_decode_matches_forward(arch):
    """Autoregressive decode == teacher-forced forward (same params)."""
    cfg = get_smoke_arch(arch)
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    tokens = jax.random.randint(rng, (B, 16), 0, cfg.vocab_size)
    logits, _ = transformer.forward(params, cfg, {"tokens": tokens},
                                    group_size=B * 16)
    state = transformer.init_decode(cfg, B, 16)
    outs = []
    for t in range(16):
        lg, state = transformer.decode_step(params, cfg, state,
                                            tokens[:, t])
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits),
                               atol=2e-4, rtol=2e-3)


def test_sliding_window_ring_buffer_decode():
    cfg = get_smoke_arch("granite-8b")
    rng = jax.random.PRNGKey(1)
    params = transformer.init_params(rng, cfg)
    tokens = jax.random.randint(rng, (B, 24), 0, cfg.vocab_size)
    win = 4
    logits, _ = transformer.forward(params, cfg, {"tokens": tokens},
                                    window_override=win, group_size=B * 24)
    # cache sized to the window only (long_500k mechanism)
    state = transformer.init_decode(cfg, B, 24, window_override=win)
    k_cache = jax.tree.leaves(state.states)[0]
    assert k_cache.shape[2] == win            # (L, B, win, KV, D)
    outs = []
    for t in range(24):
        lg, state = transformer.decode_step(params, cfg, state,
                                            tokens[:, t],
                                            window_override=win)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(logits),
                               atol=2e-4, rtol=2e-3)


def test_moe_capacity_drops_are_bounded():
    cfg = get_smoke_arch("dbrx-132b")
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    batch = _batch(cfg, rng)
    # tiny capacity still finite
    tight = dataclasses.replace(cfg, capacity_factor=0.25)
    logits, aux = transformer.forward(params, tight, batch,
                                      group_size=B * S)
    assert bool(jnp.isfinite(logits).all())
    assert float(aux) > 0.0                   # load-balance loss active


def test_vlm_prefix_embeddings_change_output():
    cfg = get_smoke_arch("internvl2-26b")
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    batch = _batch(cfg, rng)
    logits1, _ = transformer.forward(params, cfg, batch, group_size=2048)
    batch2 = dict(batch, embeds=batch["embeds"] + 1.0)
    logits2, _ = transformer.forward(params, cfg, batch2, group_size=2048)
    assert not np.allclose(np.asarray(logits1), np.asarray(logits2))
    assert logits1.shape[1] == batch["tokens"].shape[1]  # text positions


def test_unroll_equals_scan():
    cfg = get_smoke_arch("qwen3-1.7b")
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    batch = _batch(cfg, rng)
    a, _ = transformer.forward(params, cfg, batch, group_size=B * S)
    b, _ = transformer.forward(params, cfg, batch, group_size=B * S,
                               unroll=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-5)


def test_param_count_analytic_close_to_actual():
    for arch in ["granite-8b", "qwen3-1.7b", "mixtral-8x7b",
                 "mellum2-12b-a2.5b"]:
        cfg = get_smoke_arch(arch)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        actual = sum(l.size for l in jax.tree.leaves(params))
        analytic = cfg.param_count()
        assert abs(actual - analytic) / actual < 0.15, arch
