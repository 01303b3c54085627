"""Mellum2's share against its plain float32 reference, at a small size on
the CPU, on seeded random weights: hidden 64, head_dim 16, 8 query heads
with 2 key/value heads, 8 experts at top-2, a 64-row vocabulary, window
8, 32 tokens, one whole period (sliding, sliding, sliding, full); a chip
of a 2-way layer split holds 4 heads, 4 experts and 32 rows.

The reference (``bench/reference/mellum2.py``) is written from the
published config and imports nothing of the program. Both run float32
on XLA:CPU, where a dot is an exact-precision float32 dot, so they
differ only by the order of float32 sums: tolerances are a few float32
ulps of the compared values' scale.
"""
import copy
import dataclasses
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.configs import mellum2_12b_a2_5b as mellum
from repro.models import attention, moe, transformer

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

REF = spec.module("reference", "mellum2")
S = 32
held_forward = jax.jit(moe.held_forward, static_argnums=1)
attend = jax.jit(attention.forward, static_argnums=1,
                 static_argnames="kind")


def _ref_config(chips: int, chip: int) -> dict:
    """The reference's configuration: the benchmark's file at the small
    size, for chip ``chip`` of ``chips``."""
    cfg = copy.deepcopy(spec.config("mellum2-ep8-k2-ring"))
    cfg.update(hidden_size=64, head_dim=16, moe_intermediate_size=32,
               num_attention_heads=8 // chips,
               num_key_value_heads=2 // chips, num_experts=8 // chips,
               num_experts_per_tok=2, vocab_size=64 // chips,
               sliding_window=8)
    cfg["deployment"] = dict(
        cfg["deployment"], chips_per_layer=chips, chip=chip,
        published=dict(num_hidden_layers=4, num_experts=8,
                       num_attention_heads=8, num_key_value_heads=2,
                       vocab_size=64))
    return cfg


def _share(chips: int, chip: int):
    whole = dataclasses.replace(
        mellum.CONFIG, d_model=64, head_dim=16, d_ff=32, num_heads=8,
        num_kv_heads=2, num_experts=8, experts_held=8, experts_per_token=2,
        vocab_size=64,
        attention_kinds=(("sliding_attention",
                          dataclasses.replace(mellum.SLIDING, window=8)),
                         ("full_attention", mellum.FULL)))
    return base.chip_share(whole, chips, chip, 4)


def _names(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf for path, leaf in flat}


@pytest.fixture(scope="module", params=[(1, 0), (2, 1)],
                ids=["uncut", "chip1of2"])
def pair(request):
    chips, chip = request.param
    cfg, ref_cfg = _share(chips, chip), _ref_config(chips, chip)
    key = jax.random.PRNGKey(3)
    params = transformer.init_params(key, cfg)
    ref_params = REF.init(key, ref_cfg, jnp.float32)
    lo = cfg.vocab_offset
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, S + 1), lo,
                                lo + cfg.held_vocab())
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    return cfg, ref_cfg, params, ref_params, batch


def test_initial_weights_are_the_reference_s(pair):
    cfg, ref_cfg, params, ref_params, _ = pair
    named = _names(params)
    assert tuple(named) == REF.LEAVES
    for name, leaf in named.items():
        np.testing.assert_array_equal(leaf, ref_params[name], err_msg=name)
    assert sum(x.size for x in named.values()) == cfg.param_count()


def test_logits_loss_and_gradients_match_reference(pair):
    cfg, ref_cfg, params, ref_params, batch = pair
    logits, _ = jax.jit(transformer.forward, static_argnums=1)(
        params, cfg, batch)
    ref_logits = jax.jit(jax.vmap(
        lambda t: REF.forward(ref_params, t, ref_cfg)[0]))(batch["tokens"])
    # logits are O(0.1): float32 sums over 64- and 512-wide dots in
    # another order differ by ~1e-7
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-5, atol=1e-6)

    def prog_loss(p):
        return transformer.loss_and_aux(p, cfg, batch)[0]
    loss, grads = jax.jit(jax.value_and_grad(prog_loss))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, batch, ref_cfg)))(ref_params)
    # the loss is O(4): a few float32 ulps
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    for name, g in _names(grads).items():
        scale = float(jnp.abs(ref_grads[name]).max())
        # each gradient to a few ulps of its own largest element
        np.testing.assert_allclose(g, ref_grads[name], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)


def _dot_precisions(jaxpr, out: list) -> list:
    """The ``precision`` of every dot_general in ``jaxpr`` and the
    jaxprs nested in its equations."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_precisions(sub, out)
    return out


@pytest.mark.parametrize("precision", ["", "highest"])
def test_matmul_precision_reaches_every_product(pair, precision):
    """``matmul_precision`` is the precision of every product of the
    loss and its gradient; "" leaves the backend's default to all but
    the router, which always scores at the highest."""
    cfg, _, params, _, batch = pair
    cfg = dataclasses.replace(cfg, matmul_precision=precision)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: transformer.loss_and_aux(p, cfg, batch)[0]))(params)
    found = _dot_precisions(jaxpr.jaxpr, [])
    highest = (jax.lax.Precision.HIGHEST,) * 2
    routers = sum(p == highest for p in found)
    assert routers >= 3 * cfg.num_layers        # forward and two gradients
    if precision:
        assert routers == len(found)
    else:
        assert set(found) - {highest} == {None}
    """Experts 0-3 and 4-7 held on two chips give parts of the expert
    layer that add up to the uncut reference layer; so do heads 0-3 and
    4-7 (with their key/value heads) of attention."""
    whole_ref = _ref_config(1, 0)
    key = jax.random.PRNGKey(5)
    p = REF.init(key, whole_ref, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(6), (1, S, 64))
    n = "layers_list/3/"
    want, _, _ = REF.experts(h[0], p[n + "ffn/router"], p[n + "ffn/w_gate"],
                             p[n + "ffn/w_up"], p[n + "ffn/w_down"], top=2,
                             first=0)
    parts = []
    for chip in (0, 1):
        cfg = _share(2, chip)
        held = slice(4 * chip, 4 * chip + 4)
        ffn = {"router": p[n + "ffn/router"],
               "w_gate": p[n + "ffn/w_gate"][held],
               "w_up": p[n + "ffn/w_up"][held],
               "w_down": p[n + "ffn/w_down"][held]}
        parts.append(held_forward(ffn, cfg, h)[0][0])
    # float32 sums of two parts against one sum: ulps of O(1) outputs
    np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-5,
                               atol=1e-6)

    for kind in ("sliding_attention", "full_attention"):
        want = REF.attention(h[0], p[n + "mix/wq"], p[n + "mix/wk"],
                             p[n + "mix/wv"], p[n + "mix/wo"], heads=8,
                             kv_heads=2, head_dim=16, kind=kind,
                             config=whole_ref)
        parts = []
        for chip in (0, 1):
            cfg = _share(2, chip)
            q = slice(64 * chip, 64 * chip + 64)        # 4 heads of 16
            kv = slice(16 * chip, 16 * chip + 16)       # 1 head of 16
            mix = {"wq": p[n + "mix/wq"][:, q], "wk": p[n + "mix/wk"][:, kv],
                   "wv": p[n + "mix/wv"][:, kv], "wo": p[n + "mix/wo"][q]}
            parts.append(attend(mix, cfg, h,
                                kind=cfg.attention_kind(kind))[0])
        np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-5,
                                   atol=1e-6, err_msg=kind)


def test_sliding_layer_forgets_what_lies_a_window_back():
    cfg = _share(1, 0)
    p = transformer.init_params(jax.random.PRNGKey(7), cfg)
    mix = p["layers_list"][0]["mix"]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, S, 64))
    q, window = 20, 8
    moved = x.at[0, : q - window + 1].add(1.0)          # positions <= q - w
    for name, same in (("sliding_attention", True),
                       ("full_attention", False)):
        kind = cfg.attention_kind(name)
        a = attend(mix, cfg, x, kind=kind)[0, q]
        b = attend(mix, cfg, moved, kind=kind)[0, q]
        assert bool(jnp.allclose(a, b, atol=1e-6)) == same, name
    near = x.at[0, q - window + 1].add(1.0)             # inside the window
    kind = cfg.attention_kind("sliding_attention")
    assert not jnp.allclose(attend(mix, cfg, x, kind=kind)[0, q],
                            attend(mix, cfg, near, kind=kind)[0, q])


def _hf_yarn(dim, base_, factor, orig, beta_fast, beta_slow):
    """HF ``_compute_yarn_parameters`` (truncate=True), in NumPy."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base_))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(dim // 2, dtype=np.float32) - low) / (high - low)
    ramp = np.clip(linear, 0, 1)
    pos_freqs = base_ ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    extra_factor = 1 - ramp
    inv = interpolation * (1 - extra_factor) + extrapolation * extra_factor
    return inv, 0.1 * math.log(factor) + 1.0


def test_yarn_frequencies_and_scale_match_the_published_formula():
    from repro.models import layers
    rope = mellum.FULL.rope
    freqs, scale = layers.rope_table(128, rope)
    want, want_scale = _hf_yarn(128, 500_000.0, 16.0, 8192, 32.0, 1.0)
    # float32 powers of 500,000: a few ulps
    np.testing.assert_allclose(freqs, want, rtol=2e-6)
    assert scale == pytest.approx(want_scale, rel=1e-12)
    # dims that turn fast keep theta's frequency; slow ones are 1/16 of it
    plain = layers.rope_frequencies(128, 500_000.0)
    assert freqs[0] == plain[0]
    np.testing.assert_allclose(freqs[-1], plain[-1] / 16.0, rtol=1e-6)
    sliding, one = layers.rope_table(128, mellum.SLIDING.rope)
    np.testing.assert_array_equal(sliding, plain)
    assert one == 1.0


def test_no_route_is_dropped_when_every_token_picks_one_held_expert():
    cfg = _share(2, 0)                                   # experts 0-3 here
    p = transformer.init_params(jax.random.PRNGKey(9), cfg)
    ffn = dict(p["layers_list"][0]["ffn"])
    # every token's top-2 are experts 0 and 1, both held here
    ffn["router"] = jnp.zeros((64, 8)).at[:, 0].set(4.0).at[:, 1].set(2.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (2, S, 64))) + 1.0
    out, _, counts = held_forward(ffn, cfg, h)
    assert int(counts["moe/routes"]) == int(counts["moe/routes_held"]) \
        == 2 * S * 2
    want = jax.vmap(lambda x: REF.experts(
        x, ffn["router"], ffn["w_gate"], ffn["w_up"], ffn["w_down"], top=2,
        first=0)[0])(h)
    # outputs of O(1) summed over two experts in another order: ulps
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    # the other chip holds neither: its part is 0, and it computes nothing
    other = _share(2, 1)
    out1, _, c1 = held_forward(ffn, other, h)
    assert int(c1["moe/routes_held"]) == 0
    assert not bool(jnp.any(out1))


def test_share_parameters_and_published_widths():
    share = mellum.chip_share()
    assert share.param_count() == 267_211_008
    shapes = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), share))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) \
        == 267_211_008
    assert (share.d_model, share.resolved_head_dim(), share.d_ff) \
        == (2304, 128, 896)
    assert (share.num_experts, share.experts_per_token,
            share.held_experts()) == (64, 8, 8)
    assert (share.held_heads(), share.held_kv_heads(),
            share.held_vocab()) == (4, 1, 12_288)
    assert share.attention_kind("sliding_attention").window == 1024
