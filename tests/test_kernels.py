"""Pallas kernel sweeps (interpret mode on CPU) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.cnd_sketch import cnd_bitmaps, cnd_popcount
from repro.kernels.consensus_mix import consensus_mix
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels import ops


# --- flash attention ---------------------------------------------------------

@pytest.mark.parametrize("b,sq,sk,h,kv,d", [
    (1, 128, 128, 2, 2, 64),     # MHA
    (2, 256, 256, 4, 2, 64),     # GQA 2:1
    (1, 128, 128, 8, 1, 32),     # MQA
    (1, 512, 512, 2, 2, 128),    # long, wide head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, sk, h, kv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(sq + h), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, window=None,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True, window=None)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_non_square_blocks():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    # cross attention (no causal): Sq != Sk
    out = flash_attention(q, k, v, causal=False, window=None,
                          block_q=32, block_k=128, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=False, window=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


# --- CND sketch --------------------------------------------------------------

@pytest.mark.parametrize("n,f,m,h", [(64, 4, 1024, 3), (500, 8, 8192, 3),
                                     (1000, 16, 4096, 2), (37, 5, 2048, 4)])
def test_cnd_bitmaps_sweep(n, f, m, h):
    r = np.random.default_rng(n)
    items = jnp.asarray(r.integers(0, 1 << 16, size=(n, f)).astype(np.int32))
    out = cnd_bitmaps(items, h, m, interpret=True)
    exp = ref.cnd_bitmaps(items, h, m)
    assert (np.asarray(out) == np.asarray(exp)).all()


def test_cnd_popcount_kernel():
    r = np.random.default_rng(1)
    bm = jnp.asarray(r.integers(0, 1 << 32, size=(3, 256),
                                dtype=np.uint64).astype(np.uint32))
    out = cnd_popcount(bm, interpret=True)
    exp = ref.cnd_popcount(bm)
    assert (np.asarray(out) == np.asarray(exp)).all()


def test_cnd_kernel_end_to_end_cardinality():
    """Kernel bitmaps drive the same cardinality estimate as the oracle."""
    from repro.core import sketch
    r = np.random.default_rng(2)
    pool = r.integers(0, 1 << 20, size=(300, 6)).astype(np.int32)
    items = jnp.asarray(np.concatenate([pool, pool[:100]]))
    bm = cnd_bitmaps(items, 3, 8192, interpret=True)
    est = float(sketch.cardinality(bm, "linear_counting"))
    assert abs(est - 300) / 300 < 0.1


# --- consensus mix -----------------------------------------------------------

@pytest.mark.parametrize("rows,n,dtype", [
    (256, 2, jnp.float32), (512, 4, jnp.float32), (256, 2, jnp.bfloat16),
    (1024, 8, jnp.float32),
])
def test_consensus_mix_sweep(rows, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(rows + n), 3)
    w = jax.random.normal(ks[0], (rows, 128)).astype(dtype)
    nb = jax.random.normal(ks[1], (n, rows, 128)).astype(dtype)
    eta = jax.nn.softmax(jax.random.normal(ks[2], (n,)))
    out = consensus_mix(w, nb, eta, 0.4, block_rows=128, interpret=True)
    exp = ref.consensus_mix(w, nb, eta, 0.4)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("k,p,block,dtype", [
    (4, 1024, 128, jnp.float32), (4, 2048, 512, jnp.float32),
    (8, 512, 128, jnp.float32), (4, 1024, 128, jnp.bfloat16),
    (4, 640, 512, jnp.float32),                     # a partial last slab
])
def test_flat_consensus_kernel_sweep(k, p, block, dtype):
    from repro.kernels.consensus_mix import flat_consensus
    ks = jax.random.split(jax.random.PRNGKey(k + p), 2)
    buf = jax.random.normal(ks[0], (k, p)).astype(dtype)
    a = jax.nn.softmax(jax.random.normal(ks[1], (k, k)))
    out = flat_consensus(a.astype(dtype), buf, block_cols=block,
                         interpret=True)
    exp = jnp.einsum("ki,ip->kp", a, buf.astype(jnp.float32))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp), atol=tol, rtol=tol)


@pytest.mark.parametrize("p,block", [(640, 512), (640, 1024), (1152, 512)])
def test_flat_mix_kernel_partial_last_slab(p, block):
    """A slab width that does not divide P: the last slab's columns past
    P change nothing, and every column mixes as the XLA delta form."""
    from repro.kernels.consensus_mix import flat_mix
    k = 4
    ks = jax.random.split(jax.random.PRNGKey(p + block), 2)
    master = jax.random.normal(ks[0], (k, p))
    eta = jax.nn.softmax(jax.random.normal(ks[1], (k, k)))
    wire = master.astype(jnp.bfloat16)
    out = flat_mix(eta, master, wire, jnp.float32(0.4), block_cols=block,
                   interpret=True)
    w = wire.astype(jnp.float32)
    exp = master + 0.4 * (eta @ w - eta.sum(1)[:, None] * w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-6)


@pytest.mark.parametrize("k,p,cols", [
    (4, 23_936, 23_936),                 # the paper MLP: one slab
    (2, 267_211_008, 32_768),            # two stations of Mellum2's share
    (2, 128 * 2_087_593, 32_768),        # 128 times a prime
    (16, 267_211_008, 16_384),
    (64, 23_936, 4_096),
])
def test_mix_cols_wide_slab_whatever_the_factors(k, p, cols):
    """A slab is as wide as 1 MiB of padded f32 block allows, whatever
    P's factors, and never wider than P."""
    got = ops._mix_cols(k, p)
    assert got == cols
    assert got <= p and (got % 128 == 0 or got == p)
    assert 4 * (-(-k // 8) * 8) * got <= 1 << 20


def _walk(jaxpr, kernel=None):
    """Each equation of a jaxpr and of its sub-jaxprs, with the name of
    the pallas_call it sits in (None outside any)."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        inside = (eqn.params["name"] if eqn.primitive.name == "pallas_call"
                  else kernel)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inside)


@pytest.mark.parametrize("wire_dtype,separate_self", [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True),
], ids=["f32", "bf16", "wire_self"])
def test_flat_mix_paper_width_one_slab(wire_dtype, separate_self):
    """At the paper MLP's (4, 23,936) the mix runs a one-step grid. Its
    output is bitwise the 128-column kernel's, since each column depends
    only on its own column of the inputs; that is checked on inputs
    whose every product and sum is exact in f32, because XLA:CPU's own
    f32 dot rounds differently at different widths. On random inputs it
    matches the XLA delta form."""
    from repro.core import flatten
    from repro.kernels.consensus_mix import flat_mix
    k, p = 4, 23_936
    fn = lambda *a: ops.flat_mix(*a, force_kernel=True)  # noqa: E731

    def operands(exact):
        ks = jax.random.split(jax.random.PRNGKey(17), 4)
        if exact:   # multiples of 1/64 and 1/16, of 7 and 4 bits
            master = jax.random.randint(ks[0], (k, p), -64, 65) / 64
            eta = jax.random.randint(ks[1], (k, k), 0, 9) / 16
            nudge = jax.random.randint(ks[2], (k, p), -2, 3) / 64
            g = jnp.float32(0.5)
        else:
            master = jax.random.normal(ks[0], (k, p))
            eta = jax.nn.softmax(jax.random.normal(ks[1], (k, k)))
            nudge = 1e-3 * jax.random.normal(ks[2], (k, p))
            g = jnp.float32(0.4)
        wself = ((master + nudge).astype(wire_dtype)
                 if separate_self else None)
        return eta, master, master.astype(wire_dtype), g, wself

    args = operands(exact=True)
    grids = {eqn.params["name"]: eqn.params["grid_mapping"].grid
             for eqn, _ in _walk(jax.make_jaxpr(fn)(*args).jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert grids == {"flat_mix": (1,)}
    np.testing.assert_array_equal(
        np.asarray(fn(*args)),
        np.asarray(flat_mix(*args, block_cols=128, interpret=True)))
    eta, master, wire, g, wself = operands(exact=False)
    exp = flatten.mix_flat(master, eta, g, use_kernel=False, wire=wire,
                           wire_self=wself)
    np.testing.assert_allclose(np.asarray(fn(eta, master, wire, g, wself)),
                               np.asarray(exp), atol=1e-6)


def test_consensus_mix_pytree_wrapper():
    w = {"a": jnp.ones((33, 5)), "b": jnp.arange(100.0)}
    nb = {"a": jnp.zeros((3, 33, 5)),
          "b": jnp.stack([jnp.arange(100.0)] * 3)}
    eta = jnp.asarray([0.5, 0.25, 0.25])
    out = ops.consensus_mix_pytree(w, nb, eta, 1.0)
    np.testing.assert_allclose(np.asarray(out["a"]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.arange(100.0), atol=1e-6)


# --- rwkv6 chunked kernel ----------------------------------------------------

@pytest.mark.parametrize("b,s,h,d,chunk", [
    (1, 64, 1, 64, 16), (2, 128, 3, 64, 32), (1, 256, 2, 128, 64),
])
def test_rwkv6_kernel_sweep(b, s, h, d, chunk):
    ks = jax.random.split(jax.random.PRNGKey(s + h), 5)
    r = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, d))) * 0.9 + 0.05
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    y, sf = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    ye, se = ref.rwkv6_scan(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(se),
                               atol=2e-3, rtol=2e-3)


# --- dispatch: the Pallas kernels are never auto-interpreted (PR 5) ---------

def test_consensus_kernels_not_auto_selected_off_tpu(monkeypatch):
    """Off TPU the public consensus wrappers must lower to XLA, not to
    the interpreted Pallas body (~10x slower): poisoning the kernel
    entry points must not affect an auto-dispatched call."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU dispatch behavior")
    from repro.core import flatten as flatten_mod
    from repro.kernels import consensus_mix as cm

    assert not ops.use_pallas()

    def boom(*a, **k):
        raise AssertionError("Pallas kernel auto-selected off TPU")

    monkeypatch.setattr(cm, "flat_mix", boom)
    monkeypatch.setattr(cm, "flat_consensus", boom)
    monkeypatch.setattr(cm, "consensus_mix", boom)

    # fresh shapes so the poisoned modules are actually retraced
    buf = jnp.ones((4, 640))
    eta = jnp.full((4, 4), 0.25)
    out = ops.flat_mix(eta, buf, buf, jnp.float32(0.3))
    assert out.shape == buf.shape
    out = ops.flat_consensus(eta, buf)
    assert out.shape == buf.shape
    w = jnp.ones((192, 128))
    nb = jnp.ones((2, 192, 128))
    out = ops.consensus_mix(w, nb, jnp.asarray([0.5, 0.5]),
                            jnp.float32(0.5), block_rows=96)
    assert out.shape == w.shape
    # the default mix paths stay off the kernel too
    _ = flatten_mod.mix_flat(buf, eta, 0.3)
    _ = flatten_mod.apply_matrix_flat(buf, eta)


# --- dispatch: CND sketch wrappers (PR 8) -----------------------------------

def test_cnd_ops_force_kernel_matches_xla_fallback():
    """The public ``ops.cnd_*`` wrappers hit the Pallas body under
    ``force_kernel`` and the ``core.sketch`` oracle otherwise — both
    must agree bit-for-bit."""
    from repro.core import sketch
    r = np.random.default_rng(8)
    items = jnp.asarray(r.integers(0, 1 << 16, size=(200, 6),
                                   dtype=np.int64).astype(np.int32))
    auto = ops.cnd_bitmaps(items, 3, 4096)
    forced = ops.cnd_bitmaps(items, 3, 4096, force_kernel=True)
    oracle = sketch.build_bitmaps(items, 3, 4096)
    assert (np.asarray(auto) == np.asarray(oracle)).all()
    assert (np.asarray(forced) == np.asarray(oracle)).all()

    counts_auto = ops.cnd_popcount(auto)
    counts_forced = ops.cnd_popcount(forced, force_kernel=True)
    counts_oracle = sketch.set_bits(oracle)
    assert (np.asarray(counts_auto) == np.asarray(counts_oracle)).all()
    assert (np.asarray(counts_forced) == np.asarray(counts_oracle)).all()


def test_cnd_kernels_not_auto_selected_off_tpu(monkeypatch):
    """Same contract as the consensus wrappers: off TPU the ``ops.cnd_*``
    entry points lower to the XLA oracle, never the interpreted kernel."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU dispatch behavior")
    from repro.kernels import cnd_sketch as cs

    assert not ops.use_pallas()

    def boom(*a, **k):
        raise AssertionError("CND Pallas kernel auto-selected off TPU")

    monkeypatch.setattr(cs, "cnd_bitmaps", boom)
    monkeypatch.setattr(cs, "cnd_popcount", boom)

    # fresh shapes so the poisoned module is actually retraced
    r = np.random.default_rng(9)
    items = jnp.asarray(r.integers(0, 1 << 16, size=(65, 3),
                                   dtype=np.int64).astype(np.int32))
    bm = ops.cnd_bitmaps(items, 2, 2048)
    assert bm.shape == (2, 2048 // 32)
    counts = ops.cnd_popcount(bm)
    assert counts.shape == (2,)


# --- grouped matmuls over held experts ---------------------------------------

@pytest.mark.parametrize("sizes", [
    [[10, 0, 30, 5], [64, 0, 0, 0], [1, 2, 3, 4]],   # ragged, one-group, few
    [[0, 0, 0, 0], [16, 16, 16, 16], [7, 9, 0, 40]],  # none held, all held
], ids=["ragged", "empty_and_full"])
def test_grouped_matmul_kernel_matches_xla_under_station_vmap(sizes):
    """The expert kernels in interpret mode equal their XLA form,
    forward and both gradients, under a vmap over 3 stations (each with
    its own routes and weights), rows past the held routes reading 0."""
    from repro.kernels import expert_gmm
    m, k, n = 64, 32, 48
    r = jax.random.split(jax.random.PRNGKey(11), 3)
    lhs = jax.random.normal(r[0], (3, m, k))
    rhs = jax.random.normal(r[1], (3, 4, k, n))
    dout = jax.random.normal(r[2], (3, m, n))
    sizes = jnp.asarray(sizes, jnp.int32)

    def kernel(a, b, s):
        return expert_gmm.grouped_matmul(a, b, s, tm=16, cap=128,
                                         interpret=True)

    def run(fn):
        def loss(a, b):
            return (jax.vmap(fn)(a, b, sizes) * dout).sum()
        return jax.vmap(fn)(lhs, rhs, sizes), jax.grad(loss, (0, 1))(lhs, rhs)

    with jax.default_matmul_precision("highest"):
        got, (dl, dr) = run(kernel)
        want, (wl, wr) = run(expert_gmm.grouped_matmul_xla)
    # float32 sums over 32- and 64-long contractions in another order
    for a, b in ((got, want), (dl, wl), (dr, wr)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)
    held = np.asarray(sizes).sum(-1)
    for st in range(3):
        assert not np.any(np.asarray(got)[st, held[st]:])


def test_grouped_matmul_vmap_folds_into_one_kernel_call():
    from repro.kernels import expert_gmm
    lhs = jnp.ones((2, 32, 16))
    rhs = jnp.ones((2, 4, 16, 16))
    sizes = jnp.full((2, 4), 4, jnp.int32)

    def loss(a, b):
        return jax.vmap(lambda x, w, s: expert_gmm.grouped_matmul(
            x, w, s, tm=16, interpret=True))(a, b, sizes).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(lhs, rhs))
    # forward, input gradient, weight gradient: one call each
    assert text.count("pallas_call") == 3


def _kernel_dots(jaxpr) -> dict:
    """``{pallas_call name: [precision of each dot_general in it]}``."""
    out = {}
    for eqn, kernel in _walk(jaxpr):
        if eqn.primitive.name == "pallas_call":
            out.setdefault(eqn.params["name"], [])
        elif eqn.primitive.name == "dot_general" and kernel:
            out[kernel].append(eqn.params["precision"])
    return out


@pytest.mark.parametrize("precision", [None, jax.lax.Precision.HIGHEST],
                         ids=["default", "highest"])
def test_grouped_matmul_kernels_multiply_at_the_precision_asked(precision):
    """The forward, input-gradient and weight-gradient kernels all take
    the precision asked for (a float32 product left at the TPU's default
    is one bfloat16 pass)."""
    from repro.kernels import expert_gmm
    sizes = jnp.full((4,), 4, jnp.int32)

    def loss(a, b):
        return expert_gmm.grouped_matmul(a, b, sizes, precision=precision,
                                         tm=16, interpret=True).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(
        jnp.ones((32, 16)), jnp.ones((4, 16, 16))).jaxpr
    want = None if precision is None else (precision, precision)
    assert _kernel_dots(jaxpr) == {"expert_gmm": [want, want],
                                       "expert_tgmm": [want]}


def test_grouped_matmul_not_auto_selected_off_tpu(monkeypatch):
    from repro.kernels import expert_gmm
    called = []
    monkeypatch.setattr(expert_gmm, "grouped_matmul",
                        lambda *a, **kw: called.append(1))
    out = ops.grouped_matmul(jnp.ones((8, 4)), jnp.ones((2, 4, 3)),
                             jnp.array([3, 2], jnp.int32))
    assert not called and out.shape == (8, 3)
    np.testing.assert_array_equal(out[5:], 0.0)
