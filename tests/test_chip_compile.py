"""The mixing and expert kernels compile for a TPU v5e at real widths.

Interpret mode runs a Pallas kernel body op by op and accepts block
shapes, layouts and primitives that the chip's compiler refuses. These
tests compile each kernel of the round scan for a described (not
attached) ``v5e:2x2`` chip with the installed TPU compiler, at the width
of the paper MLP's flat buffer (P = 23,936 after lane padding), and
check that the compiled program calls the kernel. Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a worker that cannot
describe the chip skips these tests instead of failing collection.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import MLP_CONFIG
from repro.core import flatten
from repro.kernels import consensus_mix, ops, robust_agg, sparse_mix
from repro.models import simple


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def width():
    """P of the paper MLP (784-30-10) as the trainer packs it."""
    params = simple.mlp_init(jax.random.PRNGKey(0), MLP_CONFIG)
    p = flatten.make_layout_one(params).padded
    assert p == 23_936
    return p


def _compile(fn, *shapes):
    """The TPU program text of ``fn`` at these shapes (compiled, not run)."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("wire_dtype,separate_self", [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True),
], ids=["f32", "bf16", "wire_self"])
def test_flat_mix_compiles_for_v5e(one_chip, width, wire_dtype,
                                   separate_self):
    """Four stations of the paper MLP mix in one slab of the whole
    width: a block equal to the array, one grid step."""
    k = 4
    cols = ops._mix_cols(k, width)
    assert cols == width
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    wire = s((k, width), wire_dtype)
    hlo = _compile(
        lambda e, m, w, g, *ws: consensus_mix.flat_mix(
            e, m, w, g, *ws, block_cols=cols),
        s((k, k)), s((k, width)), wire, s(()),
        *([wire] if separate_self else []))
    assert ops.pallas_kernels(hlo) == {"flat_mix"}


@pytest.mark.parametrize("p", [267_211_008, 128 * 2_087_593])
def test_flat_mix_compiles_for_v5e_at_a_large_width(one_chip, p):
    """Two stations of Mellum2's share (P = 267,211,008), and a P that is
    128 times a prime: a buffer this long takes 32,768-column slabs
    (1 MiB of padded f32 block) whatever P's factors, and compiles with
    a partial last slab."""
    k = 2
    cols = ops._mix_cols(k, p)
    assert cols == 32_768 and p % cols
    s = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    hlo = _compile(
        lambda e, m, g: consensus_mix.flat_mix(e, m, m, g, block_cols=cols),
        s((k, k)), s((k, p)), s(()))
    assert ops.pallas_kernels(hlo) == {"flat_mix"}


def test_flat_consensus_compiles_for_v5e(one_chip, width):
    """The A @ BUF kernel in one slab of the paper MLP's width."""
    k = 4
    s = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    hlo = _compile(
        lambda a, b: consensus_mix.flat_consensus(
            a, b, block_cols=ops._mix_cols(k, width)),
        s((k, k)), s((k, width)))
    assert len(ops.pallas_kernels(hlo)) == 1


@pytest.mark.parametrize("wire_dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_mix_compiles_for_v5e(one_chip, width, wire_dtype):
    k, d = 1024, 8
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    hlo = _compile(
        lambda i, v, m, w, g: sparse_mix.sparse_mix(i, v, m, w, w, g),
        s((k, d), jnp.int32), s((k, d)), s((k, width)),
        s((k, width), wire_dtype), s(()))
    assert ops.pallas_kernels(hlo) == {"sparse_mix"}


@pytest.mark.parametrize("wire_dtype", [jnp.float32, jnp.bfloat16])
def test_cluster_mix_compiles_for_v5e(one_chip, width, wire_dtype):
    k, d = 1024, 8
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    hlo = _compile(sparse_mix.cluster_mix,
                   s((k, d), jnp.int32), s((k, d)), s((k, width)),
                   s((k, width), wire_dtype), s((k, width), wire_dtype),
                   s((k,)))
    assert ops.pallas_kernels(hlo) == {"cluster_mix"}


def test_robust_agg_compiles_for_v5e(one_chip, width):
    k = 8
    s = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    hlo = _compile(
        lambda w, mk, b, x: robust_agg.robust_agg(w, mk, b, x,
                                                  block_cols=128),
        s((k, k)), jax.ShapeDtypeStruct((k, k), jnp.bool_, sharding=one_chip),
        s((k, width)), s((k, width)))
    assert ops.pallas_kernels(hlo) == {"robust_agg"}


def test_cnd_sketch_kernels_compile_for_v5e(one_chip):
    from repro.kernels import cnd_sketch
    items = jax.ShapeDtypeStruct((320, 16), jnp.int32, sharding=one_chip)
    hlo = _compile(lambda it: cnd_sketch.cnd_popcount(
        cnd_sketch.cnd_bitmaps(it, 3, 8192)), items)
    assert ops.pallas_kernels(hlo) == {"cnd_bitmaps", "cnd_popcount"}


def _expert_step_hlo(one_chip, precision=None):
    """The grouped expert matmuls at Mellum2's share: two stations, each
    2,048 tokens x top-8 routes over 8 held experts, hidden 2,304, expert
    width 896; forward and both gradients of the SwiGLU experts under the
    trainer's vmap over stations."""
    from repro.kernels import expert_gmm
    s_, m, d, f, g = 2, 2048 * 8, 2304, 896, 8
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    gmm = functools.partial(expert_gmm.grouped_matmul, precision=precision)

    def step(x, wg, wu, wd, sizes):
        def node(x, wg, wu, wd, sizes):
            def loss(x, wg, wu, wd):
                h = (jax.nn.silu(gmm(x, wg, sizes)) * gmm(x, wu, sizes))
                return gmm(h, wd, sizes).sum()
            return jax.grad(loss, (0, 1, 2, 3))(x, wg, wu, wd)
        return jax.vmap(node)(x, wg, wu, wd, sizes)
    return _compile(step, s((s_, m, d)), s((s_, g, d, f)),
                    s((s_, g, d, f)), s((s_, g, f, d)), s((s_, g), jnp.int32))


def test_expert_kernels_compile_for_v5e(one_chip):
    hlo = _expert_step_hlo(one_chip)
    assert ops.pallas_kernels(hlo) == {"expert_gmm", "expert_tgmm"}


def test_expert_kernels_compile_for_v5e_at_the_highest_precision(one_chip):
    """Float32 contractions, as the Mellum2 cell runs them."""
    hlo = _expert_step_hlo(one_chip, jax.lax.Precision.HIGHEST)
    assert ops.pallas_kernels(hlo) == {"expert_gmm", "expert_tgmm"}
