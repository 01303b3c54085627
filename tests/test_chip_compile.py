"""The mixing kernels compile for a TPU v5e at real widths.

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


@pytest.mark.parametrize("wire_dtype", [jnp.float32, jnp.bfloat16])
def test_flat_mix_compiles_for_v5e(one_chip, width, wire_dtype):
    k = 4
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    hlo = _compile(
        lambda e, m, w, g: consensus_mix.flat_mix(e, m, w, g, block_cols=128),
        s((k, k)), s((k, width)), s((k, width), wire_dtype), s(()))
    assert ops.pallas_kernels(hlo) == {"flat_mix"}


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
