"""The packed engine's Pallas kernels compile for a TPU v5e at real width.

No chip is needed: the TPU compiler compiles for a described `v5e:2x2`
topology. Shapes are ResNet-20's packed buffer (272,250 params -> [2304,
128] fp32) and client stacks at C = 8 and 64, with the row blocks
`kernels/ops.py` picks for them — a kernel that asks for more scoped VMEM
than a v5e core has, or that Mosaic cannot lower, fails here. Interpret
mode (the CPU suite) cannot see either.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import ParamPack
from repro.kernels import ops
from repro.kernels import pruning_mask as pm
from repro.models import resnet_init


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def rows():
    shapes = jax.eval_shape(lambda k: resnet_init(k, depth=20, width=16),
                            jax.random.key(0))
    pack = ParamPack.build(jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype), shapes))
    assert pack.n_total == 272_250
    return pack.rows


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_importance_mask_2d(spec, rows):
    br = ops._packed_block_rows(rows)
    _compile(lambda w, v, t: pm.importance_mask_2d(
        w, v, t, block_rows=br, interpret=False),
        spec(rows, 128), spec(rows, 128), spec())


def test_exponent_histogram(spec, rows):
    br = ops._packed_block_rows(rows)
    _compile(lambda q, p: pm.exponent_histogram(
        q, p, block_rows=br, interpret=False),
        spec(rows, 128), spec(rows, 128))


@pytest.mark.parametrize("clients", [8, 64])
def test_importance_mask_batched(spec, rows, clients):
    br = ops._packed_block_rows(rows, clients)
    _compile(lambda w, v, p, t: pm.importance_mask_batched(
        w, v, p, t, block_rows=br, interpret=False),
        spec(rows, 128), spec(rows, 128), spec(rows, 128), spec(clients))


@pytest.mark.parametrize("clients", [8, 64])
def test_fedsgd_aggregate_weighted(spec, rows, clients):
    br = ops._packed_block_rows(rows, clients)
    _compile(lambda w, g, cw, inv, eta: pm.fedsgd_aggregate_weighted(
        w, g, cw, inv, eta, block_rows=br, interpret=False),
        spec(rows, 128), spec(clients, rows, 128), spec(clients), spec(),
        spec())


@pytest.mark.parametrize("clients", [8, 64])
def test_client_rank_sort(spec, rows, clients):
    br = ops._packed_block_rows(rows, clients, ops._SORT_STACK_BYTES)
    _compile(lambda g, cw: pm.client_rank_sort(
        g, cw, block_rows=br, interpret=False),
        spec(clients, rows, 128), spec(clients))
