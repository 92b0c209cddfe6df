"""The trust kernels compiled for a described TPU v5e, at fleet shapes.

Nothing here runs: the TPU compiler that ships with jax compiles for a
`v5e:2x2` topology that is described, not attached, and refuses what the
chip would refuse (VMEM overflow, untileable blocks) — faults interpret
mode cannot see.  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.

Shapes: N = 159,010 is the parameter count of the paper's 784-200-10 MLP.
C is the padded member count of a cluster, B the number of clusters.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.trust_aggregate import trust_aggregate, trust_aggregate_global

N = 159_010


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
def test_trust_aggregate_compiles(one_chip, masked):
    C = 16
    args = [_spec(one_chip, (C, N)), _spec(one_chip, (C,))]
    if masked:
        args.append(_spec(one_chip, (C,), jnp.bool_))
    _assert_kernel_compiles(trust_aggregate, *args)


@pytest.mark.parametrize("C,B", [(4, 4), (256, 4), (512, 4), (16, 256)])
def test_trust_aggregate_global_compiles(one_chip, C, B):
    """(4, 4) is the paper's 16-device fleet; 256 and 512 members per
    cluster and 256 clusters are capacity fleets, whose tiles overflowed
    VMEM at a fixed 8192-lane block."""
    _assert_kernel_compiles(
        trust_aggregate_global, _spec(one_chip, (C, N)),
        _spec(one_chip, (C,)), _spec(one_chip, (C,), jnp.bool_),
        _spec(one_chip, (B, N)), _spec(one_chip, (B,)),
        _spec(one_chip, (), jnp.int32))


def test_vmapped_trust_aggregate_global_compiles(one_chip):
    """The population engine's form: the fused kernel vmapped over B=16
    member federations of the paper's fleet."""
    P, C, B = 16, 4, 4
    _assert_kernel_compiles(
        jax.vmap(trust_aggregate_global), _spec(one_chip, (P, C, N)),
        _spec(one_chip, (P, C)), _spec(one_chip, (P, C), jnp.bool_),
        _spec(one_chip, (P, B, N)), _spec(one_chip, (P, B)),
        _spec(one_chip, (P,), jnp.int32))
