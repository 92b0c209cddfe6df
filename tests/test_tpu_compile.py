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


def test_scanned_round_builds_one_member_matrix(one_chip, monkeypatch):
    """The paper's §V round, compiled whole for the chip: the member
    matrix is one buffer at the trust kernel's padded width (no (M, N)
    buffer beside it, no pad in front of the kernel), and no member-sized
    layout copy of a weight stack feeds it (the flat columns hold each
    weight matrix with its last two axes swapped, as the chip stores it)."""
    import re

    import numpy as np

    import repro.api as api
    import repro.kernels.ops as ops
    from repro.data import make_classification
    from repro.kernels.trust_aggregate import padded_width

    monkeypatch.setattr(ops, "_interpret", lambda flag: False)
    data = make_classification(jax.random.PRNGKey(0), n=1024, dim=784)
    spec = api.FederationSpec(
        seed=0, local_batch=64, execution="scanned",
        fleet=api.FleetSpec(n_devices=16),
        clustering=api.ClusteringSpec(n_clusters=4),
        controller=api.ControllerSpec("fixed", {"a": 2}),
        task=api.TaskSpec("mlp", {"hidden": 200, "n_classes": 10}))
    eng = api.Federation.from_spec(
        spec, data=data,
        parts=np.array_split(np.arange(1024), 16)).engine
    pol = eng.controller.scan_policy()
    args = (eng.state, eng._scan_times, pol.state, eng._scan_energy_start(),
            eng.tables)
    specs = jax.tree.map(
        lambda x: _spec(one_chip, jnp.shape(x), jnp.asarray(x).dtype), args)
    txt = eng._build_scan_fn(1, pol).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in txt

    M = eng.tables.member_table.shape[1]
    W = padded_width(M + 4, N)
    ops_of = lambda op: re.findall(                              # noqa: E731
        rf"= f32\[{M},([0-9,]+)\]\S* {op}\(", txt)
    assert {w for w in ops_of("dynamic-update-slice")
            if int(w.split(",")[-1]) >= N} == {str(W)}
    assert ops_of("pad") == []
    assert [s for s in ops_of("copy") if s.count(",")] == []
