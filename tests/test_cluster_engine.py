"""Cluster-major shard_map engine (`repro.api.cluster_engine`).

The contract under test: re-indexing the fleet cluster-major and running
the round as an explicit `jax.shard_map` changes *where* arrays live and
*how* the global average is reduced — never *what* the federation does.

* On a 1-device mesh the engine is bit-identical to the unsharded
  reference on every record field, across controllers, execution paths,
  faults, and uneven (auto-padded) memberships.
* On an 8-way forced-host mesh (subprocess) scheduling, actions and
  counters stay exact; float reductions are allclose (the Eqn-19 psum
  reassociates the sum).
* The lowered round contains zero all-gathers and at most two
  all-reduces — one packed metrics psum plus the Eqn-19 average.
* Checkpoints speak original device order: resumable state moves between
  the cluster-major and unsharded engines in both directions.
"""
import json
import logging
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, FaultSpec,
                       Federation, FederationSpec, FleetSpec, ShardingSpec)
from repro.api.engine import DeviceScaleEngine, DeviceScaleGspmdEngine
from repro.data import dirichlet_partition, make_classification


def _data(n=512, dim=24, devices=8, seed=0):
    key = jax.random.PRNGKey(seed)
    data = make_classification(key, n=n, dim=dim)
    return data, dirichlet_partition(key, data.y, devices)


def _spec(seed, mesh=(1,), impl=None, **kw):
    kw.setdefault("controller", ControllerSpec("fixed", {"a": 3}))
    # bit-exact parity needs one Eqn-6/19 reduction on both sides: with the
    # kernel, the unsharded engine fuses Eqn 19 into it and the
    # cluster-major engine psums Eqn 19 after the masked kernel
    kw.setdefault("aggregator", AggregatorSpec("trust",
                                               {"use_kernel": False}))
    kw.setdefault("fleet", FleetSpec(n_devices=8))
    kw.setdefault("clustering", api.ClusteringSpec(n_clusters=2))
    kw.setdefault("execution", "scanned")
    kw.setdefault("rounds", 6)
    kw.setdefault("sim_seconds", 1e9)
    return FederationSpec(local_batch=16, seed=seed,
                          sharding=ShardingSpec(mesh=mesh, impl=impl),
                          **kw)


def _records(trace):
    return [(r.t, r.round, r.cluster, r.a, r.loss, r.acc, r.energy,
             r.agg_count) for r in trace.records]


def _cluster_major(fed):
    from repro.api.cluster_engine import ClusterMajorEngine
    return isinstance(fed.engine, ClusterMajorEngine)


# --------------------------------------------------------------------- #
# routing + construction guards
# --------------------------------------------------------------------- #
def test_mesh_routes_to_cluster_major_gspmd_stays_selectable():
    data, parts = _data(seed=0)
    assert _cluster_major(Federation.from_spec(_spec(0), data=data,
                                               parts=parts))
    gspmd = Federation.from_spec(_spec(0, impl="gspmd"), data=data,
                                 parts=parts)
    assert not _cluster_major(gspmd)
    assert isinstance(gspmd.engine, DeviceScaleEngine)
    # the pinned registry scale resolves to the gspmd subclass
    assert api.ENGINES.get("device-gspmd") is DeviceScaleGspmdEngine


def test_rejects_unfused_and_unmasked_aggregators():
    data, parts = _data(seed=1)
    with pytest.raises(ValueError, match="fused-only"):
        Federation.from_spec(_spec(1), data=data, parts=parts, fused=False)
    with pytest.raises(ValueError, match="supports_mask=False"):
        Federation.from_spec(_spec(1, aggregator=AggregatorSpec("krum")),
                             data=data, parts=parts)


# --------------------------------------------------------------------- #
# 1-device mesh: bit-exact parity with the unsharded reference
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("ctl", [
    ControllerSpec("fixed", {"a": 3}),
    ControllerSpec("lyapunov", {"budget": 300.0, "horizon": 40}),
])
def test_scanned_trace_bit_identical(ctl):
    data, parts = _data(seed=31)
    plain = Federation.from_spec(
        _spec(31, mesh=(), controller=ctl), data=data, parts=parts).run()
    cm = Federation.from_spec(
        _spec(31, controller=ctl), data=data, parts=parts).run()
    assert _records(plain) == _records(cm)


def test_scanned_trace_bit_identical_dqn():
    from repro.api.components import DQNController
    ctl = DQNController.pretrain(seed=0, episodes=1, horizon=8)
    mk = lambda: DQNController(ctl.agent, ctl.cfg)
    data, parts = _data(seed=32)
    plain = Federation.from_spec(_spec(32, mesh=()), data=data,
                                 parts=parts, controller=mk()).run()
    cm = Federation.from_spec(_spec(32), data=data, parts=parts,
                              controller=mk()).run()
    assert _records(plain) == _records(cm)


def test_event_heap_trace_bit_identical():
    data, parts = _data(seed=33)
    kw = dict(execution="event", sim_seconds=2.0,
              controller=ControllerSpec("fixed", {"a": 2}))
    plain = Federation.from_spec(_spec(33, mesh=(), **kw), data=data,
                                 parts=parts).run(eval_every=1.0)
    cm = Federation.from_spec(_spec(33, **kw), data=data,
                              parts=parts).run(eval_every=1.0)
    assert _records(plain) == _records(cm)


def test_faulty_scanned_trace_bit_identical():
    faults = FaultSpec(dropout=0.25, straggler_frac=0.25,
                       straggler_factor=3.0, twin_spike_prob=0.2,
                       twin_spike_scale=4.0, seed=7)
    data, parts = _data(seed=34)
    plain = Federation.from_spec(_spec(34, mesh=(), faults=faults),
                                 data=data, parts=parts).run()
    cm = Federation.from_spec(_spec(34, faults=faults), data=data,
                              parts=parts).run()
    assert _records(plain) == _records(cm)


def test_uneven_membership_pads_logs_and_stays_bit_identical(caplog):
    """Uneven clusters force sentinel device slots even on a 1-device
    mesh (n_pad = C * max_cluster_size > n): the engine logs the padding
    it applied and the trace stays bit-identical."""
    from repro.api import registry

    data, parts = _data(seed=35)
    assign = np.array([0, 0, 0, 0, 0, 1, 1, 1], np.int32)  # sizes 5 + 3

    def build(mesh, impl=None):
        spec = _spec(35, mesh=mesh, impl=impl)
        ctl = registry.CONTROLLERS.get("fixed")({"a": 3})
        agg = registry.AGGREGATORS.get("trust")({"use_kernel": False})
        task = registry.TASKS.get(spec.task.kind)(spec.task.params)
        return DeviceScaleEngine.from_spec(
            spec, data=data, parts=parts, controller=ctl, aggregator=agg,
            task=task, assign=assign)

    plain = build(mesh=())
    with caplog.at_level(logging.INFO, logger="repro.cluster"):
        cm = build(mesh=(1,))
    assert any("cluster-major padding" in r.message for r in caplog.records)
    assert _records(plain.run_scanned(6)) == _records(cm.run_scanned(6))


def test_cluster_major_round_carries_the_stage_scopes():
    """The cluster-major scan runs the unsharded engine's member phase, so
    its compiled program carries the same stage scopes the per-stage
    device-time metrics read."""
    data, parts = _data(seed=37)
    fed = Federation.from_spec(_spec(37), data=data, parts=parts)
    assert _cluster_major(fed)
    eng = fed.engine
    pol = eng.controller.scan_policy()
    args = (eng.state, eng._scan_times, pol.state, eng._scan_energy_start(),
            eng._ftbl, eng._ch3, *eng._statics)
    hlo = eng._build_scan_fn(2, pol).lower(*args).compile().as_text()
    for name in ("fl.gather", "fl.local_sgd", "fl.flatten", "fl.trust"):
        assert f"/{name}/" in hlo, name


# --------------------------------------------------------------------- #
# checkpoints: original device order at the boundary, both directions
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("src_mesh,dst_mesh", [((1,), ()), ((), (1,))])
def test_checkpoint_roundtrip_across_engines(src_mesh, dst_mesh):
    data, parts = _data(seed=36)
    straight = Federation.from_spec(_spec(36, mesh=src_mesh), data=data,
                                    parts=parts)
    a = _records(straight.engine.run_scanned(3, eval_final=False))
    b = _records(straight.engine.run_scanned(3))

    half = Federation.from_spec(_spec(36, mesh=src_mesh), data=data,
                                parts=parts)
    assert _records(half.engine.run_scanned(3, eval_final=False)) == a
    tree = half.engine.resumable_state()

    resumed = Federation.from_spec(_spec(36, mesh=dst_mesh), data=data,
                                   parts=parts)
    resumed.engine.restore_resumable(tree, rounds=half.engine.round,
                                     energy=half.engine.energy_used)
    assert _records(resumed.engine.run_scanned(3)) == b


# --------------------------------------------------------------------- #
# 8-way mesh (subprocess): parity + collective counts in the lowered HLO
# --------------------------------------------------------------------- #
_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import re
import jax
import jax.numpy as jnp
import numpy as np
import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, Federation,
                       FederationSpec, FleetSpec, ShardingSpec)
from repro.data import dirichlet_partition, make_classification

assert jax.device_count() == 8
key = jax.random.PRNGKey(41)
data = make_classification(key, n=512, dim=24)
parts = dirichlet_partition(key, data.y, 24)
spec = FederationSpec(
    fleet=FleetSpec(n_devices=24),
    clustering=api.ClusteringSpec(n_clusters=6),   # 6 % 8 != 0: auto-pad
    controller=ControllerSpec("lyapunov", {"budget": 300.0,
                                           "horizon": 40}),
    aggregator=AggregatorSpec("trust", {"use_kernel": False}),
    execution="scanned", rounds=6, sim_seconds=1e9,
    local_batch=16, seed=41)
rows = {}
for name, s in (("plain", spec),
                ("shard", spec.replace(
                    sharding=ShardingSpec(mesh=(8,))))):
    tr = Federation.from_spec(s, data=data, parts=parts).run()
    rows[name] = [[r.t, r.round, r.cluster, r.a, r.loss, r.energy,
                   r.agg_count] for r in tr.records]

# collective counts: defining call sites only (` op(`), never operand
# references (`%all-reduce.2` inside fusions)
eng = Federation.from_spec(
    spec.replace(sharding=ShardingSpec(mesh=(8,))), data=data,
    parts=parts).engine
txt = eng._build_event_fn().lower(
    eng.state, eng._ftbl, eng._ch3, jnp.int32(0), jnp.int32(3),
    *eng._statics).compile().as_text()
rows["hlo"] = {op: len(re.findall(rf" {op}\(", txt))
               for op in ("all-gather", "all-reduce", "all-to-all",
                          "collective-permute")}
print("CMPAR" + json.dumps(rows))
"""


def _run_subproc():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.split("CMPAR", 1)[1])


@pytest.fixture(scope="module")
def subproc_rows():
    return _run_subproc()


def test_sharded8_parity_subprocess(subproc_rows):
    plain, shard = subproc_rows["plain"], subproc_rows["shard"]
    assert len(plain) == len(shard) == 7          # 6 rounds + final eval
    for p, s in zip(plain, shard):
        # t, round, cluster, a, loss, energy, agg_count
        assert p[1:4] == s[1:4] and p[6] == s[6]
        np.testing.assert_allclose([p[0], p[4], p[5]], [s[0], s[4], s[5]],
                                   rtol=1e-5, atol=1e-6)


def test_round_hlo_two_allreduce_zero_allgather(subproc_rows):
    """The whole point of the cluster-major layout: membership gathers
    are shard-local, so the only collectives the round lowers to are the
    packed metrics psum and the Eqn-19 global average."""
    hlo = subproc_rows["hlo"]
    assert hlo["all-gather"] == 0, hlo
    assert hlo["all-reduce"] <= 2, hlo
    assert hlo["all-to-all"] == 0 and hlo["collective-permute"] == 0, hlo
