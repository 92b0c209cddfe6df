"""The cluster-major engine as one `jax.distributed` job of two processes.

Two ranks, started through `repro.launch.distributed.spawn_local`, each
contribute two forced-host CPU devices to one global 4-way mesh, so the
round's two psums run as real cross-process collectives.  The ranks must
emit the same trace, and that trace must agree with the single-process
unsharded engine: scheduling, actions and counters exactly, the float
columns to the Eqn-19 psum's reassociation.
"""
import json
import os
import re

import numpy as np

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, FederationSpec,
                       FleetSpec, ShardingSpec, registry)
from repro.api.engine import DeviceScaleEngine
from repro.data import make_classification
from repro.data.federated import uniform_cycle_partition
from repro.launch.distributed import spawn_local

N_DEVICES, N_CLUSTERS, MESH, ROUNDS, SEED = 64, 8, (4,), 8, 5
SAMPLES, DIM = 4096, 16

# one rank: join the job before jax starts its backend (the forced-host
# device count is read once, at backend init), then run the scan
_WORKER = r"""
import json
from repro.launch.distributed import initialize_from_env
pid = initialize_from_env()
import jax
import test_distributed as t
eng = t.build(t.spec(t.MESH))
tr = eng.run_scanned(t.ROUNDS, eval_final=False)
print("DISTROWS" + json.dumps({"pid": pid,
                               "global_devices": jax.device_count(),
                               "rows": t.rows(tr)}), flush=True)
"""


def spec(mesh):
    return FederationSpec(
        fleet=FleetSpec(n_devices=N_DEVICES),
        clustering=api.ClusteringSpec(n_clusters=N_CLUSTERS),
        controller=ControllerSpec("fixed", {"a": 2}),
        aggregator=AggregatorSpec("trust", {"use_kernel": False}),
        execution="scanned", rounds=ROUNDS, sim_seconds=1e9,
        local_batch=4, seed=SEED, sharding=ShardingSpec(mesh=mesh))


def build(s):
    import jax
    data = make_classification(jax.random.PRNGKey(s.seed), n=SAMPLES,
                               dim=DIM)
    parts = uniform_cycle_partition(SAMPLES, s.fleet.n_devices)
    return DeviceScaleEngine.from_spec(
        s, data=data, parts=parts,
        controller=registry.CONTROLLERS.get(s.controller.kind)(
            s.controller.params),
        aggregator=registry.AGGREGATORS.get(s.aggregator.kind)(
            dict(s.aggregator.params)),
        task=registry.TASKS.get(s.task.kind)(s.task.params))


def rows(trace):
    return [[r.t, r.round, r.cluster, r.a, r.loss, r.energy, r.agg_count]
            for r in trace.records]


def _worker_env():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [here, os.path.join(here, "..", "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    # each rank brings its own two devices: drop a host-wide forced count
    env["XLA_FLAGS"] = re.sub(r"--xla_force_host_platform_device_count=\d+",
                              "", env.get("XLA_FLAGS", "")).strip()
    return env


def test_two_process_trace_matches_the_unsharded_engine():
    res = spawn_local(["-c", _WORKER], n_procs=2, local_devices=2,
                      timeout=300.0, env=_worker_env())
    for i, r in enumerate(res):
        assert r.returncode == 0, f"rank {i} failed:\n{r.stderr[-3000:]}"
    payloads = [json.loads(r.stdout.split("DISTROWS", 1)[1]) for r in res]
    assert payloads[0]["rows"] == payloads[1]["rows"], \
        "the ranks emitted different traces"
    assert payloads[0]["global_devices"] == 4

    ref = rows(build(spec(())).run_scanned(ROUNDS, eval_final=False))
    dist = payloads[0]["rows"]
    assert len(ref) == len(dist) == ROUNDS
    for p, s in zip(ref, dist):
        # t, round, cluster, a, loss, energy, agg_count
        assert p[1:4] == s[1:4] and p[6] == s[6], (p, s)
        np.testing.assert_allclose([p[0], p[4], p[5]], [s[0], s[4], s[5]],
                                   rtol=1e-5, atol=1e-6)
