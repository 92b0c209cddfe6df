"""Capacity-scaling curve of the cluster-major shard_map engine.

How far does the fleet simulation stretch on one host?  The curve sweeps
n_devices = 10^4 -> 10^6 (fixed members-per-cluster growth, k-means
bypassed with a round-robin assignment, O(1)-per-device data shards) and
records setup + steady-state rounds/sec of the scanned cluster-major
round.  A second arm brings the same engine up under `jax.distributed`:
two local processes, two forced-host CPU devices each, one global 4-way
mesh — and asserts the 2-process trace agrees with the single-process
unsharded reference (scheduling/counters exact, float reductions
allclose) before recording its throughput.

    PYTHONPATH=src python benchmarks/capacity_bench.py            # full
    PYTHONPATH=src python benchmarks/capacity_bench.py --fast     # CI smoke

Writes BENCH_capacity.json next to the repo root.
"""
import os
import sys

if "--dist-worker" in sys.argv:
    # worker rank: join the jax.distributed job BEFORE importing jax —
    # initialize_from_env appends the forced-host device flag to
    # XLA_FLAGS, which XLA reads once at backend init
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "..", "src"))
    from repro.launch.distributed import initialize_from_env
    _DIST_PID = initialize_from_env()

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, FederationSpec,
                       FleetSpec, ShardingSpec)
from repro.api import registry
from repro.api.engine import DeviceScaleEngine
from repro.data import make_classification
from repro.data.federated import uniform_cycle_partition

SAMPLES, DIM = 4096, 16


def _spec(n, C, mesh=(1,), seed=0, rounds=8):
    return FederationSpec(
        fleet=FleetSpec(n_devices=n),
        clustering=api.ClusteringSpec(n_clusters=C),
        controller=ControllerSpec("fixed", {"a": 2}),
        aggregator=AggregatorSpec("trust", {"use_kernel": False}),
        execution="scanned", rounds=rounds, sim_seconds=1e9,
        local_batch=4, seed=seed, sharding=ShardingSpec(mesh=mesh))


def _build(spec, assign=None):
    data = make_classification(jax.random.PRNGKey(spec.seed), n=SAMPLES,
                               dim=DIM)
    parts = uniform_cycle_partition(SAMPLES, spec.fleet.n_devices)
    ctl = registry.CONTROLLERS.get(spec.controller.kind)(
        spec.controller.params)
    agg = registry.AGGREGATORS.get(spec.aggregator.kind)(
        dict(spec.aggregator.params))
    task = registry.TASKS.get(spec.task.kind)(spec.task.params)
    return DeviceScaleEngine.from_spec(
        spec, data=data, parts=parts, controller=ctl, aggregator=agg,
        task=task, assign=assign)


def _rounds_per_sec(eng, K, reps=3):
    eng.set_trace_sink(None, retain=False)    # deferred host sync
    eng.run_scanned(K, eval_final=False)      # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.run_scanned(K, eval_final=False)
        eng.energy_used                       # flush: includes host sync
        best = min(best, time.perf_counter() - t0)
    return K / best


# --------------------------------------------------------------------- #
# arm 1: single-process capacity curve
# --------------------------------------------------------------------- #
def run_curve(sizes):
    rows = []
    for n, C in sizes:
        t0 = time.perf_counter()
        # k-means on 10^6 twins would dominate setup; the curve measures
        # the engine, so clusters are assigned round-robin
        eng = _build(_spec(n, C), assign=np.arange(n, dtype=np.int32) % C)
        setup = time.perf_counter() - t0
        K = 20 if n <= 10 ** 5 else 5
        rps = _rounds_per_sec(eng, K, reps=3 if n <= 10 ** 5 else 2)
        row = {"n_devices": n, "n_clusters": C,
               "members_per_cluster": n // C,
               "setup_seconds": round(setup, 2),
               "rounds_per_sec": round(rps, 2),
               "ms_per_round": round(1e3 / rps, 2)}
        rows.append(row)
        print(f"capacity,n={n},clusters={C},setup_s={setup:.2f},"
              f"rounds_per_sec={rps:.2f}")
        del eng
    return rows


# --------------------------------------------------------------------- #
# arm 2: 2-process jax.distributed bring-up + trace parity
# --------------------------------------------------------------------- #
DIST_N, DIST_C, DIST_MESH, DIST_ROUNDS = 64, 8, (4,), 8


def dist_worker():
    """One rank of the 2-process job (spawned by run_distributed)."""
    spec = _spec(DIST_N, DIST_C, mesh=DIST_MESH, seed=5,
                 rounds=DIST_ROUNDS)
    eng = _build(spec)
    tr = eng.run_scanned(DIST_ROUNDS, eval_final=False)
    rows = [[r.t, r.round, r.cluster, r.a, r.loss, r.energy, r.agg_count]
            for r in tr.records]
    t0 = time.perf_counter()
    eng.run_scanned(DIST_ROUNDS, eval_final=False)
    rps = DIST_ROUNDS / (time.perf_counter() - t0)
    print("DISTROWS" + json.dumps(
        {"pid": _DIST_PID, "global_devices": jax.device_count(),
         "local_devices": jax.local_device_count(),
         "rounds_per_sec": round(rps, 2), "rows": rows}), flush=True)
    return 0


def run_distributed():
    from repro.launch.distributed import spawn_local

    res = spawn_local([os.path.abspath(__file__), "--dist-worker"],
                      n_procs=2, local_devices=2)
    for i, r in enumerate(res):
        if r.returncode:
            raise RuntimeError(
                f"dist worker {i} failed:\n{r.stderr[-3000:]}")
    payloads = [json.loads(r.stdout.split("DISTROWS", 1)[1])
                for r in res]
    assert payloads[0]["rows"] == payloads[1]["rows"], \
        "worker processes emitted different traces"
    assert payloads[0]["global_devices"] == 4

    # single-process unsharded reference, same spec sans mesh
    ref_eng = _build(_spec(DIST_N, DIST_C, mesh=(), seed=5,
                           rounds=DIST_ROUNDS))
    ref = ref_eng.run_scanned(DIST_ROUNDS, eval_final=False)
    ref_rows = [[r.t, r.round, r.cluster, r.a, r.loss, r.energy,
                 r.agg_count] for r in ref.records]
    dist_rows = payloads[0]["rows"]
    assert len(ref_rows) == len(dist_rows) == DIST_ROUNDS
    for p, s in zip(ref_rows, dist_rows):
        assert p[1:4] == s[1:4] and p[6] == s[6], (p, s)
        np.testing.assert_allclose([p[0], p[4], p[5]],
                                   [s[0], s[4], s[5]],
                                   rtol=1e-5, atol=1e-6)
    print(f"capacity,distributed_2proc_rounds_per_sec,"
          f"{payloads[0]['rounds_per_sec']:.2f} (parity asserted over "
          f"{DIST_ROUNDS} rounds)")
    return {"n_processes": 2, "local_devices_per_process": 2,
            "mesh": list(DIST_MESH), "n_devices": DIST_N,
            "n_clusters": DIST_C, "rounds": DIST_ROUNDS,
            "rounds_per_sec": payloads[0]["rounds_per_sec"],
            "trace_parity": "round/cluster/a/agg_count exact vs the "
                            "single-process unsharded engine; t/loss/"
                            "energy allclose rtol=1e-5 atol=1e-6 "
                            "(the Eqn-19 psum reassociates the sum)"}


def main(argv=None):
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: curve stops at 10^4 devices")
    ap.add_argument("--skip-dist", action="store_true",
                    help="skip the 2-process jax.distributed arm")
    ap.add_argument("--dist-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default="BENCH_capacity.json")
    args = ap.parse_args(argv)

    if args.dist_worker:
        return dist_worker()

    sizes = [(10 ** 4, 64)]
    if not args.fast:
        sizes += [(10 ** 5, 512), (10 ** 6, 4096)]
    curve = run_curve(sizes)
    dist = None if args.skip_dist else run_distributed()

    if not args.fast:
        payload = {
            "bench": "cluster-major shard_map engine capacity: scanned "
                     "rounds/sec vs fleet size, plus a 2-process "
                     "jax.distributed bring-up with asserted trace parity",
            "note": "curve: 1-device mesh, round-robin cluster assignment "
                    "(k-means bypassed), O(1)-per-device cyclic data "
                    "shards, deferred host sync (no trace sink); "
                    "distributed: 2 processes x 2 forced-host CPU devices "
                    "= one 4-way mesh, gloo collectives",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
            "device": str(jax.devices()[0]),
            "samples": SAMPLES, "dim": DIM, "local_batch": 4,
            "curve": curve,
            "distributed": dist,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
