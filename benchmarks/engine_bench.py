"""Device-scale engine throughput: fused `FleetState` rounds vs the
pre-refactor engine.

Three engines run the same federation (same spec shapes, fixed controller,
trust aggregation):

  legacy     a faithful reconstruction of the pre-refactor
             `DeviceScaleEngine._cluster_round`: per-member batch assembly
             in Python lists, `np.asarray`/`float()` device syncs every
             round, an unjitted trust pipeline, and the O(C^2)
             `_pick_frequency` recomputation — the host-bound baseline the
             FleetState refactor replaced.
  reference  the *new* round function executed eagerly (fused=False):
             fixed-shape padded math, per-op dispatch, per-round host
             syncs.  Isolates the jit-fusion gain from the data-layout
             gain.
  fused      one jit-compiled `_fleet_round` call per round; only the
             event heap, controller select and a 4-scalar metrics pull
             stay on the host (the post-refactor hot path).

Fused and reference share RNG streams and produce matching traces (see
tests/test_api.py::test_fused_round_parity_with_reference); legacy is the
old computation (different batch sampler), timed on the same workload.

``--scanned`` benches the control plane instead: the per-event fused path
(host event heap + controller `select` each round) against
`run_scanned(K)` (K rounds + in-jit controller + Eqn-12 queue in one
`lax.scan`), for the `fixed` and `dqn` controllers.  The scanned/dqn
number is the headline: it is the adaptive-frequency path with zero
per-round host syncs.

``--sharded`` benches the placement layer: the same `run_scanned(K)`
workload on the single-device fallback vs a `ShardingSpec(mesh=(M,))`
host mesh (default M=8; force a CPU device pool with
XLA_FLAGS=--xla_force_host_platform_device_count=M).  A 1-D mesh now
resolves to the cluster-major `shard_map` engine
(`repro.api.cluster_engine`): memberships are shard-local by layout and
the round's only collectives are two psums, so the recorded ratio is the
real cost/benefit of splitting one CPU into M shards — it superseded the
0.17x the GSPMD-inferred path recorded (all-gathers on every membership
gather; still measurable via ``ShardingSpec(impl='gspmd')``).

``--segmented`` benches service-mode execution (`repro.serve`): S
segments of `run_scanned(K)` each followed by a full resumable checkpoint
(`SegmentRunner`) against the same S*K rounds in one scan — the recorded
per-segment overhead is the price of bit-exact resumability.

    PYTHONPATH=src python benchmarks/engine_bench.py            # full
    PYTHONPATH=src python benchmarks/engine_bench.py --fast     # CI smoke
    PYTHONPATH=src python benchmarks/engine_bench.py --scanned  # scan bench
    PYTHONPATH=src python benchmarks/engine_bench.py --segmented
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/engine_bench.py --sharded

Full runs write BENCH_engine_throughput.json / BENCH_engine_scan.json /
BENCH_engine_shard.json / BENCH_engine_segmented.json at the repo root.
"""
from __future__ import annotations

import argparse
import heapq
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (AggregatorSpec, ClusteringSpec, ControllerSpec,
                       Federation, FederationSpec, FleetSpec, ShardingSpec,
                       WeightedAggregator)
from repro.core.clustering import (cluster_devices, ensure_nonempty,
                                   tolerance_bound)
from repro.core.energy import (channel_transition, comm_energy,
                               compute_energy, step_channel)
from repro.core.trust import (belief, gradient_diversity, learning_quality,
                              time_weighted_average, trust_weights,
                              update_reputation)
from repro.core.twin import (TwinState, calibrate, calibrated_freq,
                             init_twins, observe_round, sample_deviation)
from repro.data import dirichlet_partition, make_classification
from repro.kernels.ops import flatten_rows


class LegacyEngine:
    """Frozen copy of the pre-refactor `DeviceScaleEngine` hot loop
    (commit 59dc9de), kept verbatim-in-spirit as the benchmark baseline:
    host-bound Python per-member batch assembly, no fused round, per-round
    device syncs, O(C^2) frequency recomputation in `_pick_frequency`."""

    def __init__(self, spec, data, parts, *, controller, aggregator, task):
        self.spec = spec
        self.data = data
        self.parts = parts
        self.controller = controller
        self.aggregator = aggregator
        self.task = task
        key = jax.random.PRNGKey(spec.seed)
        (self.key, kt, kd, kc, kp, km) = jax.random.split(key, 6)
        self.twins = sample_deviation(
            kd, init_twins(kt, spec.fleet.n_devices), spec.fleet.dt_max_dev)
        sizes = jnp.asarray([len(p) for p in parts], jnp.float32)
        self.twins = self.twins._replace(data_size=sizes)
        assign, _ = cluster_devices(kc, self.twins,
                                    spec.clustering.n_clusters)
        self.assign = ensure_nonempty(np.asarray(assign),
                                      spec.clustering.n_clusters)
        self.global_params = task.init(kp, dim=data.x.shape[1])
        self.cluster_params = [self.global_params] * spec.clustering.n_clusters
        self.cluster_ts = np.zeros(spec.clustering.n_clusters)
        self.round = 0
        self.rep = jnp.ones((spec.fleet.n_devices,))
        self.channel = jnp.zeros((spec.fleet.n_devices,), jnp.int32)
        self.malicious = np.zeros(spec.fleet.n_devices, bool)
        self.energy_used = 0.0
        self.agg_count = 0

    def _cluster_freq(self, c):
        members = np.where(self.assign == c)[0]
        f = np.asarray(calibrated_freq(self.twins))[members]
        return float(f.min()) if len(members) else 1.0

    def _pick_frequency(self, c):
        spec = self.spec
        a = self.controller.select(None)        # fixed controller only
        # same a_req/f_max tolerance reference as the live engine so both
        # benchmark arms run the identical per-round workload
        t_ref = a / max(max(self._cluster_freq(cc), 1e-6)
                        for cc in range(spec.clustering.n_clusters))
        alpha = min(1.0, spec.clustering.alpha0 +
                    spec.clustering.alpha_growth * self.round)
        a = int(tolerance_bound(jnp.asarray(a), jnp.asarray(
            self._cluster_freq(c)), jnp.asarray(t_ref), alpha))
        return max(1, min(a, self.controller.n_actions))

    def _cluster_round(self, c, a, kround):
        spec = self.spec
        members = np.where(self.assign == c)[0]
        kb, ke, kc2 = jax.random.split(kround, 3)
        xs, ys = [], []
        for m in members:                       # Python batch assembly
            ix = self.parts[m]
            sel = np.asarray(jax.random.choice(
                jax.random.fold_in(kb, int(m)), jnp.asarray(ix),
                (spec.local_batch,), replace=len(ix) < spec.local_batch))
            xs.append(np.asarray(self.data.x)[sel])
            ys.append(np.asarray(self.data.y)[sel])
        batch = {"x": jnp.asarray(np.stack(xs)),
                 "y": jnp.asarray(np.stack(ys))}
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (len(members),) + x.shape),
            self.cluster_params[c])
        new = self.task.local_train(stacked, batch, spec.lr, a)
        upd_flat = flatten_rows(new) - flatten_rows(stacked)
        q = learning_quality(upd_flat)
        div = gradient_diversity(upd_flat)
        tw_m = jax.tree.map(lambda x: x[members], self.twins._asdict())
        b = belief(TwinState(**tw_m), q, spec.channel.pkt_fail, div)
        rep_m = update_reputation(self.rep[members], b,
                                  spec.channel.pkt_fail, spec.iota)
        self.rep = self.rep.at[jnp.asarray(members)].set(rep_m)
        w = trust_weights(rep_m)
        self.cluster_params[c] = self.aggregator(new, w)
        losses = self.task.losses(new, batch)
        e_cmp = a * compute_energy(
            (self.twins.freq + self.twins.freq_dev)[members])
        e_com = comm_energy(self.channel[members], ke)
        self.energy_used += float(e_cmp.sum() + e_com.sum())
        full_loss = self.twins.loss.at[jnp.asarray(members)].set(losses)
        full_e = jnp.zeros_like(self.twins.energy).at[
            jnp.asarray(members)].set(e_cmp + e_com)
        self.twins = observe_round(
            self.twins, full_loss, full_e,
            jnp.asarray(self.malicious, jnp.float32))
        if spec.fleet.calibrate_dt:
            self.twins = calibrate(self.twins)
        self.channel = step_channel(kc2, self.channel,
                                    channel_transition(spec.channel.p_good))
        return float(a) / max(self._cluster_freq(c), 1e-6)

    def run(self, eval_every=1.0, max_rounds=None):
        spec = self.spec
        events = [(0.0, c) for c in range(spec.clustering.n_clusters)]
        heapq.heapify(events)
        t, done = 0.0, 0
        while events and t < spec.sim_seconds:
            if max_rounds is not None and done >= max_rounds:
                break
            t, c = heapq.heappop(events)
            self.key, ka, kr = jax.random.split(self.key, 3)
            a = self._pick_frequency(c)
            dur = self._cluster_round(c, a, kr)
            self.round += 1
            self.cluster_ts[c] = self.round
            staleness = jnp.asarray(self.round - self.cluster_ts,
                                    jnp.float32)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *self.cluster_params)
            self.global_params, _ = time_weighted_average(stacked, staleness)
            self.agg_count += 1
            self.cluster_params[c] = self.global_params
            heapq.heappush(events, (t + dur, c))
            done += 1


def _build(n_devices, n_clusters, seed, fused, data, parts, local_batch):
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=n_devices),
        clustering=ClusteringSpec(n_clusters=n_clusters),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("trust"),
        sim_seconds=1e9,                 # bounded by max_rounds, not time
        local_batch=local_batch, seed=seed)
    return Federation.from_spec(spec, data=data, parts=parts, fused=fused)


def bench_mode(fused, *, n_devices, n_clusters, rounds, warmup, data,
               parts, local_batch=64, seed=0):
    fed = _build(n_devices, n_clusters, seed, fused, data, parts,
                 local_batch)
    fed.run(eval_every=1e9, max_rounds=warmup)        # compile + warm
    t0 = time.perf_counter()
    fed.run(eval_every=1e9, max_rounds=rounds)
    dt = time.perf_counter() - t0
    return rounds / dt, dt


def bench_fused_split(*, n_devices, n_clusters, rounds, data, parts,
                      local_batch=64, seed=0):
    """Span-derived compile vs steady-state split of the fused scanned
    path.  With an `EngineObs` attached, the first ``run_scanned(K)`` is
    a scan-cache miss, so the engine AOT-compiles under its
    ``span("compile")``; the second identical call is a cache hit whose
    fenced ``span("round")`` is pure execution.  Separating the two keeps
    the perf trajectory honest: a compile-time regression and a
    steady-state regression are different bugs."""
    from repro.obs import EngineObs
    fed = _build(n_devices, n_clusters, seed, True, data, parts,
                 local_batch)
    obs = EngineObs()
    fed.engine.set_obs(obs)
    fed.engine.run_scanned(rounds, eval_final=False)    # pays the compile
    fed.engine.run_scanned(rounds, eval_final=False)    # steady state
    compile_sp = obs.spans.last("compile")
    steady = obs.spans.last("round")
    split = {
        "compile_s": round(compile_sp.dur_s, 4) if compile_sp else None,
        "steady_segment_s": round(steady.dur_s, 4),
        "steady_rounds_per_sec": round(rounds / steady.dur_s, 2),
        "steady_dispatch_s": round(steady.attrs["dispatch_s"], 4)
        if "dispatch_s" in steady.attrs else None,
    }
    hlo_flops = obs.m_hlo_flops.total()
    if hlo_flops:
        split["hlo_flops"] = hlo_flops
        split["hlo_collective_ops"] = obs.m_hlo_coll.total()
    return split


def bench_legacy(*, n_devices, n_clusters, rounds, warmup, data, parts,
                 local_batch=64, seed=0):
    from repro.api.components import FixedController, MLPTask
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=n_devices),
        clustering=ClusteringSpec(n_clusters=n_clusters),
        controller=ControllerSpec("fixed", {"a": 3}),
        sim_seconds=1e9, local_batch=local_batch, seed=seed)
    eng = LegacyEngine(spec, data, parts,
                       controller=FixedController(3),
                       aggregator=WeightedAggregator(), task=MLPTask())
    eng.run(max_rounds=warmup)
    t0 = time.perf_counter()
    eng.run(max_rounds=rounds)
    dt = time.perf_counter() - t0
    return rounds / dt, dt


def _controller_for(kind, agent_and_cfg):
    from repro.api.components import DQNController, FixedController
    if kind == "fixed":
        return FixedController(3)
    return DQNController(*agent_and_cfg)


def bench_controller(kind, scanned, *, n_devices, n_clusters, rounds,
                     warmup, data, parts, local_batch=16,
                     agent_and_cfg=None, seed=0):
    """Rounds/sec of the per-event fused path vs run_scanned(K) under a
    given controller kind.  A fresh engine per mode; the DQN agent is
    trained once and shared so both modes run the same policy."""
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=n_devices),
        clustering=ClusteringSpec(n_clusters=n_clusters),
        controller=ControllerSpec("fixed", {"a": 3}),   # shape only;
        aggregator=AggregatorSpec("trust"),             # instance overrides
        sim_seconds=1e9, local_batch=local_batch, seed=seed)
    fed = Federation.from_spec(spec, data=data, parts=parts,
                               controller=_controller_for(kind,
                                                          agent_and_cfg))
    # best of `reps` timed repetitions: per-round work is a few ms, so a
    # background scheduling blip in a single pass dominates the mean
    reps = 3
    if scanned:
        fed.engine.run_scanned(rounds, eval_final=False)   # compile + warm
        dt = min(_timed(lambda: fed.engine.run_scanned(rounds,
                                                       eval_final=False))
                 for _ in range(reps))
    else:
        fed.run(eval_every=1e9, max_rounds=warmup)
        dt = min(_timed(lambda: fed.run(eval_every=1e9, max_rounds=rounds))
                 for _ in range(reps))
    return rounds / dt


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run_scan_bench(args):
    from repro.api.components import DQNController
    key = jax.random.PRNGKey(0)
    data = make_classification(key, n=args.samples, dim=args.dim)
    parts = dirichlet_partition(key, data.y, args.devices)
    ctl = DQNController.pretrain(seed=0, episodes=2, horizon=15)
    agent_and_cfg = (ctl.agent, ctl.cfg)
    kw = dict(n_devices=args.devices, n_clusters=args.clusters,
              rounds=args.rounds, warmup=args.warmup, data=data,
              parts=parts, local_batch=args.local_batch)

    results = {}
    for kind in ("fixed", "dqn"):
        heap = bench_controller(kind, False, agent_and_cfg=agent_and_cfg,
                                **kw)
        scan = bench_controller(kind, True, agent_and_cfg=agent_and_cfg,
                                **kw)
        results[kind] = {"event_heap_rounds_per_sec": round(heap, 2),
                         "scanned_rounds_per_sec": round(scan, 2),
                         "speedup": round(scan / heap, 2)}
        print(f"engine,{kind}_event_heap_rounds_per_sec,{heap:.2f}")
        print(f"engine,{kind}_scanned_rounds_per_sec,{scan:.2f}")
        print(f"engine,{kind}_scanned_speedup,{scan / heap:.2f}x")

    if not args.fast:
        payload = {
            "bench": "DeviceScaleEngine rounds/sec: lax.scan-over-rounds "
                     "(in-jit controller + Lyapunov queue) vs the "
                     "per-event fused path",
            "note": "event_heap = one jitted _fleet_round per heap event "
                    "with host-side controller select (ctx pull per round "
                    "for dqn); scanned = run_scanned(K): K rounds, "
                    "controller and Eqn-12 queue in one lax.scan, metrics "
                    "synced once at the end",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
            "device": str(jax.devices()[0]),
            "n_devices": args.devices,
            "n_clusters": args.clusters,
            "rounds_measured": args.rounds,
            "local_batch": args.local_batch,
            "dim": args.dim,
            **{f"{k}_{f}": v for k, r in results.items()
               for f, v in r.items()},
        }
        with open(args.scan_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.scan_out}")
    return 0


def bench_placement(mesh, *, n_devices, n_clusters, rounds, data, parts,
                    local_batch=8, seed=0):
    """Rounds/sec of run_scanned(K) under a given placement (mesh shape;
    () = the single-device fallback)."""
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=n_devices),
        clustering=ClusteringSpec(n_clusters=n_clusters),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("trust"),
        execution="scanned", rounds=rounds, sim_seconds=1e9,
        local_batch=local_batch, seed=seed,
        sharding=ShardingSpec(mesh=mesh))
    fed = Federation.from_spec(spec, data=data, parts=parts)
    fed.engine.run_scanned(rounds, eval_final=False)     # compile + warm
    dt = min(_timed(lambda: fed.engine.run_scanned(rounds,
                                                   eval_final=False))
             for _ in range(3))
    return rounds / dt


def run_shard_bench(args):
    mesh = (args.mesh_size,)
    if jax.device_count() < args.mesh_size:
        print(f"error: --sharded needs {args.mesh_size} devices, backend "
              f"exposes {jax.device_count()}; run under XLA_FLAGS="
              f"--xla_force_host_platform_device_count={args.mesh_size}")
        return 2
    key = jax.random.PRNGKey(0)
    data = make_classification(key, n=args.samples, dim=args.dim)
    parts = dirichlet_partition(key, data.y, args.devices)
    kw = dict(n_devices=args.devices, n_clusters=args.clusters,
              rounds=args.rounds, data=data, parts=parts,
              local_batch=args.local_batch)

    single = bench_placement((), **kw)
    sharded = bench_placement(mesh, **kw)
    print(f"engine,single_device_rounds_per_sec,{single:.2f}")
    print(f"engine,sharded_mesh{args.mesh_size}_rounds_per_sec,"
          f"{sharded:.2f}")
    print(f"engine,sharded_vs_single_ratio,{sharded / single:.2f}x "
          f"(n_devices={args.devices}, mesh={mesh})")

    if not args.fast:
        payload = {
            "bench": "DeviceScaleEngine run_scanned rounds/sec: "
                     "ShardingSpec mesh placement vs the single-device "
                     "fallback",
            "note": "sharded = the cluster-major shard_map engine "
                    "(repro.api.cluster_engine): fleet re-indexed so "
                    "memberships are shard-local, explicit jax.shard_map "
                    "round with exactly two psums (Eqn-19 average + packed "
                    "scalar metrics), zero all-gathers (HLO-pinned by "
                    "tests/test_cluster_engine.py).  Supersedes the 0.17x "
                    "this file recorded for the GSPMD-inferred path, which "
                    "stays selectable via ShardingSpec(impl='gspmd'); see "
                    "BENCH_capacity.json for the n_devices=10^4..10^6 "
                    "capacity curve",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
            "device": str(jax.devices()[0]),
            "device_count": jax.device_count(),
            "mesh": list(mesh),
            "n_devices": args.devices,
            "n_clusters": args.clusters,
            "rounds_measured": args.rounds,
            "local_batch": args.local_batch,
            "dim": args.dim,
            "single_device_rounds_per_sec": round(single, 2),
            "sharded_rounds_per_sec": round(sharded, 2),
            "sharded_vs_single_ratio": round(sharded / single, 2),
        }
        with open(args.shard_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.shard_out}")
    return 0


def run_segmented_bench(args):
    """Checkpoint overhead of service-mode execution: S segments of
    `run_scanned(K)` with a full resumable checkpoint after each
    (`repro.serve.SegmentRunner`) vs the same S*K rounds in one scan."""
    import tempfile

    from repro.serve import SegmentRunner

    key = jax.random.PRNGKey(0)
    data = make_classification(key, n=args.samples, dim=args.dim)
    parts = dirichlet_partition(key, data.y, args.devices)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=args.devices),
        clustering=ClusteringSpec(n_clusters=args.clusters),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("trust"),
        execution="scanned", rounds=args.segment_rounds, sim_seconds=1e9,
        local_batch=args.local_batch, seed=0)
    K, S = args.segment_rounds, args.segments

    fed = Federation.from_spec(spec, data=data, parts=parts)
    fed.engine.run_scanned(S * K, eval_final=False)       # compile + warm
    straight_dt = min(_timed(lambda: fed.engine.run_scanned(
        S * K, eval_final=False)) for _ in range(3))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        fed2 = Federation.from_spec(spec, data=data, parts=parts)
        runner = SegmentRunner(fed2, ckpt_dir, segment_rounds=K, keep=2,
                               eval_final=False)
        runner.run_segment()                              # compile + warm

        def run_segments():
            for _ in range(S):
                runner.run_segment()

        seg_dt = min(_timed(run_segments) for _ in range(3))
        ckpt_dt = min(_timed(runner.checkpoint) for _ in range(3))

    # per-scan sync cost, isolated from checkpointing: S segments with the
    # default per-scan device_get + trace build vs the same S segments
    # with no sink and retention off, where run_scanned queues each
    # segment's consumed stack device-side and the host f64 tally is
    # rebuilt only at the final host-facing read (energy_used)
    fed3 = Federation.from_spec(spec, data=data, parts=parts)
    fed3.engine.run_scanned(K, eval_final=False)          # compile + warm

    def run_synced():
        for _ in range(S):
            fed3.engine.run_scanned(K, eval_final=False)

    synced_dt = min(_timed(run_synced) for _ in range(5))

    fed4 = Federation.from_spec(spec, data=data, parts=parts)
    fed4.engine.set_trace_sink(None, retain=False)
    fed4.engine.run_scanned(K, eval_final=False)          # compile + warm

    def run_deferred():
        for _ in range(S):
            fed4.engine.run_scanned(K, eval_final=False)
        fed4.engine.energy_used                 # one flush per S segments

    deferred_dt = min(_timed(run_deferred) for _ in range(5))

    straight_rps = S * K / straight_dt
    seg_rps = S * K / seg_dt
    synced_rps = S * K / synced_dt
    deferred_rps = S * K / deferred_dt
    overhead = (seg_dt - straight_dt) / S
    print(f"engine,straight_scan_rounds_per_sec,{straight_rps:.2f}")
    print(f"engine,segmented_rounds_per_sec,{seg_rps:.2f}")
    print(f"engine,synced_segments_rounds_per_sec,{synced_rps:.2f}")
    print(f"engine,deferred_sync_rounds_per_sec,{deferred_rps:.2f}")
    print(f"engine,checkpoint_seconds_per_segment,{ckpt_dt:.4f}")
    print(f"engine,segment_overhead_seconds,{overhead:.4f} "
          f"(K={K}, {S} segments)")
    print(f"engine,deferred_vs_synced_ratio,"
          f"{deferred_rps / synced_rps:.3f}x")

    if not args.fast:
        payload = {
            "bench": "repro.serve segmented execution: run_scanned(K) x S "
                     "with a full resumable checkpoint per segment vs one "
                     "run_scanned(S*K)",
            "note": "checkpoint = FleetState (typed PRNG key included) + "
                    "event times + policy carry to .npz, plus the JSON "
                    "manifest, both written atomically; overhead is the "
                    "service-mode price of bit-exact resumability per "
                    "segment",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
            "device": str(jax.devices()[0]),
            "n_devices": args.devices,
            "n_clusters": args.clusters,
            "segment_rounds": K,
            "segments": S,
            "local_batch": args.local_batch,
            "dim": args.dim,
            "straight_scan_rounds_per_sec": round(straight_rps, 2),
            "segmented_rounds_per_sec": round(seg_rps, 2),
            "synced_segments_rounds_per_sec": round(synced_rps, 2),
            "deferred_sync_rounds_per_sec": round(deferred_rps, 2),
            "checkpoint_seconds_per_segment": round(ckpt_dt, 4),
            "segment_overhead_seconds": round(overhead, 4),
            "throughput_ratio": round(seg_rps / straight_rps, 3),
            "deferred_vs_synced_ratio": round(deferred_rps / synced_rps, 3),
            "deferred_note": "synced = S bare run_scanned(K) calls with "
                             "the default per-scan device_get + trace "
                             "build; deferred = the same S segments with "
                             "no sink and retention off — run_scanned "
                             "queues consumed stacks device-side and "
                             "flushes once at the first host-facing read "
                             "(energy_used / checkpoint)",
        }
        with open(args.seg_out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.seg_out}")
    return 0


def main(argv=None):
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--clusters", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--samples", type=int, default=None)
    # 128 features keeps the per-round model compute in the regime the
    # refactor targets (high-frequency rounds over many small IIoT
    # devices); --dim 784 reproduces the paper's MNIST shape, where the
    # vmapped matmuls + the CPU interpret-mode Pallas kernel dominate both
    # engines and compress the ratio.  The --scanned mode defaults go
    # further down the same axis (dim 32, batch 8, 16 clusters): tiny
    # per-device models at a high round rate, where per-event dispatch and
    # controller syncs are the bottleneck the scan removes.
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--local-batch", type=int, default=None)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: small fleet, few rounds, no JSON")
    ap.add_argument("--scanned", action="store_true",
                    help="bench run_scanned(K) vs the per-event fused path "
                         "(fixed and dqn controllers)")
    ap.add_argument("--sharded", action="store_true",
                    help="bench run_scanned(K) on a ShardingSpec mesh vs "
                         "the single-device fallback (needs a device pool; "
                         "see module docstring)")
    ap.add_argument("--mesh-size", type=int, default=8)
    ap.add_argument("--segmented", action="store_true",
                    help="bench checkpointed segments (repro.serve "
                         "SegmentRunner) vs one straight run_scanned")
    ap.add_argument("--segment-rounds", type=int, default=25,
                    help="K rounds per segment (--segmented)")
    ap.add_argument("--segments", type=int, default=4,
                    help="segments per timed pass (--segmented)")
    ap.add_argument("--out", default="BENCH_engine_throughput.json")
    ap.add_argument("--scan-out", default="BENCH_engine_scan.json")
    ap.add_argument("--shard-out", default="BENCH_engine_shard.json")
    ap.add_argument("--seg-out", default="BENCH_engine_segmented.json")
    args = ap.parse_args(argv)
    # per-mode defaults (any explicit flag wins)
    scan_defaults = dict(devices=64, clusters=16, rounds=150, samples=2048,
                         dim=32, local_batch=8)
    shard_defaults = dict(devices=256, clusters=16, rounds=60, samples=4096,
                          dim=32, local_batch=8)
    seg_defaults = dict(devices=64, clusters=16, rounds=100, samples=2048,
                        dim=32, local_batch=8)
    full_defaults = dict(devices=64, clusters=8, rounds=100, samples=4096,
                         dim=128, local_batch=64)
    mode_defaults = (shard_defaults if args.sharded
                     else scan_defaults if args.scanned
                     else seg_defaults if args.segmented else full_defaults)
    for name, val in mode_defaults.items():
        if getattr(args, name) is None:
            setattr(args, name, val)
    if args.fast:
        args.devices, args.clusters = 16, 2
        args.rounds, args.warmup = 8, 3
        args.samples, args.dim = 1024, 64
        if args.sharded:
            args.devices, args.clusters = 32, 4
        if args.segmented:
            args.segment_rounds, args.segments = 4, 2
    if args.sharded:
        return run_shard_bench(args)
    if args.scanned:
        return run_scan_bench(args)
    if args.segmented:
        return run_segmented_bench(args)

    key = jax.random.PRNGKey(0)
    data = make_classification(key, n=args.samples, dim=args.dim)
    parts = dirichlet_partition(key, data.y, args.devices)
    kw = dict(n_devices=args.devices, n_clusters=args.clusters,
              rounds=args.rounds, warmup=args.warmup, data=data,
              parts=parts, local_batch=args.local_batch)

    legacy_rps, _ = bench_legacy(**kw)
    print(f"engine,legacy_rounds_per_sec,{legacy_rps:.2f}")
    ref_rps, _ = bench_mode(False, **kw)
    print(f"engine,reference_rounds_per_sec,{ref_rps:.2f}")
    fused_rps, _ = bench_mode(True, **kw)
    print(f"engine,fused_rounds_per_sec,{fused_rps:.2f}")
    speedup = fused_rps / legacy_rps
    print(f"engine,fused_vs_legacy_speedup,{speedup:.2f}x "
          f"(n_devices={args.devices}, {args.rounds} rounds)")
    print(f"engine,fused_vs_reference_speedup,{fused_rps / ref_rps:.2f}x")
    split = bench_fused_split(
        n_devices=args.devices, n_clusters=args.clusters,
        rounds=args.rounds, data=data, parts=parts,
        local_batch=args.local_batch)
    print(f"engine,scan_compile_s,{split['compile_s']}")
    print(f"engine,scan_steady_rounds_per_sec,"
          f"{split['steady_rounds_per_sec']}")

    if not args.fast:
        payload = {
            "bench": "DeviceScaleEngine rounds/sec: fused FleetState jit "
                     "round vs the pre-refactor engine",
            "note": "legacy = reconstruction of the pre-refactor "
                    "DeviceScaleEngine (Python batch assembly, per-round "
                    "np/float syncs, unjitted trust pipeline, O(C^2) "
                    "_pick_frequency); reference = the new fixed-shape "
                    "round executed eagerly (trace-matches fused, see "
                    "test_fused_round_parity_with_reference); fused = one "
                    "jitted FleetState round per event",
            "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
            "device": str(jax.devices()[0]),
            "n_devices": args.devices,
            "n_clusters": args.clusters,
            "rounds_measured": args.rounds,
            "local_batch": args.local_batch,
            "dim": args.dim,
            "legacy_rounds_per_sec": round(legacy_rps, 2),
            "reference_rounds_per_sec": round(ref_rps, 2),
            "fused_rounds_per_sec": round(fused_rps, 2),
            "speedup_vs_legacy": round(speedup, 2),
            "speedup_vs_reference": round(fused_rps / ref_rps, 2),
            # span-derived split (repro.obs): scan-path compile time vs
            # steady-state execution, so the trajectory separates
            # compilation regressions from execution regressions
            "scan_span_split": split,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
