"""Bring-up smoke test of the fleet plane on one TPU chip.

    python chip_smoke.py             # phases A, B and C on one chip
    python chip_smoke.py --chips 4   # the sharded phase-A spec on a 4-chip
                                     # mesh against the same spec on one

Runs the system's main path through its normal entry points, in this one
process, which holds the chip for its whole life:

A. the paper's §V deployment — the ``adaptive-scanned`` scenario: 16
   devices in 4 clusters, the 784-200-10 MLP, Eqn-6/19 trust aggregation
   on the Pallas kernel and the DQN controller with its scanned pretrain —
   through ``Federation.from_spec`` and ``run_scanned(40)``; then the
   event-loop ``run()`` against ``run_scanned`` on the same spec with a
   fixed controller, and the fused kernel against its jnp oracle;
B. 4096 devices in 16 clusters (k-means sizes around 256 members) on the
   same MLP and 65,536 samples, 10 scanned rounds with the kernel compiled;
C. ``repro.serve.run_service`` on ``autoencoder-anomaly``: 2 checkpointed
   segments, a resume for 1 more, byte-compared with 3 uninterrupted ones.

Every check failing, or an accelerator other than a TPU, exits non-zero
without the result line.  Data and weights come from fixed seeds.  The
seconds printed are one cold run each, not benchmark numbers.  The last
line of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# final accuracy of phase A's `run_scanned(40)` on the CPU (JAX_PLATFORMS=
# cpu, Pallas in interpret mode) at the same spec and seed; the chip must
# reach at least ACC_FLOOR of it
CPU_PHASE_A_ACC = 1.0
ACC_FLOOR = 0.9
PARITY_ROUNDS = 8           # phase A: event loop vs scan, fixed controller


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **kv) -> None:
    print(f"phase {phase} | " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def timed_scan(engine, K: int):
    """``engine.run_scanned(K)`` under telemetry (`repro.obs`): the engine
    compiles ahead of time and times the compile and the fenced execution
    in its own spans.  Returns the trace, both times, and whether the
    compiled program calls a Mosaic kernel (``tpu_custom_call``)."""
    from repro.obs import EngineObs
    hlo = []

    class Obs(EngineObs):
        def record_compile(self, fn_name, seconds, hlo_text=None,
                           **kw):
            hlo.append(hlo_text or "")
            super().record_compile(fn_name, seconds, hlo_text, **kw)

    obs = Obs()
    engine.set_obs(obs)
    trace = engine.run_scanned(K)
    engine.set_obs(None)
    return (trace, obs.spans.last("compile").dur_s,
            obs.spans.last("round").dur_s,
            any("tpu_custom_call" in h for h in hlo))


def finite_losses(trace) -> bool:
    import numpy as np
    return all(np.isfinite(r.loss) for r in trace.records)


def phase_a_spec():
    from repro.api import scenarios  # noqa: F401  (populates SCENARIOS)
    from repro.api.registry import SCENARIOS
    return SCENARIOS.get("adaptive-scanned")()


# --------------------------------------------------------------------- #
# A. the paper's deployment
# --------------------------------------------------------------------- #
def phase_a() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ControllerSpec, Federation
    from repro.core.trust import trust_weighted_average
    from repro.kernels.ops import trust_aggregate_global_tree

    spec = phase_a_spec()
    t0 = time.perf_counter()
    fed = Federation.from_spec(spec)        # includes the DQN pretrain
    setup_s = time.perf_counter() - t0
    trace, compile_s, steady_s, kernel = timed_scan(fed.engine, spec.rounds)
    acc = trace.records[-1].acc
    check(finite_losses(trace), "phase A: non-finite loss")
    check(kernel, "phase A: the compiled scan holds no tpu_custom_call")
    check(acc >= ACC_FLOOR * CPU_PHASE_A_ACC,
          f"phase A: accuracy {acc} < {ACC_FLOOR} x the CPU's "
          f"{CPU_PHASE_A_ACC}")
    report("A", rounds=spec.rounds, setup_s=setup_s,
           compile_s=compile_s, steady_s=steady_s,
           seconds="one-cold-run-not-a-benchmark", acc=acc,
           cpu_acc=CPU_PHASE_A_ACC, peak_bytes_in_use=peak_bytes())

    # event loop vs scan on the same spec, fixed controller
    K = PARITY_ROUNDS
    fixed = spec.replace(controller=ControllerSpec("fixed", {"a": 5}),
                         sim_seconds=1e9)
    event = Federation.from_spec(
        fixed.replace(execution="event")).run(eval_every=0.0, max_rounds=K)
    scan = Federation.from_spec(fixed).engine.run_scanned(K)
    rows = scan.records[:K]
    check(len(event.records) == K and len(scan.records) == K + 1,
          "phase A parity: record counts")
    for col in ("round", "cluster", "a", "agg_count"):
        check([getattr(r, col) for r in event.records]
              == [getattr(r, col) for r in rows],
              f"phase A parity: column {col} differs")
    np.testing.assert_allclose(event.times, [r.t for r in rows],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(event.energies, [r.energy for r in rows],
                               rtol=1e-6)
    np.testing.assert_allclose(event.records[-1].loss,
                               scan.records[-1].loss, rtol=1e-4)
    report("A-parity", rounds=K, event_loss=event.records[-1].loss,
           scan_loss=scan.records[-1].loss)

    # the fused Eqn 6 + 19 kernel against its jnp oracle at phase-A shapes
    C = int(np.bincount(fed.engine.assign).max())    # padded members
    B = spec.clustering.n_clusters
    N = sum(l.size for l in jax.tree.leaves(fed.engine.global_params))
    ku, kw, ks, kg = jax.random.split(jax.random.key(0), 4)
    upd = jax.random.normal(ku, (C, N), jnp.float32)
    w = jax.nn.softmax(jax.random.normal(kw, (C,)))
    mask = jnp.arange(C) < C - 1
    stack = jax.random.normal(ks, (B, N), jnp.float32)
    gw = jax.nn.softmax(jax.random.normal(kg, (B,)))
    got = trust_aggregate_global_tree(upd, w, mask, stack, gw, 2)
    agg = trust_weighted_average(upd, w * mask)
    want = trust_weighted_average(stack.at[2].set(agg), gw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    report("A-kernel", C=C, B=B, N=N,
           max_abs_err=float(jnp.max(jnp.abs(got - want))))


# --------------------------------------------------------------------- #
# B. clusters of 256 members
# --------------------------------------------------------------------- #
def phase_b(devices: int = 4096, clusters: int = 16,
            samples: int = 65536) -> None:
    import numpy as np

    from repro.api import (AggregatorSpec, ClusteringSpec, ControllerSpec,
                           Federation, FederationSpec, FleetSpec, TaskSpec)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=devices),
        clustering=ClusteringSpec(n_clusters=clusters),
        controller=ControllerSpec("fixed", {"a": 5}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("mlp", {"n_samples": samples}),
        execution="scanned", rounds=10, sim_seconds=1e9)
    fed = Federation.from_spec(spec)
    members = int(np.bincount(fed.engine.assign).max())
    trace, compile_s, steady_s, kernel = timed_scan(fed.engine, spec.rounds)
    check(finite_losses(trace), "phase B: non-finite loss")
    check(kernel, "phase B: the compiled scan holds no tpu_custom_call")
    report("B", rounds=spec.rounds, devices=devices, clusters=clusters,
           padded_members=members, compile_s=compile_s, steady_s=steady_s,
           seconds="one-cold-run-not-a-benchmark",
           acc=trace.records[-1].acc, peak_bytes_in_use=peak_bytes())


# --------------------------------------------------------------------- #
# C. service mode: checkpointed segments and a resume
# --------------------------------------------------------------------- #
def _span_seconds(records, name: str) -> float:
    def walk(sp):
        own = sp.get("dur_s", 0.0) if sp.get("name") == name else 0.0
        return own + sum(walk(c) for c in sp.get("children", ()))
    return sum(walk(r) for r in records if r.get("schema") == "span/1")


def phase_c(work: str) -> None:
    from repro.api import scenarios  # noqa: F401  (populates SCENARIOS)
    from repro.api.records import tail_jsonl
    from repro.api.registry import SCENARIOS
    from repro.serve.service import RunDir, run_service

    spec = SCENARIOS.get("autoencoder-anomaly")()
    seg = spec.rounds
    quiet = lambda *a, **k: None                            # noqa: E731

    def fresh(name):
        rd = RunDir(os.path.join(work, name)).ensure()
        rd.write_spec(spec)
        return rd

    ref = fresh("straight")
    run_service(ref.root, segment_rounds=seg, max_segments=3, keep=None,
                log=quiet)
    cut = fresh("resumed")
    run_service(cut.root, segment_rounds=seg, max_segments=2, keep=None,
                log=quiet)
    run_service(cut.root, segment_rounds=seg, max_segments=1, keep=None,
                resume=True, log=quiet)
    with open(ref.trace_path, "rb") as fa, open(cut.trace_path, "rb") as fb:
        check(fa.read() == fb.read(),
              "phase C: resumed trace.jsonl differs from the straight run")
    metrics = tail_jsonl(ref.metrics_path, n=10_000)
    last = tail_jsonl(ref.trace_path, n=1)[-1]
    check(last["acc"] is not None and math.isfinite(last["loss"]),
          "phase C: no finite final evaluation")
    report("C", rounds=3 * seg, segments=3,
           compile_s=_span_seconds(metrics, "compile"),
           steady_s=_span_seconds(metrics, "round"),
           seconds="one-cold-run-not-a-benchmark", auc=last["acc"],
           peak_bytes_in_use=peak_bytes())


# --------------------------------------------------------------------- #
# --chips 4: the cluster-major shard_map engine against one chip
# --------------------------------------------------------------------- #
def _sharded_pair(spec, ctl):
    """``run_scanned`` of ``spec`` on one chip and on a 4-chip mesh (the
    cluster-major shard_map engine), one DQN agent for both.  Checks what
    `api/cluster_engine.py` pins exactly across shards: scheduling,
    actions, counters and energies."""
    from repro.api import Federation, ShardingSpec
    from repro.api.cluster_engine import ClusterMajorEngine
    from repro.api.components import DQNController

    def build(s):
        return Federation.from_spec(
            s, controller=DQNController(ctl.agent, ctl.cfg)).engine

    plain = build(spec)
    sharded = build(spec.replace(sharding=ShardingSpec(mesh=(4,))))
    check(isinstance(sharded, ClusterMajorEngine),
          "sharded spec did not resolve to the cluster-major engine")
    ref, _, ref_steady, _ = timed_scan(plain, spec.rounds)
    got, compile_s, steady_s, kernel = timed_scan(sharded, spec.rounds)
    check(kernel, "sharded: the compiled scan holds no tpu_custom_call")
    check(len(ref.records) == len(got.records), "sharded: record counts")
    for col in ("t", "round", "cluster", "a", "agg_count", "energy"):
        check([getattr(r, col) for r in ref.records]
              == [getattr(r, col) for r in got.records],
              f"sharded: column {col} differs from the one-chip run")
    return ref, got, compile_s, steady_s, ref_steady


def sharded_phase() -> None:
    import jax
    import numpy as np

    from repro.api import Federation

    spec = phase_a_spec()
    ctl = Federation.from_spec(spec).controller        # one DQN pretrain

    def loss_rel_diff(ref, got):
        a = np.asarray([r.loss for r in ref.records])
        b = np.asarray([r.loss for r in got.records])
        return float(np.max(np.abs(a - b) / np.abs(a)))

    # as users run it: f32 matmuls at the chip's default (bf16-pass)
    # precision, which turns the Eqn-19 psum's reassociated ulps into
    # larger loss differences over the rounds; reported, and the exact
    # columns checked
    ref, got, compile_s, steady_s, ref_steady = _sharded_pair(spec, ctl)
    report("sharded-4", precision="default", rounds=spec.rounds,
           compile_s=compile_s, steady_s=steady_s,
           one_chip_steady_s=ref_steady,
           seconds="one-cold-run-not-a-benchmark",
           loss_max_rel_diff=loss_rel_diff(ref, got),
           acc=got.records[-1].acc, one_chip_acc=ref.records[-1].acc,
           peak_bytes_in_use=peak_bytes())
    # with f32 matmuls the reassociation is all that differs: the
    # contract's losses to rtol 1e-5
    with jax.default_matmul_precision("highest"):
        ref, got, compile_s, steady_s, ref_steady = _sharded_pair(spec, ctl)
    np.testing.assert_allclose([r.loss for r in got.records],
                               [r.loss for r in ref.records], rtol=1e-5)
    report("sharded-4", precision="highest", rounds=spec.rounds,
           compile_s=compile_s, steady_s=steady_s,
           one_chip_steady_s=ref_steady,
           seconds="one-cold-run-not-a-benchmark",
           loss_max_rel_diff=loss_rel_diff(ref, got),
           acc=got.records[-1].acc, one_chip_acc=ref.records[-1].acc,
           peak_bytes_in_use=peak_bytes())


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase-A comparison on a "
                         "4-chip mesh")
    args = ap.parse_args(argv)
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"error: no repro package under {src}; run chip_smoke.py "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: no TPU (JAX found {dev.platform!r}); the smoke test "
              "runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    try:
        if args.chips == 4:
            sharded_phase()
        else:
            phase_a()
            phase_b()
            os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
            with tempfile.TemporaryDirectory(
                    dir=os.path.join(REPO, "chiprun_out")) as work:
                phase_c(work)
    except (SmokeFailure, AssertionError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
