"""Population engine (`repro.pop`) + multi-tenant pool serving.

The contract under test: stacking B federations along a population axis
and vmapping the fused round changes *how many* federations one device
program advances — never *what* any member computes.

* Per-member traces from `PopulationEngine.run_scanned` are bit-identical
  to standalone ``Federation.from_spec(spec).run_scanned`` runs of the
  expanded member specs, across controllers (fixed / Lyapunov / DQN),
  heterogeneous lifted scalars (lr, pkt_fail, DP sigma, fault
  intensities, the trust-vs-fedavg flag), and segmented continuation.
* `PopulationSpec` expands grids x replicates deterministically, derives
  member seeds via `member_seed` (fold_in, not ``seed + i``), and
  round-trips through dict/JSON.
* The pool supervisor (`repro.serve.pool`) drives per-member run dirs
  that speak the single-tenant file protocol: traces and checkpointed
  resume stay bit-identical to a standalone `run_service` of the same
  member spec — including resume from a ragged checkpoint frontier.
* ``pop``-labeled telemetry respects the registry's cardinality cap.
* On an 8-way forced-host mesh (subprocess) the sharded population keeps
  the unsharded one's scheduling and counters exactly, and its floats to
  rtol 1e-6.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.api as api
from repro.api import (AggregatorSpec, ChannelSpec, ControllerSpec,
                       Federation, FederationSpec, FleetSpec, PrivacySpec,
                       ShardingSpec, TaskSpec)
from repro.faults import FaultSpec
from repro.pop import PopulationEngine, PopulationSpec, member_seed


def _spec(seed, **kw):
    base = dict(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("mlp", {"n_samples": 256, "dim": 16, "hidden": 16}),
        execution="scanned", rounds=5, sim_seconds=1e9,
        local_batch=16, seed=seed)
    base.update(kw)
    return FederationSpec(**base)


def _tuples(trace):
    return [(r.t, r.round, r.cluster, r.a, r.loss, r.acc, r.energy,
             r.agg_count) for r in trace.records]


def _assert_member_parity(specs, traces, K):
    for b, s in enumerate(specs):
        ref = Federation.from_spec(s).run_scanned(K)
        assert _tuples(traces[b]) == _tuples(ref), f"member {b} diverged"


# --------------------------------------------------------------------- #
# spec layer
# --------------------------------------------------------------------- #
def test_member_seed_deterministic_and_distinct():
    seeds = [member_seed(7, b) for b in range(16)]
    assert seeds == [member_seed(7, b) for b in range(16)]
    assert len(set(seeds)) == 16
    assert all(isinstance(s, int) and s >= 0 for s in seeds)
    assert member_seed(8, 0) != member_seed(7, 0)


def test_population_spec_expand_grid_replicates_roundtrip():
    pspec = PopulationSpec(base=_spec(3),
                           grid={"lr": [0.1, 0.05],
                                 "channel.pkt_fail": [0.0, 0.2]},
                           replicates=2)
    assert pspec.size == 8
    members = pspec.expand()
    assert len(members) == 8
    # cartesian order, replicates innermost; derived member seeds
    assert [m.lr for m in members] == [0.1] * 4 + [0.05] * 4
    assert [m.channel.pkt_fail for m in members] == \
        ([0.0, 0.0, 0.2, 0.2] * 2)
    assert [m.seed for m in members] == \
        [member_seed(3, b) for b in range(8)]
    # dict/JSON round-trip reproduces the same expansion
    again = PopulationSpec.from_dict(
        json.loads(json.dumps(pspec.to_dict())))
    assert again.expand() == members
    # derive_seeds=False sweeps against the verbatim base seed
    fixed = pspec.replace(derive_seeds=False).expand()
    assert all(m.seed == 3 for m in fixed)


def test_population_spec_validation_errors():
    with pytest.raises(ValueError, match="replicates"):
        PopulationSpec(base=_spec(0), replicates=0).validate()
    with pytest.raises(ValueError, match="grid"):
        PopulationSpec(base=_spec(0), grid={"lr": []}).validate()
    with pytest.raises(ValueError, match="unsharded"):
        PopulationSpec(base=_spec(
            0, sharding=ShardingSpec(mesh=(2,)))).validate()
    with pytest.raises(ValueError, match="does not divide"):
        PopulationSpec(base=_spec(0), replicates=3,
                       sharding=ShardingSpec(mesh=(2,))).validate()
    with pytest.raises(KeyError, match="no field"):
        PopulationSpec(base=_spec(0), grid={"nope": [1]}).expand()


def test_population_engine_rejects_structural_mismatch():
    specs = [_spec(0), _spec(1, fleet=FleetSpec(n_devices=12))]
    with pytest.raises(ValueError, match="uniform"):
        PopulationEngine(specs)


# --------------------------------------------------------------------- #
# bit-parity with standalone runs (the tentpole invariant)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("ctl", [
    ControllerSpec("fixed", {"a": 3}),
    ControllerSpec("lyapunov", {"budget": 200.0, "horizon": 40}),
    ControllerSpec("dqn", {"episodes": 1, "horizon": 8, "seed": 0}),
], ids=["fixed", "lyapunov", "dqn"])
def test_population_trace_bit_identical(ctl):
    specs = [_spec(member_seed(11, b), controller=ctl) for b in range(2)]
    traces = PopulationEngine(specs).run_scanned(5)
    _assert_member_parity(specs, traces, 5)


def test_population_parity_heterogeneous_members():
    """Every lifted axis at once: per-member lr, pkt_fail, DP sigma,
    fault intensities + fault seed, and the trust-vs-fedavg flag (mixed
    aggregators and DP are each lifted, but cannot combine — the DP
    weight path branches on the aggregator kind)."""
    faults = lambda b: FaultSpec(                            # noqa: E731
        dropout=0.1 + 0.1 * b, straggler_frac=0.2,
        straggler_factor=2.0 + b, twin_spike_prob=0.15,
        seed=100 + b)
    mixed = [
        _spec(member_seed(19, b),
              lr=0.1 - 0.02 * b,
              channel=ChannelSpec(pkt_fail=0.05 * b),
              aggregator=AggregatorSpec(
                  "fedavg" if b == 1 else "trust"),
              faults=faults(b))
        for b in range(3)]
    _assert_member_parity(mixed, PopulationEngine(mixed).run_scanned(5), 5)

    dp = [
        _spec(member_seed(19, b),
              lr=0.1 - 0.02 * b,
              channel=ChannelSpec(pkt_fail=0.05 * b),
              privacy=PrivacySpec(clip=1.0, noise=0.01 * (b + 1)),
              faults=faults(b))
        for b in range(3)]
    _assert_member_parity(dp, PopulationEngine(dp).run_scanned(5), 5)

    forbidden = [dataclasses.replace(s, privacy=p.privacy)
                 for s, p in zip(mixed, dp)]
    with pytest.raises(ValueError, match="DP"):
        PopulationEngine(forbidden)


def test_population_segments_match_one_run():
    specs = [_spec(member_seed(23, b), lr=0.1 - 0.03 * b)
             for b in range(2)]
    pop = PopulationEngine(specs)
    first = pop.run_scanned(2, eval_final=False)
    rest = pop.run_scanned(3)
    for b, s in enumerate(specs):
        ref = Federation.from_spec(s).run_scanned(5)
        assert _tuples(first[b]) + _tuples(rest[b]) == _tuples(ref)


# --------------------------------------------------------------------- #
# pool supervisor: per-member run dirs + bit-exact ragged resume
# --------------------------------------------------------------------- #
def test_pool_serve_resume_bit_parity(tmp_path):
    from repro.serve.pool import (common_checkpoint_step, member_dir,
                                  pool_status, run_pool, write_pool_spec)
    from repro.serve.service import RunDir, run_service

    pspec = PopulationSpec(base=_spec(42), replicates=2)
    root = str(tmp_path / "pool")
    os.makedirs(root)
    write_pool_spec(root, pspec)
    quiet = lambda m: None                                   # noqa: E731

    run_pool(root, segment_rounds=2, max_segments=2, keep=None, log=quiet)
    assert common_checkpoint_step(
        [member_dir(root, b) for b in range(2)]) == 4

    # ragged frontier: member 1 lost its newest checkpoint (a crash
    # mid-sweep); resume must fall back to the common step for BOTH
    for f in os.listdir(os.path.join(member_dir(root, 1), "checkpoints")):
        if "00000004" in f:
            os.remove(os.path.join(member_dir(root, 1), "checkpoints", f))
    run_pool(root, segment_rounds=2, max_segments=2, keep=None,
             resume=True, log=quiet)

    st = pool_status(root)
    assert st["state"]["status"] == "stopped"
    assert st["state"]["rounds"] == 6
    assert [m["checkpoint_step"] for m in st["members"]] == [6, 6]

    # each member dir speaks the single-tenant protocol and its trace is
    # bit-identical to a standalone service run of the expanded spec
    for b, spec in enumerate(pspec.expand()):
        sdir = str(tmp_path / f"single{b}")
        rd = RunDir(sdir).ensure()
        rd.write_spec(spec)
        run_service(sdir, segment_rounds=2, max_segments=3, keep=None,
                    log=quiet)
        with open(os.path.join(member_dir(root, b), "trace.jsonl")) as f:
            got = [json.loads(ln) for ln in f]
        with open(rd.trace_path) as f:
            want = [json.loads(ln) for ln in f]
        assert got == want, f"member {b} trace diverged"


def test_pool_metrics_pop_label_cardinality_cap():
    from repro.obs import EngineObs
    obs = EngineObs(source="pool", max_series=4)
    g = obs.registry.gauge("pool_member_loss", "per-member loss")
    for b in range(32):
        g.set(float(b), pop=str(b))
    snap = obs.registry.snapshot()
    series = snap["families"]["pool_member_loss"]["series"]
    assert len(series) <= 5                  # cap + the overflow series
    labels = [s["labels"] for s in series]
    assert {"overflow": "true"} in labels
    dropped = snap["families"]["metrics_dropped_series_total"]["series"]
    assert dropped[0]["labels"] == {"metric": "pool_member_loss"}
    assert dropped[0]["value"] >= 28


# --------------------------------------------------------------------- #
# 8-way mesh (subprocess): sharded population parity
# --------------------------------------------------------------------- #
_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, FederationSpec,
                       FleetSpec, ShardingSpec, TaskSpec)
from repro.pop import PopulationEngine, PopulationSpec

assert jax.device_count() == 8
base = FederationSpec(
    fleet=FleetSpec(n_devices=8),
    clustering=api.ClusteringSpec(n_clusters=2),
    controller=ControllerSpec("fixed", {"a": 3}),
    aggregator=AggregatorSpec("trust"),
    task=TaskSpec("mlp", {"n_samples": 256, "dim": 16, "hidden": 16}),
    execution="scanned", rounds=4, sim_seconds=1e9,
    local_batch=16, seed=51)
rows = {}
for name, sh in (("plain", ShardingSpec()),
                 ("shard", ShardingSpec(mesh=(8,)))):
    pspec = PopulationSpec(base=base, replicates=8, sharding=sh)
    pop = PopulationEngine.from_population(pspec)
    assert (pop.mesh is not None) == (name == "shard")
    traces = pop.run_scanned(4)
    rows[name] = [[[r.t, r.round, r.cluster, r.a, r.loss, r.energy,
                    r.agg_count] for r in tr.records] for tr in traces]
print("POPPAR" + json.dumps(rows))
"""


def _run_subproc():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.split("POPPAR", 1)[1])


def test_sharded_population_ints_exact_floats_close_subprocess():
    """The sharded-reduction contract: members never exchange data, but
    XLA may partition a member's float reductions differently once the
    population axis is sharded, so losses (and the times and energies
    downstream) may move in the last ulp.  Scheduling and counters stay
    exact."""
    rows = _run_subproc()
    assert len(rows["plain"]) == len(rows["shard"]) == 8
    for plain, shard in zip(rows["plain"], rows["shard"]):
        assert len(plain) == len(shard)
        for p, s in zip(plain, shard):
            # t, round, cluster, a, loss, energy, agg_count
            assert p[1:4] == s[1:4] and p[6] == s[6]
            np.testing.assert_allclose([p[0], p[4], p[5]],
                                       [s[0], s[4], s[5]], rtol=1e-6)
