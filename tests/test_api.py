"""Unified federation API: spec round-trips, registries, engine parity
with the legacy entry points, scenario CLI, both execution scales."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
import repro.core as core
from repro.api import (AggregatorSpec, ControllerSpec, Federation,
                       FederationSpec, FleetSpec, legacy_spec)
from repro.data import dirichlet_partition, make_classification


def _data(n=1536, dim=48, devices=8, seed=0):
    key = jax.random.PRNGKey(seed)
    data = make_classification(key, n=n, dim=dim)
    return data, dirichlet_partition(key, data.y, devices)


# --------------------------------------------------------------------- #
# spec <-> dict round-trip
# --------------------------------------------------------------------- #
def test_spec_dict_roundtrip():
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8, malicious_frac=0.25),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("krum", {"f": 1}),
        sim_seconds=5.0, seed=7)
    d = spec.to_dict()
    assert d["fleet"]["n_devices"] == 8
    assert FederationSpec.from_dict(d) == spec


def test_spec_from_dict_rejects_unknown_keys():
    with pytest.raises(KeyError, match="unknown keys"):
        FederationSpec.from_dict({"fleeet": {}})
    with pytest.raises(KeyError, match="unknown keys"):
        FederationSpec.from_dict({"fleet": {"n_devicez": 4}})


def test_spec_validate_rejects_unknown_components():
    with pytest.raises(KeyError, match="unknown aggregator"):
        FederationSpec(aggregator=AggregatorSpec("krummm")).validate()
    with pytest.raises(KeyError, match="unknown controller"):
        FederationSpec(controller=ControllerSpec("dqnn")).validate()


def test_spec_validate_rejects_scale_task_mismatch():
    with pytest.raises(ValueError, match="use task 'lm'"):
        FederationSpec(scale=api.DATACENTER_SCALE).validate()   # default mlp
    with pytest.raises(ValueError, match="use task 'mlp'"):
        FederationSpec(task=api.TaskSpec("lm")).validate()


def test_spec_validate_rejects_unimplemented_datacenter_components():
    base = FederationSpec(scale=api.DATACENTER_SCALE, task=api.TaskSpec("lm"))
    with pytest.raises(ValueError, match="not supported at datacenter"):
        base.replace(aggregator=AggregatorSpec("krum")).validate()
    with pytest.raises(ValueError, match="not implemented at datacenter"):
        base.replace(privacy=api.PrivacySpec(clip=1.0, noise=0.5)).validate()


def test_registry_decorator_and_lookup():
    from repro.api.registry import Registry
    reg = Registry("widget")

    @reg.register("foo")
    def make_foo(params):
        return ("foo", params)

    assert reg.get("foo")({"x": 1}) == ("foo", {"x": 1})
    assert "foo" in reg and reg.names() == ["foo"]
    with pytest.raises(KeyError, match="unknown widget"):
        reg.get("bar")
    with pytest.raises(ValueError, match="duplicate"):
        reg.register("foo")(make_foo)


def test_builtin_registries_populated():
    for name in ("trust", "fedavg", "krum", "multi_krum", "median",
                 "trimmed_mean"):
        assert name in api.AGGREGATORS
    for name in ("fixed", "dqn", "lyapunov"):
        assert name in api.CONTROLLERS
    for name in ("mlp", "lm"):
        assert name in api.TASKS
    for name in ("byzantine", "dp", "heterogeneous", "sync-baseline",
                 "lm-modeA"):
        assert name in api.SCENARIOS


# --------------------------------------------------------------------- #
# parity: spec-built federation == legacy AsyncFederation, bit for bit.
# Both entry points run DeviceScaleEngine, so this pins the *translation*
# (legacy_spec + the shim's controller mapping), not monolith-era numerics:
# a drift in either construction path breaks float equality here.
# --------------------------------------------------------------------- #
def test_spec_parity_with_legacy():
    data, parts = _data()
    cfg = core.AsyncFLConfig(n_devices=8, n_clusters=2, local_batch=32,
                             sim_seconds=5.0, seed=11)
    legacy = core.AsyncFederation(cfg, data, parts).run(eval_every=1.5)
    tr = Federation.from_spec(legacy_spec(cfg), data=data,
                              parts=parts).run(eval_every=1.5)
    assert legacy.times == tr.times
    assert legacy.accs == tr.accs          # float equality: bit-for-bit
    assert legacy.losses == tr.losses
    assert legacy.energies == tr.energies
    assert legacy.agg_counts == tr.agg_counts


def test_fused_round_parity_with_reference():
    """The fused jitted `FleetState` round reproduces the reference
    (pre-refactor-style eager, per-round host-sync) execution of the same
    round function at a fixed seed.  Scheduling (event times), chosen a_i,
    round/aggregation counters and accuracies match bit for bit; losses and
    energies are float32 reductions whose XLA-fused (FMA-contracted) form
    may differ from eager op-by-op dispatch in the last ulp, so they are
    pinned to ulp-level tolerance instead."""
    data, parts = _data(seed=9)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8, malicious_frac=0.25),
        clustering=api.ClusteringSpec(n_clusters=3),
        controller=ControllerSpec("fixed", {"a": 4}),
        sim_seconds=4.0, local_batch=32, seed=9)
    fused = Federation.from_spec(spec, data=data, parts=parts,
                                 fused=True).run(eval_every=1.0)
    ref = Federation.from_spec(spec, data=data, parts=parts,
                               fused=False).run(eval_every=1.0)
    assert len(fused.records) == len(ref.records) > 1
    # integer fields are bit-exact everywhere; float fields are observed
    # bit-exact on this CPU container but asserted at ulp tolerance so the
    # test stays meaningful on backends with different fusion contraction
    assert [r.a for r in fused.records] == [r.a for r in ref.records]
    assert fused.agg_counts == ref.agg_counts
    assert [r.cluster for r in fused.records] == \
           [r.cluster for r in ref.records]
    np.testing.assert_allclose(fused.times, ref.times, rtol=1e-6)
    np.testing.assert_allclose(fused.accs, ref.accs, atol=2e-3)
    np.testing.assert_allclose(fused.losses, ref.losses, rtol=5e-6)
    np.testing.assert_allclose(fused.energies, ref.energies, rtol=5e-6)


def test_fleet_state_is_device_resident_pytree():
    """FleetState is one flat pytree of arrays (jit-donatable): no Python
    scalars or host state hide inside."""
    data, parts = _data(seed=6)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 2}),
        sim_seconds=1.0, local_batch=32, seed=6)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    fed.run(eval_every=1.0)
    leaves = jax.tree.leaves(fed.engine.state)
    assert leaves and all(isinstance(l, jax.Array) for l in leaves)
    assert fed.engine.state.rep.shape == (8,)
    assert int(fed.engine.state.round) == fed.engine.agg_count > 0


def _buffer_pointer(leaf):
    if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
        leaf = jax.random.key_data(leaf)
    return leaf.unsafe_buffer_pointer()


def test_fleet_state_leaves_own_their_buffers_under_donation():
    """The rounds donate `FleetState` on every backend, and XLA refuses to
    donate one buffer twice: every leaf of a fresh engine owns its buffer,
    and back-to-back scans consume (delete) the state they were given."""
    data, parts = _data(seed=16)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 2}),
        execution="scanned", rounds=3, sim_seconds=1e9, local_batch=32,
        seed=16)
    eng = Federation.from_spec(spec, data=data, parts=parts).engine
    ptrs = [_buffer_pointer(l) for l in jax.tree.leaves(eng.state)]
    assert len(set(ptrs)) == len(ptrs)
    given = eng.state
    eng.run_scanned(3, eval_final=False)
    assert given.rep.is_deleted()
    tr = eng.run_scanned(3)
    assert eng.round == 6
    assert all(np.isfinite(r.loss) for r in tr.records)


def test_exact_shape_mode_drives_robust_aggregators():
    """Aggregators without mask support (krum-family rank statistics; the
    ±inf-padded sorts give median and trimmed_mean masked variants) run
    through the exact-shape jitted round and still produce a learning
    federation."""
    data, parts = _data(seed=7)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8, malicious_frac=0.25),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("multi_krum"),
        sim_seconds=3.0, local_batch=32, seed=7)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    assert not fed.engine._padded          # exact member shapes, no padding
    trace = fed.run(eval_every=1.0)
    assert trace.records and trace.accs[-1] > 0.2


def test_kernel_and_jnp_aggregation_agree():
    """The Pallas hot path and the jnp fallback build the same federation."""
    data, parts = _data(seed=2)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        sim_seconds=3.0, local_batch=32, seed=2)
    t_kernel = Federation.from_spec(
        spec.replace(aggregator=AggregatorSpec("trust", use_kernel=True)),
        data=data, parts=parts).run(eval_every=1.0)
    t_jnp = Federation.from_spec(
        spec.replace(aggregator=AggregatorSpec("trust", use_kernel=False)),
        data=data, parts=parts).run(eval_every=1.0)
    np.testing.assert_allclose(t_kernel.accs, t_jnp.accs, atol=1e-6)
    np.testing.assert_allclose(t_kernel.losses, t_jnp.losses, atol=1e-5)


# --------------------------------------------------------------------- #
# components through the facade
# --------------------------------------------------------------------- #
def test_robust_aggregator_scenario_runs():
    data, parts = _data(seed=3)
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8, malicious_frac=0.25),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("median"),
        sim_seconds=3.0, local_batch=32, seed=3)
    trace = Federation.from_spec(spec, data=data, parts=parts).run(
        eval_every=1.0)
    assert trace.records and trace.accs[-1] > 0.2


def test_lyapunov_controller_respects_budget_pressure():
    """With a tiny budget the deficit queue builds and the greedy controller
    backs off to small a; with a huge budget it picks larger a."""
    ctx = api.ControllerCtx(round=5, cluster=0, obs=lambda: None,
                            cluster_loss=2.0, cluster_freq=1.0,
                            mean_freq=1.0, channel_good_frac=0.5,
                            energy_used=0.0)
    rich = api.LyapunovGreedyController(budget=1e6, horizon=10)
    poor = api.LyapunovGreedyController(budget=1.0, horizon=10)
    for _ in range(5):                      # build up the deficit queue
        poor.observe(ctx, consumed=10.0, loss=2.0)
    assert rich.select(ctx) >= poor.select(ctx)
    assert poor.select(ctx) == 1


def test_dp_privacy_spec_applies_noise():
    data, parts = _data(seed=4)
    base = FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 2}),
        sim_seconds=2.0, local_batch=32, seed=4)
    clean = Federation.from_spec(base, data=data, parts=parts).run()
    noisy = Federation.from_spec(
        base.replace(privacy=api.PrivacySpec(clip=1.0, noise=2.0)),
        data=data, parts=parts).run()
    assert clean.losses != noisy.losses     # DP path actually engaged


def test_datacenter_scale_runs_and_records():
    spec = FederationSpec(
        scale=api.DATACENTER_SCALE,
        fleet=FleetSpec(n_devices=4),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 1, "n_actions": 2}),
        task=api.TaskSpec("lm", {"seq": 8, "micro_batch": 2}),
        rounds=2)
    trace = Federation.from_spec(spec).run()
    assert len(trace.records) == 2
    assert all(np.isfinite(r.loss) for r in trace.records)
    assert trace.records[0].acc is None


# --------------------------------------------------------------------- #
# scenario CLI
# --------------------------------------------------------------------- #
def test_cli_spec_json_and_list(capsys):
    from repro.api import run as cli
    assert cli.main(["--list"]) == 0
    assert cli.main(["--scenario", "byzantine", "--spec-json"]) == 0
    out = capsys.readouterr().out
    assert '"malicious_frac": 0.25' in out


def test_cli_byzantine_end_to_end(capsys):
    from repro.api import run as cli
    rc = cli.main(["--scenario", "byzantine", "--sim-seconds", "2",
                   "--devices", "8", "--clusters", "2",
                   "--eval-every", "1.0"])
    assert rc == 0
    assert "summary:" in capsys.readouterr().out


def test_legacy_shim_exposes_engine_state():
    data, parts = _data(seed=5)
    cfg = core.AsyncFLConfig(n_devices=8, n_clusters=2, local_batch=32,
                             sim_seconds=2.0, malicious_frac=0.25, seed=5)
    fed = core.AsyncFederation(cfg, data, parts)
    fed.run(eval_every=1.0)
    assert fed.agg_count > 0 and fed.energy_used > 0
    assert fed.rep.shape == (8,) and fed.malicious.sum() == 2
