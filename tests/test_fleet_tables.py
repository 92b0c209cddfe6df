"""The fleet's read-only tables (`FleetTables`) are arguments of every
round program, never constants baked into it.

* The traced scan (``run_k``), the per-event round and the controller
  feature/observation programs capture no array constant larger than
  64 KB, on a tiny writer-partitioned fleet and at the paper's §V shapes,
  for the single-device engine and the cluster-major engine.
* Two deployments with the same shapes but other data lower the scan to
  the same module text (so the persistent compile cache serves the
  second), and still train differently: the data are read from the
  argument.
* Under telemetry, each compile records the bytes of the constants it
  captured (``fl_program_const_bytes``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, Federation,
                       FederationSpec, FleetSpec, ShardingSpec, TaskSpec)
from repro.data import make_classification
from repro.obs import EngineObs, compiles

LIMIT = 64 * 1024          # bytes: scalars and small per-device vectors
K = 3


def _writer_parts(seed, n_samples, n_devices):
    """Ragged shards, one per writer: a seeded permutation cut at sizes
    drawn around the mean (every writer holds at least one sample)."""
    rng = np.random.default_rng(seed)
    raw = np.maximum(rng.normal(1.0, 0.4, n_devices), 0.1)
    sizes = np.maximum(np.floor(raw / raw.sum() * n_samples), 1).astype(int)
    sizes[-1] = n_samples - sizes[:-1].sum()
    perm = rng.permutation(n_samples)
    return [np.sort(p) for p in np.split(perm, np.cumsum(sizes)[:-1])]


FLEETS = {
    # tiny writer-partitioned fleet
    "tiny": dict(n=1024, dim=32, devices=24, clusters=3, hidden=16,
                 classes=6, batch=8, writer=True),
    # the paper's §V shapes (paper-v: 4,096 x 784 data, 784-200-10 MLP)
    "paper-v": dict(n=4096, dim=784, devices=16, clusters=4, hidden=200,
                    classes=10, batch=64, writer=False),
}


def _fleet(shape, seed=0, data_seed=None, mesh=None):
    f = FLEETS[shape]
    key = jax.random.PRNGKey(seed if data_seed is None else data_seed)
    data = make_classification(key, n=f["n"], dim=f["dim"],
                               n_classes=f["classes"])
    if f["writer"]:
        parts = _writer_parts(0, f["n"], f["devices"])
    else:
        parts = np.array_split(np.arange(f["n"]), f["devices"])
    spec = FederationSpec(
        seed=seed, local_batch=f["batch"], execution="scanned",
        fleet=FleetSpec(n_devices=f["devices"]),
        clustering=api.ClusteringSpec(n_clusters=f["clusters"]),
        controller=ControllerSpec("fixed", {"a": 2}),
        aggregator=AggregatorSpec("trust", {"use_kernel": False}),
        task=TaskSpec("mlp", {"hidden": f["hidden"],
                              "n_classes": f["classes"]}),
        sharding=ShardingSpec(mesh=mesh) if mesh else ShardingSpec())
    return DeviceEngine(Federation.from_spec(spec, data=data,
                                             parts=parts).engine)


class DeviceEngine:
    """The traced round programs of one engine."""

    def __init__(self, engine):
        self.engine = engine

    def scan(self):
        e = self.engine
        pol = e.controller.scan_policy()
        args = [e.state, e._scan_times, pol.state, e._scan_energy_start()]
        if hasattr(e, "_statics"):           # cluster-major
            args += [e._ftbl, e._ch3, *e._statics]
        else:
            args += [e.tables]
        return e._build_scan_fn(K, pol).trace(*args)

    def per_event(self):
        """The event loop's per-round program and the controller's
        feature and observation programs."""
        e = self.engine
        if hasattr(e, "_statics"):
            return [e._build_event_fn().trace(
                e.state, e._ftbl, e._ch3, jnp.int32(0), jnp.int32(2),
                *e._statics)]
        return [e._round_fn.trace(e.state, e.tables, 0, 2, e._members[0],
                                  e._masks[0]),
                e._features_fn.trace(e.state, e.tables, jnp.int32(0)),
                e._obs_fn.trace(e.state, e.tables, jnp.int32(0))]


def _consts(traced):
    return [(c.shape, c.nbytes) for c in traced.jaxpr.consts
            if getattr(c, "nbytes", 0) > LIMIT]


@pytest.mark.parametrize("mesh", [None, (1,)],
                         ids=["single-device", "cluster-major"])
@pytest.mark.parametrize("shape", sorted(FLEETS))
def test_round_programs_capture_no_fleet_table(shape, mesh):
    eng = _fleet(shape, mesh=mesh)
    assert (mesh is not None) == hasattr(eng.engine, "_statics")
    x_bytes = eng.engine.tables.x.nbytes
    assert x_bytes > LIMIT                     # the data would show
    for traced in [eng.scan()] + eng.per_event():
        assert _consts(traced) == []
        assert compiles.const_bytes(traced.jaxpr) <= LIMIT


def test_same_shapes_other_data_lower_to_one_program():
    """Two deployments of one shape, on data drawn from other seeds,
    lower the scan to the same text, and their runs differ."""
    a = _fleet("tiny", data_seed=3)
    b = _fleet("tiny", data_seed=11)
    assert not np.array_equal(np.asarray(a.engine.tables.x),
                              np.asarray(b.engine.tables.x))
    assert a.scan().lower().as_text() == b.scan().lower().as_text()
    ra = a.engine.run_scanned(K, eval_final=False).records
    rb = b.engine.run_scanned(K, eval_final=False).records
    assert [r.loss for r in ra] != [r.loss for r in rb]


def test_compile_records_the_constant_bytes_it_captured():
    eng = _fleet("tiny").engine
    obs = EngineObs()
    eng.set_obs(obs)
    eng.run_scanned(K, eval_final=False)
    fam = obs.registry.snapshot()["families"]["fl_program_const_bytes"]
    series = {tuple(sorted(s["labels"].items())): s["value"]
              for s in fam["series"]}
    assert set(series) == {(("fn", f"run_scanned[K={K}]"),)}
    assert 0 <= min(series.values()) <= LIMIT
    # the reading is the captured bytes: a program that closes over an
    # array reports that array's size
    big = jnp.ones((300, 100), jnp.float32)
    assert compiles.const_bytes(
        jax.jit(lambda v: v + big.sum()).trace(1.0).jaxpr) == big.nbytes
