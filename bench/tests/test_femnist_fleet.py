"""The femnist-fleet deployment on the CPU, cut to a tiny fleet: its
writer-partitioned shards, and its first steps against the reference with
the trust kernel off (the jnp rule) and its own fixed controller."""
import jax
import numpy as np

from bench import check
from bench import harness as h
from bench import run as bench_run
from bench.tests.tiny import tiny_config

WORKLOAD = "femnist-fleet.scan"


def _tiny():
    cfg = tiny_config(h.cell(WORKLOAD)["config"])
    cfg["spec"]["aggregator"]["use_kernel"] = False
    return cfg


def test_tiny_fleet_is_writer_partitioned():
    cfg = _tiny()
    data, parts = h.bench_data.build(cfg, cfg["deploy_seed"])
    sizes = np.asarray([len(p) for p in parts])
    assert len(parts) == cfg["spec"]["fleet"]["n_devices"]
    assert sizes.sum() == cfg["data"]["n_samples"] and sizes.min() >= 1
    rows = np.concatenate(parts)
    assert np.array_equal(np.sort(rows), np.arange(cfg["data"]["n_samples"]))


def test_tiny_fleet_first_steps_are_correct_with_the_kernel_off(tmp_path):
    cfg = _tiny()
    assert cfg["spec"]["controller"] == {"kind": "fixed",
                                         "params": {"a": 5}}
    out = bench_run.run_cell(WORKLOAD, 2 ** 31 + 7, 0.3, False, cfg=cfg,
                             devices=jax.devices(),
                             work_dir=str(tmp_path))
    assert out["correct"], out["checks"]
    assert out["checks"]["cluster_mismatch"]["value"] == 0
    assert out["checks"]["action_gap"]["value"] == 0


def test_tiny_fleet_reference_sees_a_wrong_action():
    """The fixed a = 5 is scored: steps that took another a are not."""
    cfg = _tiny()
    mix = h.traffic(h.cell(WORKLOAD)["traffic"])
    spec = h.spec_dict(cfg, mix)
    data, parts = h.bench_data.build(cfg, cfg["deploy_seed"])
    key = h.episode_key(2 ** 31 + 7, 0)
    first, assign = check.control_first(spec, h.dims(cfg), data, parts, 4,
                                        "f32", jax.numpy.float32, key,
                                        "action")
    ref = check.reference_for(first, spec, h.dims(cfg), data, parts, assign,
                              key)
    assert check.numbers(first, ref, assign)["action_gap"] == 1.0
