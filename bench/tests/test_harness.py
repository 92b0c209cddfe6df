"""The harness on the CPU: every driver on a tiny configuration, the
off-chip refusal, and BENCHMARK.json against the benchmark's contract."""
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from bench import harness as h
from bench import run as bench_run
from bench.tests.tiny import FIXED, tiny_config

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = h.benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_runs_on_a_tiny_fleet(workload, tmp_path):
    cell = h.cell(workload)
    cfg = tiny_config(cell["config"], FIXED)
    out = bench_run.run_cell(workload, 2 ** 33 + 17, 0.5, False, cfg=cfg,
                             devices=jax.devices(),
                             work_dir=str(tmp_path))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_off_chip_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(h.ROOT, "bench", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=h.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_names_units_and_keys_follow_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in metrics])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def _reports(workload, metric):
    return workload in metric.get("workloads", [workload])


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        for w in BENCH["workloads"]:
            if _reports(w["name"], m):
                assert _reports(w["name"], e2e[m["moves"]]), (m, w)
    for w in BENCH["workloads"]:
        assert any(_reports(w["name"], m) for m in BENCH["per_layer"])
        assert any(_reports(w["name"], m) for m in BENCH["end_to_end"]
                   if m["name"] != "setup_s")


def test_every_metric_lists_only_cells_that_exist():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]


def test_every_configuration_has_a_cell_and_its_files():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        cfg = h.config(c["name"])
        assert {"source", "reduced", "assumed"} <= set(cfg)
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        h.traffic(w["traffic"])
        h.limits(w["name"])
        h.driver(h.traffic(w["traffic"])["driver"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(h.metric_reader(m["name"]).read)


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A later cell adds files; nothing that exists is edited."""
    for sub in ("configs", "traffic", "limits"):
        (tmp_path / sub).mkdir()
    cfg = tiny_config("paper-v")
    (tmp_path / "configs" / "new-fleet.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(
        {"driver": "scan_episodes", "segment_rounds": 5, "round_cap": 10,
         "trace_seconds": 1}))
    (tmp_path / "limits" / "new.cell.json").write_text(json.dumps(
        {"numbers": {}}))
    monkeypatch.setattr(h, "BENCH", str(tmp_path))
    assert h.config("new-fleet")["data"]["dim"] == 32
    assert h.traffic("new-mix")["driver"] == "scan_episodes"
    assert h.limits("new.cell") == {"numbers": {}}
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "new_metric.py").write_text("def read(ctx):\n    return 7\n")
    import bench.metrics
    monkeypatch.setattr(bench.metrics, "__path__",
                        list(bench.metrics.__path__) + [str(metrics)])
    assert h.metric_reader("new_metric").read({}) == 7


def test_every_seed_runs_the_same_streams_in_another_order():
    def keys(seed, n):
        run = h.Run(workload="w", seed=seed, cfg={}, mix={"streams": n},
                    spec={})
        return [tuple(jax.random.key_data(h.stream_key(run, e)).tolist())
                for e in range(1 + 2 * n)]
    a, b = keys(2 ** 31 + 5, 4), keys(7, 4)
    assert a[0] != b[0]                      # the check's own stream
    assert a[1:5] != b[1:5] and sorted(a[1:5]) == sorted(b[1:5])
    assert a[1:5] == a[5:9]                  # the pool cycles
    assert keys(7, 4) == b
