"""repro.serve: bit-exact checkpointed resume, streamed JSONL traces, the
run-dir file protocol, and the service CLI.

The load-bearing guarantee is *segment parity*: running ``run_scanned(2K)``
straight equals running K rounds, checkpointing, rebuilding the federation
in a fresh object graph (standing in for a fresh process), restoring, and
running K more — record-for-record, including the float64 energy column.
"""
import json
import os

import jax
import numpy as np
import pytest

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, Federation,
                       FederationSpec, FleetSpec, TaskSpec)
from repro.api.records import (JsonlSink, read_jsonl_trace, tail_jsonl)
from repro.checkpoint import load_checkpoint
from repro.data import dirichlet_partition, make_classification
from repro.serve import (SegmentRunner, latest_resumable, restore_resumable,
                         save_resumable, truncate_jsonl_trace,
                         verify_checkpoint)
from repro.serve.chaos import run_supervised
from repro.serve.service import RunDir, service_status


def _data(n=1536, dim=48, devices=8, seed=0):
    key = jax.random.PRNGKey(seed)
    data = make_classification(key, n=n, dim=dim)
    return data, dirichlet_partition(key, data.y, devices)


def _spec(controller, seed=0):
    return FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=controller,
        execution="scanned", rounds=4, sim_seconds=1e9,
        local_batch=32, seed=seed)


CONTROLLERS = [
    ("fixed", {"a": 3}),
    ("lyapunov", {"budget": 120.0, "horizon": 40}),
    ("dqn", {"episodes": 2, "horizon": 10}),
]


# --------------------------------------------------------------------- #
# resume bit-parity (the tentpole invariant)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kind,params", CONTROLLERS,
                         ids=[k for k, _ in CONTROLLERS])
def test_resume_bit_parity(tmp_path, kind, params):
    data, parts = _data(seed=1)
    spec = _spec(ControllerSpec(kind, dict(params)), seed=1)
    K = 4

    straight = Federation.from_spec(spec, data=data, parts=parts)
    want = straight.engine.run_scanned(2 * K, eval_final=False).records

    ckpt = str(tmp_path / "ckpts")
    fed1 = Federation.from_spec(spec, data=data, parts=parts)
    first = fed1.engine.run_scanned(K, eval_final=False).records
    save_resumable(fed1, ckpt, segment=1)

    # a fresh federation stands in for a fresh process: every leaf is
    # rebuilt from the spec, then overwritten from the checkpoint
    fed2 = Federation.from_spec(spec, data=data, parts=parts)
    manifest = restore_resumable(fed2, ckpt)
    assert manifest["rounds"] == K
    assert manifest["energy"] == fed1.engine.energy_used   # exact f64
    second = fed2.engine.run_scanned(K, eval_final=False).records

    got = first + second
    assert len(got) == len(want) == 2 * K
    for a, b in zip(want, got):
        assert a == b          # dataclass eq: every float compares exact


def test_checkpoint_roundtrips_fleetstate_leaves(tmp_path):
    """Every resumable leaf — including the typed PRNG-key — survives the
    npz round-trip with dtype and bits intact."""
    data, parts = _data(seed=2)
    spec = _spec(ControllerSpec("fixed", {"a": 2}), seed=2)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    fed.engine.run_scanned(3, eval_final=False)
    save_resumable(fed, str(tmp_path), segment=1)

    like = {"fleet": fed.engine.resumable_state()["fleet"],
            "times": fed.engine.scan_times,
            "policy": fed.controller.scan_policy().state}
    path, _ = latest_resumable(str(tmp_path))
    got = load_checkpoint(path, like)

    key_a, key_b = like["fleet"].key, got["fleet"].key
    assert jax.dtypes.issubdtype(key_b.dtype, jax.dtypes.prng_key)
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(key_a)),
                                  np.asarray(jax.random.key_data(key_b)))
    for a, b in zip(jax.tree.leaves(like["fleet"])[:-1],
                    jax.tree.leaves(got["fleet"])[:-1]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(like["times"]),
                                  np.asarray(got["times"]))


def test_runner_streams_identical_trace_across_resume(tmp_path):
    """trace.jsonl of stop-and-resume equals an uninterrupted segmented
    run's, byte for byte (per-segment eval records included)."""
    data, parts = _data(seed=3)
    spec = _spec(ControllerSpec("fixed", {"a": 2}), seed=3)

    def streamed(name, ckpt, federations):
        path = str(tmp_path / name)
        for i, fed in enumerate(federations):
            fed.engine.set_trace_sink(JsonlSink(path), retain=False)
            runner = SegmentRunner(fed, ckpt, segment_rounds=3)
            if i:
                runner.maybe_resume()
            runner.run_segment()
            fed.engine.trace_sink.close()
        return path

    a = streamed("a.jsonl", str(tmp_path / "ca"), [
        Federation.from_spec(spec, data=data, parts=parts)] * 2)
    b = streamed("b.jsonl", str(tmp_path / "cb"), [
        Federation.from_spec(spec, data=data, parts=parts),
        Federation.from_spec(spec, data=data, parts=parts)])
    with open(a) as fa, open(b) as fb:
        assert fa.read() == fb.read()
    trace = read_jsonl_trace(b)
    assert trace.n_records == 8            # 2 * (3 rounds + 1 eval)
    assert trace.records[-1].acc is not None


def test_retention_prunes_old_checkpoints(tmp_path):
    data, parts = _data(seed=4)
    spec = _spec(ControllerSpec("fixed", {"a": 1}), seed=4)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    runner = SegmentRunner(fed, str(tmp_path), segment_rounds=2, keep=2)
    for _ in range(4):
        runner.run_segment()
    files = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert files == ["ckpt_00000006.npz", "ckpt_00000008.npz"]
    assert latest_resumable(str(tmp_path))[1]["rounds"] == 8


def test_incomplete_checkpoint_is_skipped(tmp_path):
    """An npz without its manifest (crash between the two writes) must not
    be chosen for resume."""
    data, parts = _data(seed=5)
    spec = _spec(ControllerSpec("fixed", {"a": 1}), seed=5)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    runner = SegmentRunner(fed, str(tmp_path), segment_rounds=2)
    runner.run_segment()
    complete, _ = latest_resumable(str(tmp_path))
    with open(tmp_path / "ckpt_00000099.npz", "wb") as f:
        f.write(b"not a real checkpoint")    # no .json sidecar
    assert latest_resumable(str(tmp_path))[0] == complete


def test_corrupt_checkpoint_falls_back_to_verified(tmp_path):
    """A truncated npz (torn write / bit rot) fails its manifest CRC and
    resume silently falls back to the previous verified checkpoint."""
    data, parts = _data(seed=6)
    spec = _spec(ControllerSpec("fixed", {"a": 1}), seed=6)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    runner = SegmentRunner(fed, str(tmp_path), segment_rounds=2, keep=None)
    runner.run_segment()
    good, good_manifest = latest_resumable(str(tmp_path))
    runner.run_segment()
    newest, _ = latest_resumable(str(tmp_path))
    assert newest != good and verify_checkpoint(newest)

    # truncate the newest npz: manifest intact, bytes no longer match
    with open(newest, "r+b") as f:
        f.truncate(os.path.getsize(newest) // 2)
    assert not verify_checkpoint(newest)
    path, manifest = latest_resumable(str(tmp_path))
    assert path == good and manifest == good_manifest

    # restore actually loads the fallback (round counter proves which)
    fed2 = Federation.from_spec(spec, data=data, parts=parts)
    assert restore_resumable(fed2, str(tmp_path))["rounds"] == 2

    # pruning deletes the corrupt newest outright, keeps the verified one
    from repro.serve import prune_checkpoints
    prune_checkpoints(str(tmp_path), keep=2)
    assert not os.path.exists(newest)
    assert os.path.exists(good)


def test_legacy_manifest_without_digest_still_verifies(tmp_path):
    """Pre-digest manifests (no crc32 field) verify by existence, so old
    run dirs remain resumable."""
    data, parts = _data(seed=7)
    spec = _spec(ControllerSpec("fixed", {"a": 1}), seed=7)
    fed = Federation.from_spec(spec, data=data, parts=parts)
    fed.engine.run_scanned(2, eval_final=False)
    npz = save_resumable(fed, str(tmp_path), segment=1)
    mpath = npz[:-len(".npz")] + ".json"
    manifest = json.load(open(mpath))
    for k in ("crc32", "bytes"):
        manifest.pop(k)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    assert verify_checkpoint(npz)
    assert latest_resumable(str(tmp_path))[0] == npz


def test_stale_pidfile_is_cleaned(tmp_path):
    """A SIGKILLed daemon leaves its pidfile; running_pid must treat the
    dead pid as not-running AND remove the stale file."""
    rd = RunDir(str(tmp_path)).ensure()
    with open(rd.path("serve.pid"), "w") as f:
        f.write("999999999")            # beyond pid_max: never alive
    assert rd.running_pid() is None
    assert not os.path.exists(rd.path("serve.pid"))


# --------------------------------------------------------------------- #
# JSONL plumbing
# --------------------------------------------------------------------- #
def test_truncate_jsonl_trace(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        for r in range(1, 7):
            f.write(json.dumps({"round": r, "loss": r * 0.5}) + "\n")
        f.write('{"round": 7, "los')           # torn tail from a crash
    assert truncate_jsonl_trace(path, 4) == 3  # rounds 5, 6 + torn line
    kept = [json.loads(l) for l in open(path)]
    assert [r["round"] for r in kept] == [1, 2, 3, 4]
    assert truncate_jsonl_trace(str(tmp_path / "missing.jsonl"), 4) == 0


def test_tail_jsonl_reads_only_the_tail(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        for r in range(200):
            f.write(json.dumps({"round": r}) + "\n")
    assert [d["round"] for d in tail_jsonl(path, n=5, block=64)] \
        == [195, 196, 197, 198, 199]
    assert tail_jsonl(str(tmp_path / "missing.jsonl")) == []


# --------------------------------------------------------------------- #
# the service CLI (in-process, --foreground)
# --------------------------------------------------------------------- #
def _tiny_spec_file(tmp_path):
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 2}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("autoencoder-anomaly",
                      {"n_samples": 512, "dim": 16, "n_types": 4,
                       "latent": 2, "hidden": 16, "code": 4,
                       "dirichlet_alpha": 5.0}),
        execution="scanned", rounds=3, sim_seconds=1e9,
        local_batch=16, lr=0.1, seed=11)
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec.to_dict()))
    return str(path)


def test_service_cli_lifecycle(tmp_path, capsys):
    from repro.serve.__main__ import main
    run_dir = str(tmp_path / "run")
    spec_file = _tiny_spec_file(tmp_path)

    assert main(["start", "--run-dir", run_dir, "--spec-file", spec_file,
                 "--segment-rounds", "3", "--max-segments", "2",
                 "--foreground"]) == 0
    st = service_status(run_dir)
    assert not st["alive"]
    assert st["state"]["status"] == "stopped"
    assert st["state"]["rounds"] == 6
    assert st["latest_checkpoint"].endswith("ckpt_00000006.npz")

    # stopped service: `checkpoint` locates the newest checkpoint
    capsys.readouterr()
    assert main(["checkpoint", "--run-dir", run_dir]) == 0
    assert capsys.readouterr().out.strip() == st["latest_checkpoint"]

    # `start` refuses a run dir that already has checkpoints...
    assert main(["start", "--run-dir", run_dir, "--spec-file", spec_file,
                 "--foreground"]) == 1
    # ...and `resume` continues it (one more segment)
    assert main(["resume", "--run-dir", run_dir, "--segment-rounds", "3",
                 "--max-segments", "1", "--foreground"]) == 0
    st = service_status(run_dir)
    assert st["state"]["rounds"] == 9
    trace = read_jsonl_trace(os.path.join(run_dir, "trace.jsonl"))
    assert trace.n_records == 12          # 3 segments * (3 rounds + eval)
    assert [r.round for r in trace.records if r.acc is None] \
        == list(range(1, 10))

    # `stop` on a stopped service is a clean no-op
    assert main(["stop", "--run-dir", run_dir]) == 0
    # `resume` on an empty dir is a config error, not a traceback
    assert main(["resume", "--run-dir", str(tmp_path / "empty"),
                 "--foreground"]) == 1


# --------------------------------------------------------------------- #
# chaos: SIGKILL mid-segment, supervised recovery
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kind,params", CONTROLLERS,
                         ids=[k for k, _ in CONTROLLERS])
def test_chaos_sigkill_recovery_trace_parity(tmp_path, monkeypatch,
                                             kind, params):
    """SIGKILL the service after a checkpoint lands (next segment in
    flight), let the supervisor restart it, and byte-compare the final
    trace.jsonl against an uninterrupted run of the same spec: recovery
    must be invisible in the output, for every controller."""
    import repro.serve
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(repro.serve.__file__))))
    monkeypatch.setenv(
        "PYTHONPATH",
        src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    from repro.serve.__main__ import main

    spec = _spec(ControllerSpec(kind, dict(params)), seed=13)
    spec_file = str(tmp_path / "spec.json")
    with open(spec_file, "w") as f:
        json.dump(spec.to_dict(), f)

    # uninterrupted reference, in-process
    ref = str(tmp_path / "ref")
    assert main(["start", "--run-dir", ref, "--spec-file", spec_file,
                 "--segment-rounds", "2", "--max-segments", "3",
                 "--keep", "0", "--foreground"]) == 0

    # chaos run: subprocess children under the supervisor, one SIGKILL
    chaos = str(tmp_path / "chaos")
    summary = run_supervised(
        chaos, total_segments=3, segment_rounds=2, kills=1, keep=0,
        spec_file=spec_file, log=lambda *a, **k: None)
    assert summary["segments"] == 3
    assert summary["kills"] == 1
    assert summary["restarts"] >= 1

    with open(os.path.join(ref, "trace.jsonl")) as fa, \
            open(os.path.join(chaos, "trace.jsonl")) as fb:
        assert fa.read() == fb.read()
    st = service_status(chaos)
    assert not st["alive"]
    assert st["checkpoint_manifest"]["rounds"] == 6


# --------------------------------------------------------------------- #
# parents that spawn a service child leave the accelerator to the child
# --------------------------------------------------------------------- #
_PARENT_SUBPROC = r"""
import sys
from jax._src import xla_bridge
import repro.api, repro.kernels, repro.pop, repro.serve
assert not xla_bridge.backends_are_initialized(), "backend started on import"
from repro.serve import __main__ as cli, chaos


class _Exited:                          # a child that died at once
    pid, returncode = 0, 1
    poll = wait = lambda self: 1


cli._spawn = lambda rd, argv: 0
chaos.spawn_service = lambda *a, **k: _Exited()
root = sys.argv[1]
assert cli.main(["start", "--run-dir", root + "/svc"]) == 0
assert cli.main(["pool", "start", "--run-dir", root + "/pool",
                 "--replicates", "2"]) == 0
assert cli.main(["chaos", "--run-dir", root + "/chaos",
                 "--total-segments", "1", "--kills", "0",
                 "--max-restarts", "0"]) == 1
print("BACKEND", xla_bridge.backends_are_initialized())
"""


def test_spawning_parents_start_no_jax_backend(tmp_path):
    """A chip belongs to one process: `serve start`, `pool start` and
    `chaos` re-exec a child to run the federation, so neither importing
    `repro` nor the parent's own work may start a JAX backend."""
    import subprocess
    import sys
    import repro.serve
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(repro.serve.__file__))))
    env = dict(os.environ, PYTHONPATH=src + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-c", _PARENT_SUBPROC, str(tmp_path)], env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "BACKEND False" in out.stdout


def test_rundir_pid_and_requests(tmp_path):
    rd = RunDir(str(tmp_path)).ensure()
    assert rd.running_pid() is None
    rd.write_pid()
    assert rd.running_pid() == os.getpid()    # we are alive
    rd.clear_pid()
    assert rd.running_pid() is None
    assert not rd.take_request("stop.req")
    rd.request("stop.req")
    assert rd.take_request("stop.req")
    assert not rd.take_request("stop.req")    # consumed
