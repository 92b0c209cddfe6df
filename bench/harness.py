"""What every cell shares: finding its files by name, building the
deployment through the program's public entry point, and the episode
snapshot.

A cell (`BENCHMARK.json` ``workloads``) names a configuration and a traffic
mix.  ``configs/<config>.json`` holds the deployment (the `FederationSpec`
fields, the data it trains on, the accuracy target); ``traffic/<mix>.json``
names a driver in ``drivers/`` and its parameters.  Nothing here knows any
cell by name, so a new configuration, mix or driver is a new file.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Federation, FederationSpec
from repro.obs import EngineObs

from bench import data as bench_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
WORK = os.path.join(ROOT, ".bench_work")


def enable_cache() -> None:
    """The program's persistent compilation cache (``.jax_cache`` in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program the
    run compiles, however small: the set-up's and the reference's many
    small programs too, so that only a checkout's first run compiles."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: dict = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json("configs", f"{name}.json")


def traffic(name: str) -> dict:
    return load_json("traffic", f"{name}.json")


def driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def limits(workload: str) -> dict:
    return load_json("limits", f"{workload}.json")


def merge(base: dict, over: dict) -> dict:
    """Recursive dict update (traffic overrides of the spec)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def spec_dict(cfg: dict, mix: dict) -> dict:
    """The cell's `FederationSpec` as a dict.  Its seed is the
    configuration's ``deploy_seed``: the fleet, its data and the initial
    model are the deployment's, the same in every run."""
    return merge(merge(cfg["spec"], mix.get("spec", {})),
                 {"seed": cfg["deploy_seed"]})


def episode_key(seed: int, episode: int):
    """The random stream of one episode: ``seed`` (folded into the 31 bits
    a key holds) and the episode's number.  It drives every random draw of
    the rounds -- batches, channels, energy noise."""
    return jax.random.fold_in(jax.random.key(int(seed) % (2 ** 31 - 1)),
                              episode)


STREAMS = 12345                 # the seed of the window's stream pool


def stream_key(run: "Run", episode: int):
    """Episode 0, the check's first steps, runs on ``--seed``'s own
    stream.  The window's episodes cycle through a fixed pool of the
    traffic's ``streams`` streams, in an order drawn from ``--seed``: every
    seed does the same work in another order."""
    if episode == 0:
        return episode_key(run.seed, 0)
    n = run.mix["streams"]
    order = np.random.default_rng(abs(int(run.seed))).permutation(n)
    return episode_key(STREAMS, int(order[(episode - 1) % n]))


def dims(cfg: dict) -> dict:
    p = cfg["spec"]["task"]["params"]
    return {"dim": cfg["data"]["dim"], "hidden": p["hidden"],
            "n_classes": p["n_classes"]}


class Clock:
    """Seconds since ``t0`` (by default, since it was made)."""

    def __init__(self, t0: float = None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


@dataclasses.dataclass
class Run:
    """One cell's run: the deployment and what the window reports."""
    workload: str
    seed: int
    cfg: dict
    mix: dict
    spec: dict
    episode: int = 0
    data: object = None
    parts: list = None
    fed: object = None
    obs: object = None
    snapshot: dict = None
    policy: object = None
    work_dir: str = ""
    first: dict = None          # the program's first steps, for `correct`
    drv: dict = dataclasses.field(default_factory=dict)   # driver-owned

    @property
    def engine(self):
        return self.fed.engine


def build(run: Run) -> None:
    """The deployment's data, then ``Federation.from_spec`` on it, with an
    obs bundle attached so that the engine times its compiles."""
    run.data, run.parts = bench_data.build(run.cfg, run.cfg["deploy_seed"])
    spec = FederationSpec.from_dict(run.spec)
    run.fed = Federation.from_spec(spec, data=run.data, parts=run.parts)
    run.obs = EngineObs()
    run.engine.set_obs(run.obs)


def take_snapshot(run: Run) -> None:
    """The set-up state every episode starts from (copied, because the
    engine donates its state to each round)."""
    run.snapshot = jax.tree.map(jnp.copy, run.engine.resumable_state())
    run.policy = run.fed.controller.scan_policy().state


def restore(run: Run, episode: int) -> None:
    """The set-up state, on the random stream of ``episode``."""
    tree = jax.tree.map(jnp.copy, run.snapshot)
    tree["fleet"] = tree["fleet"]._replace(key=stream_key(run, episode))
    run.engine.restore_resumable(tree, rounds=0, energy=0.0)
    restore_policy = getattr(run.fed.controller, "restore_policy_state",
                             None)
    if restore_policy is not None:
        restore_policy(run.policy)


def first_of(rows, run: Run, clock: str) -> dict:
    """The first steps as the check reads them, from the round records of
    a scanned trace (energies are cumulative there)."""
    energies = [0.0] + [r.energy for r in rows]
    return {"rounds": [{"t": r.t, "cluster": r.cluster, "a": r.a,
                        "loss": r.loss, "consumed": e1 - e0}
                       for r, e0, e1 in zip(rows, energies, energies[1:])],
            "state": host_state(run), "clock": clock, "qnet": qnet(run)}


def host_state(run: Run) -> dict:
    """The program's state after its first steps, on the host."""
    st = run.engine.state
    return {"global": {k: np.asarray(v) for k, v in
                       jax.device_get(st.global_params).items()},
            "rep": np.asarray(st.rep), "queue": float(st.queue),
            "energy": np.asarray(st.twins.energy),
            "channel": np.asarray(st.channel)}


def real_members(run: Run):
    return np.bincount(run.engine.assign,
                       minlength=run.spec["clustering"]["n_clusters"])


def qnet(run: Run):
    """The Q-network the window's episodes deploy (the scan policy's
    carry), when the controller is a DQN."""
    if getattr(run.fed.controller, "agent", None) is None:
        return None
    return {k: np.asarray(v) for k, v in jax.device_get(run.policy).items()}


def fresh_work_dir(run: Run) -> str:
    """An empty ``run.work_dir`` (by default ``.bench_work/<cell>``)."""
    path = run.work_dir or os.path.join(WORK, run.workload)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    run.work_dir = path
    return path



def new_window() -> dict:
    """What a window reports (host clock, seconds)."""
    return {"window_s": 0.0, "segments_s": [], "rounds": [], "evals": 0,
            "attempted": 0, "failed": 0, "reached_s": []}


def samples(run: Run, rounds) -> int:
    """Local-SGD samples of ``rounds`` [(cluster, a)]: a x real members x
    local batch, each round."""
    m = real_members(run)
    return int(sum(a * int(m[c]) for c, a in rounds)
               * run.spec["local_batch"])
