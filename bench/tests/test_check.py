"""`correct` comes out false when the timed path is broken underneath it
(the faults a one-chip training cell can have), and for the bfloat16
control in the program's place; the run itself is driven as on the chip,
minus the look for one."""
import jax
import jax.numpy as jnp
import pytest

from bench import check
from bench import harness as h
from bench import run as bench_run
from bench.tests.tiny import FIXED, tiny_config
from repro.api.components import MLPTask
from repro.api.engine import DeviceScaleEngine

WORKLOAD = "paper-v.scan"


def _frozen(monkeypatch):
    """A round that returns the fleet's state unchanged."""
    orig = DeviceScaleEngine._fleet_round

    def round_(self, state, c, a, members, mask):
        new, m = orig(self, state, c, a, members, mask)
        return new._replace(twins=state.twins, rep=state.rep,
                            cluster_params=state.cluster_params,
                            global_params=state.global_params), m
    monkeypatch.setattr(DeviceScaleEngine, "_fleet_round", round_)


def _half_batch(monkeypatch):
    """Local training on half of each member's batch."""
    orig = MLPTask.local_train

    def local_train(self, stacked, batch, lr, steps):
        half = batch["x"].shape[1] // 2
        return orig(self, stacked, {"x": batch["x"][:, :half],
                                    "y": batch["y"][:, :half]}, lr, steps)
    monkeypatch.setattr(MLPTask, "local_train", local_train)


def _altered_loss(monkeypatch):
    """The round's reported loss altered where it is produced."""
    orig = DeviceScaleEngine._fleet_round

    def round_(self, state, c, a, members, mask):
        new, m = orig(self, state, c, a, members, mask)
        return new, dict(m, loss=m["loss"] * 1.5)
    monkeypatch.setattr(DeviceScaleEngine, "_fleet_round", round_)


@pytest.mark.parametrize("fault", [None, _frozen, _half_batch,
                                   _altered_loss])
@pytest.mark.parametrize("workload", [w["name"] for w in
                                      h.benchmark()["workloads"]])
def test_a_broken_round_is_not_correct(workload, fault, monkeypatch,
                                       tmp_path):
    if fault is not None:
        fault(monkeypatch)
    cfg = tiny_config(h.cell(workload)["config"], FIXED)
    out = bench_run.run_cell(workload, 2 ** 32 + 3, 0.3, False,
                             cfg=cfg, devices=jax.devices(),
                             work_dir=str(tmp_path))
    assert out["correct"] is (fault is None), out["checks"]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11, 2 ** 40 + 1])
def test_the_bf16_control_is_not_correct(seed):
    cfg = tiny_config("paper-v", FIXED)
    spec = h.spec_dict(cfg, h.traffic(h.cell(WORKLOAD)["traffic"]))
    data, parts = h.bench_data.build(cfg, cfg["deploy_seed"])
    key = h.episode_key(seed, 0)
    dims = h.dims(cfg)
    first, assign = check.control_first(spec, dims, data, parts, 10, "f32",
                                        jnp.bfloat16, key)
    ref = check.reference_for(first, spec, dims, data, parts, assign, key)
    ok, rows = check.judge(check.numbers(first, ref, assign),
                           h.limits(WORKLOAD))
    assert not ok, rows


def _untrained(monkeypatch):
    """The controller's Alg.-1 training returns the network it was given."""
    from repro.api import components
    monkeypatch.setattr(components, "train_on_env",
                        lambda key, agent, cfg, p, **kw: (agent, {}))


def _next_action(monkeypatch):
    """The deployed policy picks the action after its greedy one."""
    from repro.api.components import DQNController
    from repro.control import policy as ctl_policy
    orig = DQNController.scan_policy

    def scan_policy(self):
        pol = orig(self)

        def step(state, obs):
            a, state = pol.step(state, obs)
            return a % self.n_actions + 1, state
        return ctl_policy.ScanPolicy(state=pol.state, step=step,
                                     needs_obs=pol.needs_obs)
    monkeypatch.setattr(DQNController, "scan_policy", scan_policy)


@pytest.mark.parametrize("fault", [None, _untrained, _next_action])
def test_a_broken_controller_is_not_correct(fault, monkeypatch, tmp_path):
    """paper-v's own DQN controller at a tiny fleet: the reference trains
    its own network (Alg. 1) and holds the deployed one and its actions
    to it."""
    if fault is not None:
        fault(monkeypatch)
    out = bench_run.run_cell(WORKLOAD, 2 ** 33 + 5, 0.3, False,
                             cfg=tiny_config("paper-v"),
                             devices=jax.devices(), work_dir=str(tmp_path))
    assert out["correct"] is (fault is None), out["checks"]
    if fault is None:
        assert out["checks"]["qnet_gap"]["value"] < 1e-4
