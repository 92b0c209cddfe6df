"""Whether the timed path's output is correct: the program's first steps
against the plain reference (`bench.reference`).

The numbers compared, each against its own limit in
``limits/<workload>.json`` (PERF.md gives the readings each was set from):

  cluster_mismatch  devices the program clustered differently from the
                    reference's own k-means
  schedule_gap      how much later than the earliest pending event the
                    program's scheduled cluster lay (relative)
  action_gap        the reference's Q-value of the program's action below
                    its best (DQN, the reference's own Alg.-1 network), or
                    1 for a fixed action not taken
  qnet_gap          (DQN) the program's deployed Q-network against the
                    reference's, at the observations of the first steps:
                    the largest gap of a Q-value over the reference's
                    largest change of one in training (an untrained
                    network reads 1)
  loss_rel          per-round mean member training loss, relative gap
  energy_rel        per-round energy (Eqns 7-8), relative gap
  rep_rel           reputations (Eqns 4-5) after the steps, relative to
                    the reference's largest change
  param_gap         gap of the norms of the global model's change, worst
                    leaf, against the larger of that leaf's and the median
                    leaf's change in the reference
  channel_mismatch  devices whose channel state differs
"""
from __future__ import annotations

import numpy as np

from bench import reference


def reference_for(first: dict, spec: dict, dims: dict, data, parts,
                  assign, key):
    """The reference, on the run's stream ``key``, following the program's
    decisions."""
    rows = first["rounds"]
    force = {"assign": assign, "cluster": [r["cluster"] for r in rows],
             "a": [r["a"] for r in rows]}
    return reference.simulate(spec, dims, data, parts, len(rows),
                              force=force, clock=first["clock"], key=key)


def control_first(spec: dict, dims: dict, data, parts, rounds: int,
                  clock: str, dtype, key, fault=None) -> tuple:
    """The reference in ``dtype`` (with a planted ``fault``), deciding for
    itself, in the program's place: -> (first steps as the program reports
    them, its assignment)."""
    sim = reference.simulate(spec, dims, data, parts, rounds, clock=clock,
                             dtype=dtype, fault=fault, key=key)
    fl = sim["fleet"]
    state = {"global": {k: np.asarray(v, np.float32)
                        for k, v in fl.global_params.items()},
             "rep": np.asarray(fl.rep), "queue": float(fl.queue),
             "energy": np.asarray(fl.energy),
             "channel": np.asarray(fl.channel)}
    return ({"rounds": sim["records"], "state": state, "clock": clock,
             "qnet": sim["qnet"]}, sim["assign"])


def _rel(p, r) -> float:
    return float(abs(p - r) / max(abs(r), 1e-12))


def _q(net, obs):
    h = np.maximum(obs @ net["w1"] + net["b1"], 0.0)
    h = np.maximum(h @ net["w2"] + net["b2"], 0.0)
    return h @ net["w3"] + net["b3"]


def qnet_gap(prog, ref: dict) -> float:
    """The program's Q-network against the reference's (float64, at the
    observations the reference scored): the largest gap of a Q-value over
    the largest change training made to one."""
    obs = np.stack(ref["obs"])
    f64 = lambda n: {k: np.asarray(v, np.float64)        # noqa: E731
                     for k, v in n.items()}
    q_ref = _q(f64(ref["qnet"]), obs)
    moved = np.max(np.abs(q_ref - _q(f64(ref["qnet0"]), obs)))
    return float(np.max(np.abs(_q(f64(prog), obs) - q_ref))
                 / max(moved, 1e-12))


def numbers(first: dict, ref: dict, assign) -> dict:
    """Compare the program's first steps with the reference's."""
    pr, rr = first["rounds"], ref["records"]
    out = {
        "cluster_mismatch": float(np.sum(np.asarray(assign)
                                         != ref["assign"])),
        "schedule_gap": ref["schedule_gap"],
        "action_gap": ref["action_gap"],
        "loss_rel": max(_rel(p["loss"], r["loss"]) for p, r in zip(pr, rr)),
        "energy_rel": max(_rel(p["consumed"], r["consumed"])
                          for p, r in zip(pr, rr)),
    }
    st, fl = first["state"], ref["fleet"]
    rep_r = np.asarray(fl.rep, np.float64)
    out["rep_rel"] = float(np.max(np.abs(st["rep"] - rep_r))
                           / max(np.max(np.abs(rep_r - 1.0)), 1e-12))
    out["channel_mismatch"] = float(np.sum(st["channel"]
                                           != np.asarray(fl.channel)))
    g0 = {k: np.asarray(v, np.float64) for k, v in
          ref["init_global"].items()}
    d_ref = {k: float(np.linalg.norm(np.asarray(fl.global_params[k],
                                                np.float64) - g0[k]))
             for k in g0}
    d_prog = {k: float(np.linalg.norm(st["global"][k].astype(np.float64)
                                      - g0[k])) for k in g0}
    med = float(np.median(list(d_ref.values())))
    # leaves the reference leaves still (under a thousandth of the median
    # leaf's change) move by round-off alone and are not compared
    kept = [k for k in d_ref if d_ref[k] >= 1e-3 * med]
    out["param_gap"] = max(abs(d_prog[k] - d_ref[k]) / max(d_ref[k], med)
                           for k in kept)
    # no network on either side: nothing differs; on one side only: all
    prog_q = first.get("qnet")
    if ref["qnet"] is None or prog_q is None:
        out["qnet_gap"] = 0.0 if ref["qnet"] is prog_q else np.inf
    else:
        out["qnet_gap"] = qnet_gap(prog_q, ref)
    return out


def judge(values: dict, limits: dict) -> tuple:
    """-> (correct, [(name, value, limit)]) over the numbers the limits
    file names; a number that is missing or not finite fails."""
    rows, ok = [], True
    for name, lim in limits["numbers"].items():
        v = values.get(name)
        good = v is not None and np.isfinite(v) and v <= lim["limit"]
        ok &= bool(good)
        rows.append((name, float("nan") if v is None else float(v),
                     float(lim["limit"])))
    return ok, rows
