"""Plain reference of the fleet round (paper §III-§IV, Eqns 1-8, 12, 19)
and of the controller's training (Alg. 1).

Written from the paper's equations as the program states them, in
straightforward ``jax.numpy`` over the real members of each cluster: no
padding, no kernels, no fused scan, float32 at ``highest`` matmul
precision.  It imports nothing of the program and takes nothing the
program made: its inputs are the benchmark's own (the seeded data and
shards), and the DQN controller's Q-network is its own, trained by
`dqn_pretrain` from the controller's seed on the twin-simulated
environment of §IV-C.

`simulate` follows a run round by round.  Given the program's decisions
(``force``: the scheduled cluster and the bounded local-step count of each
round) it follows them and scores each against its own: a schedule that
lies later than the reference's earliest pending event by
``schedule_gap``, and an action is scored by how far
the reference's Q-value of the best action consistent with the program's
bounded ``a`` lies below its own best (``action_gap``) -- the served-token
rule, applied to the controller.  Without ``force`` it decides itself; run
in bfloat16 that way it is the control that stands in the program's place.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-8
NOISE_MEAN_DB = (0.1, 0.3, 0.5)     # channel states good / medium / bad
BANDWIDTH, N_SUB, TX_POWER, GAIN = 1e5, 8, 0.2, 1.0
MODEL_BITS, N_COM, N_CMP, TRAIN_CYCLES = 8e6, 1.0, 1.0, 1.0
LOSS_MAX = 2.3                       # DQN observation's loss ceiling
OBS_DIM = 48


class Fleet(NamedTuple):
    """The reference's fleet state; float leaves are host or device
    arrays, ``members`` is a list of device-id arrays per cluster."""
    freq: jnp.ndarray
    freq_dev: jnp.ndarray
    dev_est: jnp.ndarray
    loss: jnp.ndarray
    energy: jnp.ndarray
    data_size: jnp.ndarray
    alpha: jnp.ndarray
    beta: jnp.ndarray
    rep: jnp.ndarray
    channel: jnp.ndarray
    cluster_params: list             # one MLP dict per cluster
    global_params: dict
    cluster_ts: jnp.ndarray
    queue: float
    round: int
    key: jnp.ndarray


# --------------------------------------------------------------------- #
# the model: an MLP classifier (784-hidden-classes, ReLU, softmax CE)
# --------------------------------------------------------------------- #
def mlp_init(key, dim, hidden, n_classes):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (dim, hidden)) / jnp.sqrt(dim),
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, n_classes))
            / jnp.sqrt(hidden),
            "b2": jnp.zeros((n_classes,))}


def mlp_loss(p, x, y):
    h = jnp.maximum(x @ p["w1"] + p["b1"], 0.0)
    logits = (h @ p["w2"] + p["b2"]).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


@jax.jit
def _local_sgd(p, x, y, lr, steps):
    """``steps`` plain SGD steps of every member from its start params."""
    def one_member(p, x, y):
        def step(_, q):
            g = jax.grad(mlp_loss)(q, x, y)
            return jax.tree.map(lambda a, b: (a - lr * b).astype(a.dtype),
                                q, g)
        q = jax.lax.fori_loop(0, steps, step, p)
        return q, mlp_loss(q, x, y)
    return jax.vmap(one_member)(p, x, y)


# --------------------------------------------------------------------- #
# fleet set-up: twins (Eqns 1-2), k-means clusters, initial models
# --------------------------------------------------------------------- #
def _kmeans(key, feats, k, iters=25):
    n = feats.shape[0]
    cent = feats[jax.random.choice(key, n, (k,), replace=False)]
    for _ in range(iters):
        d2 = jnp.sum((feats[:, None] - cent[None]) ** 2, axis=-1)
        oh = jax.nn.one_hot(jnp.argmin(d2, axis=1), k)
        cnt = oh.sum(0)[:, None]
        cent = jnp.where(cnt > 0, (oh.T @ feats) / jnp.maximum(cnt, 1.0),
                         cent)
    d2 = jnp.sum((feats[:, None] - cent[None]) ** 2, axis=-1)
    return np.asarray(jnp.argmin(d2, axis=1))


def _fill_empty(assign, k):
    """An empty cluster takes the first device of the largest one."""
    assign = assign.copy()
    counts = np.bincount(assign, minlength=k)
    for c in range(k):
        if counts[c] == 0:
            donor = int(counts.argmax())
            i = int(np.where(assign == donor)[0][0])
            assign[i], counts[donor], counts[c] = c, counts[donor] - 1, 1
    return assign


def init_fleet(spec: dict, dims: dict, parts, assign=None, key=None):
    """The seeded fleet at round 0 -> (Fleet, members, own assignment).

    The reference clusters the fleet itself (k-means at the matmul
    precision the configuration states, the chip's default); with
    ``assign`` it then follows that assignment instead.  ``key`` is the
    rounds' random stream (default: the one the spec's seed gives)."""
    n = spec["fleet"]["n_devices"]
    k = spec["clustering"]["n_clusters"]
    key0, kt, kd, kc, kp, _ = jax.random.split(
        jax.random.key(spec["seed"]), 6)
    kf, _ = jax.random.split(kt)
    freq = jax.random.uniform(kf, (n,), minval=0.5, maxval=2.0)
    dev = jax.random.uniform(kd, (n,), minval=0.0,
                             maxval=spec["fleet"]["dt_max_dev"]) * freq
    sizes = jnp.asarray([len(p) for p in parts], jnp.float32)
    feats = jnp.stack([sizes, freq], axis=1)
    feats = (feats - feats.mean(0)) / (feats.std(0) + 1e-8)
    with jax.default_matmul_precision("default"):
        own = _fill_empty(_kmeans(kc, feats, k), k)
    use = own if assign is None else np.asarray(assign)
    members = [np.where(use == c)[0] for c in range(k)]
    gp = mlp_init(kp, dims["dim"], dims["hidden"], dims["n_classes"])
    z = jnp.zeros((n,), jnp.float32)
    fleet = Fleet(freq=freq, freq_dev=dev, dev_est=z, loss=z + jnp.inf,
                  energy=z, data_size=sizes, alpha=z + 1.0, beta=z,
                  rep=z + 1.0, channel=jnp.zeros((n,), jnp.int32),
                  cluster_params=[gp] * k, global_params=gp,
                  cluster_ts=jnp.zeros((k,), jnp.float32), queue=0.0,
                  round=0, key=key0 if key is None else key)
    return fleet, members, own


# --------------------------------------------------------------------- #
# the controller's view (paper §IV-B) and the Alg.-2 bound
# --------------------------------------------------------------------- #
def _cluster_freqs(fleet, members):
    f = np.asarray(fleet.freq + fleet.dev_est)
    return np.asarray([f[m].min() for m in members], np.float32)


def _bounded(a_req, f_c, f_max, round_, clustering, n_actions,
             slack=0.0):
    """Alg. 2: cap a so that a/f_c <= alpha * T_m, T_m = a_req / f_max.
    ``slack`` scales the product before its floor: the cap lands on
    whole numbers, where two correct divisions may floor apart."""
    a_req = np.clip(np.asarray(a_req), 1, n_actions).astype(np.float32)
    t_ref = a_req / np.float32(max(f_max, 1e-6))
    alpha = np.float32(min(1.0, np.float32(clustering["alpha0"])
                           + np.float32(clustering["alpha_growth"])
                           * np.float32(round_)))
    t_local = a_req / np.float32(max(f_c, 1e-6))
    cap = np.floor(alpha * t_ref * np.float32(f_c)
                   * np.float32(1.0 + slack)).astype(np.int64)
    a = np.where(t_local > alpha * t_ref, np.maximum(cap, 1),
                 a_req.astype(np.int64))
    return np.clip(a, 1, n_actions)


def _q_values(qnet, obs):
    h = jnp.maximum(obs @ qnet["w1"] + qnet["b1"], 0.0)
    h = jnp.maximum(h @ qnet["w2"] + qnet["b2"], 0.0)
    return h @ qnet["w3"] + qnet["b3"]


def _dqn_obs(fleet, members, c, x256):
    m = members[c]
    loss = jnp.nan_to_num(jnp.mean(fleet.loss[m]), nan=0.0, posinf=LOSS_MAX)
    f = (fleet.freq + fleet.dev_est)[m]
    p = fleet.cluster_params[c]
    tau = jnp.mean(jnp.maximum(x256 @ p["w1"] + p["b1"], 0.0))
    ch3 = jax.nn.one_hot(fleet.channel, 3).mean(0)
    feats = jnp.concatenate([
        jnp.stack([loss, LOSS_MAX - loss, jnp.float32(fleet.queue),
                   jnp.float32(fleet.round / 100.0),
                   tau.astype(jnp.float32)]),
        jax.nn.one_hot(min(fleet.round % 10, 9), 10), ch3,
        jnp.stack([jnp.mean(f), jnp.float32(0.0), jnp.float32(0.0)])])
    return jnp.pad(feats, (0, OBS_DIM - feats.shape[0]))


# --------------------------------------------------------------------- #
# Alg. 1: the DQN trained on the twin-simulated environment (§IV-C)
# --------------------------------------------------------------------- #
# the controller's settings where the configuration leaves them to the
# repo's defaults: the agent (48-200-200-10 net, replay, epsilon-greedy
# with growing greed, target sync) and the environment it trains on
ALG1 = {"seed": 0, "episodes": 4, "horizon": 25, "p_good": 0.5,
        "buffer_size": 512, "batch_size": 32, "lr": 2e-3}
DQN_HIDDEN, N_ACTIONS, GAMMA, TARGET_SYNC = 200, 10, 0.9, 50
EPS0, EPS_GROWTH, CLIP = 0.1, 1e-3, 5.0
ENV = {"n_devices": 16, "budget": 250.0, "kappa": 0.08, "f_star": 0.1,
       "f0": 2.3, "v0": 1.0, "v_growth": 0.02, "noise": 0.01,
       "reward_scale": 0.02}


def _qnet_init(key):
    k1, k2, k3 = jax.random.split(jax.random.split(key)[0], 3)
    h = DQN_HIDDEN
    return {"w1": jax.random.normal(k1, (OBS_DIM, h)) * (1.0 / jnp.sqrt(
                OBS_DIM)),
            "b1": jnp.zeros((h,)),
            "w2": jax.random.normal(k2, (h, h)) * (1.0 / jnp.sqrt(h)),
            "b2": jnp.zeros((h,)),
            "w3": jax.random.normal(k3, (h, N_ACTIONS)) * (1.0 / jnp.sqrt(h)),
            "b3": jnp.zeros((N_ACTIONS,))}


def _next_channel(key, n, p_good):
    rest = (1.0 - p_good) / 2.0
    row = jnp.array([p_good, rest, rest])
    return jax.random.categorical(
        key, jnp.log(row[None, :] + 1e-12) + jnp.zeros((n, 3)), axis=-1)


def _env_reset(key, p_good):
    n = ENV["n_devices"]
    kt, kd, kc, ks = jax.random.split(key, 4)
    freq = jax.random.uniform(jax.random.split(kt)[0], (n,), minval=0.5,
                              maxval=2.0)
    dev = jax.random.uniform(kd, (n,), minval=0.0, maxval=0.2) * freq
    return {"freq": freq, "dev": dev, "est": jnp.zeros((n,)),
            "loss": jnp.float32(ENV["f0"]), "queue": jnp.float32(0.0),
            "spent": jnp.float32(0.0), "round": jnp.int32(0),
            "channel": _next_channel(kc, n, p_good).astype(jnp.int32),
            "last": jnp.int32(0), "key": ks}


def _env_obs(s, horizon):
    feats = jnp.concatenate([
        jnp.stack([s["loss"], ENV["f0"] - s["loss"], s["queue"],
                   s["round"].astype(jnp.float32) / horizon,
                   s["spent"] / ENV["budget"]]),
        jax.nn.one_hot(s["last"], N_ACTIONS),
        jax.nn.one_hot(s["channel"], 3).mean(0),
        jnp.stack([jnp.mean(s["freq"] + s["est"]),
                   jnp.mean(jnp.abs(s["dev"] - s["est"])),
                   jnp.tanh(s["loss"])])])
    return jnp.pad(feats, (0, OBS_DIM - feats.shape[0]))


def _env_step(s, action, horizon, p_good):
    """One aggregation round of the twin-simulated environment: Eqns 7-8
    energy, the loss's decay with diminishing aggregation gain, the Eqn-12
    queue and the Eqn-15 reward on the twin-estimated cost."""
    a = action.astype(jnp.float32) + 1.0
    key, kc, kn, ke = jax.random.split(s["key"], 4)
    e_cmp = jnp.mean(1.0 / jnp.maximum(s["freq"] + s["dev"], 1e-3))
    e_est = jnp.mean(1.0 / jnp.maximum(s["freq"] + s["est"], 1e-3))
    lam = jnp.asarray(NOISE_MEAN_DB, jnp.float32)[s["channel"]]
    noise_w = 10.0 ** ((jax.random.poisson(ke, lam, lam.shape).astype(
        jnp.float32) + lam) / 10.0) * 1e-7
    rate = BANDWIDTH * jnp.log2(1.0 + TX_POWER * GAIN / noise_w)
    e_com = jnp.mean(N_COM * MODEL_BITS / jnp.maximum(rate, 1.0))
    consumed, estimated = a * e_cmp + e_com, a * e_est + e_com
    rnd = s["round"].astype(jnp.float32)
    decay = jnp.exp(-ENV["kappa"] * a / (1.0 + 0.05 * rnd))
    mis = jnp.abs(e_est - e_cmp) / jnp.maximum(e_cmp, 1e-6)
    wobble = ENV["noise"] * jax.random.normal(kn, ()) * (1.0 + 5.0 * mis)
    loss = jnp.maximum(ENV["f_star"] + (s["loss"] - ENV["f_star"]) * decay
                       + wobble, 0.0)
    queue = jnp.maximum(s["queue"] + consumed - ENV["budget"] / horizon, 0.0)
    v = ENV["v0"] * (1.0 + ENV["v_growth"] * s["round"])
    reward = ((v * (s["loss"] - loss) - s["queue"] * estimated)
              * ENV["reward_scale"])
    n = s["freq"].shape[0]
    out = dict(s, loss=loss, queue=queue, spent=s["spent"] + consumed,
               round=s["round"] + 1, last=action.astype(jnp.int32), key=key,
               channel=_next_channel(kc, n, p_good).astype(jnp.int32),
               est=0.9 * s["est"] + (1.0 - 0.9) * s["dev"])
    done = (out["round"] >= horizon) | (out["spent"] >= ENV["budget"])
    return out, reward, done


def _td_loss(q, target, s, a, r, s2):
    q_sa = jnp.take_along_axis(_q_values(q, s), a[:, None], axis=1)[:, 0]
    y = jax.lax.stop_gradient(
        r + GAMMA * jnp.max(_q_values(target, s2), axis=1))
    return jnp.mean((y - q_sa.astype(jnp.float32)) ** 2)


@functools.partial(jax.jit, static_argnames=("hyper", "dtype"))
def _alg1_step(key, agent, env, hyper, dtype):
    """Epsilon-greedy select, environment step, replay write and one TD
    step of SGD (Eqns 16-18) with the periodic target sync."""
    horizon, p_good, batch, lr = hyper
    key, ka, kt = jax.random.split(key, 3)
    kg, kr = jax.random.split(ka)
    obs = _env_obs(env, horizon)
    q, target, rep, step = (agent["q"], agent["target"], agent["replay"],
                            agent["step"])
    greedy = jnp.argmax(_q_values(q, obs.astype(dtype)))
    eps = jnp.minimum(EPS0 + EPS_GROWTH * step.astype(jnp.float32), 1.0)
    action = jnp.where(jax.random.uniform(kg) < eps, greedy,
                       jax.random.randint(kr, (), 0, N_ACTIONS)
                       ).astype(jnp.int32)
    env2, reward, done = _env_step(env, action, horizon, p_good)
    i, cap = rep["ptr"], rep["s"].shape[0]
    rep = {"s": rep["s"].at[i].set(obs), "a": rep["a"].at[i].set(action),
           "r": rep["r"].at[i].set(reward),
           "s2": rep["s2"].at[i].set(_env_obs(env2, horizon)),
           "ptr": (i + 1) % cap, "full": rep["full"] | (i + 1 >= cap)}
    limit = jnp.where(rep["full"], cap, jnp.maximum(rep["ptr"], 1))
    idx = jax.random.randint(kt, (batch,), 0, limit)
    grads = jax.grad(_td_loss)(q, target, rep["s"][idx].astype(dtype),
                               rep["a"][idx], rep["r"][idx],
                               rep["s2"][idx].astype(dtype))
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, CLIP / (norm + 1e-9))
    q = jax.tree.map(lambda p, g: p - (lr * scale).astype(dtype) * g, q,
                     grads)
    target = jax.tree.map(lambda t, e: jnp.where(step % TARGET_SYNC == 0,
                                                 e, t), target, q)
    agent = {"q": q, "target": target, "replay": rep, "step": step + 1}
    return key, agent, env2, done


@functools.lru_cache(maxsize=None)
def _pretrain(seed, episodes, horizon, p_good, buffer_size, batch_size, lr,
              dtype_name):
    dtype = jnp.dtype(dtype_name)
    q0 = _qnet_init(jax.random.PRNGKey(seed))
    q = jax.tree.map(lambda l: l.astype(dtype), q0)
    agent = {"q": q, "target": q, "step": jnp.int32(0), "replay": {
        "s": jnp.zeros((buffer_size, OBS_DIM)),
        "a": jnp.zeros((buffer_size,), jnp.int32),
        "r": jnp.zeros((buffer_size,)),
        "s2": jnp.zeros((buffer_size, OBS_DIM)),
        "ptr": jnp.int32(0), "full": jnp.zeros((), bool)}}
    hyper = (horizon, p_good, batch_size, lr)
    key = jax.random.PRNGKey(seed + 1)
    for ep in range(episodes):
        env = _env_reset(jax.random.fold_in(key, ep), p_good)
        for _ in range(horizon):        # an episode ends at its budget
            key, agent, env, done = _alg1_step(key, agent, env, hyper, dtype)
            if bool(done):
                break
    host = lambda t: {k: np.asarray(v, np.float32)        # noqa: E731
                      for k, v in jax.device_get(t).items()}
    return host(agent["q"]), host(q0)


def dqn_pretrain(params: dict, dtype=jnp.float32) -> tuple:
    """Alg. 1 from the controller's seed: ``episodes`` episodes of the
    twin-simulated environment -> (trained Q-network, initial one), both
    float32 on the host.  It runs at the matmul precision the
    configuration states (the chip's default), as the reference's k-means
    does: its greedy steps are decisions.  ``dtype`` bfloat16 trains the
    network in bfloat16 (the control); ``episodes`` 0 leaves it
    untrained (a planted fault)."""
    p = {**ALG1, **{k: v for k, v in params.items() if k in ALG1}}
    with jax.default_matmul_precision("default"):
        return _pretrain(int(p["seed"]), int(p["episodes"]),
                         int(p["horizon"]), float(p["p_good"]),
                         int(p["buffer_size"]), int(p["batch_size"]),
                         float(p["lr"]), jnp.dtype(dtype).name)


def action_scores(ctl: dict, qnet, obs):
    """Score of every raw action 1..n: the DQN's Q-values at ``obs``, or
    1 for the fixed controller's constant and 0 elsewhere."""
    n = ctl.get("params", {}).get("n_actions", 10)
    if ctl["kind"] == "fixed":
        return np.asarray([1.0 if r + 1 == ctl["params"].get("a", 5)
                           else 0.0 for r in range(n)])
    if ctl["kind"] == "dqn":
        return np.asarray(_q_values(qnet, obs), np.float64)
    raise ValueError(f"no reference for controller {ctl['kind']!r}")


# --------------------------------------------------------------------- #
# one asynchronous cluster round
# --------------------------------------------------------------------- #
@jax.jit
def _energy(a, true_freq, channel, key, members):
    """Eqns 7-8: a local trainings plus one OFDMA upload per member."""
    e_cmp = a * N_CMP * TRAIN_CYCLES / jnp.maximum(true_freq, 1e-3)
    lam = jnp.asarray(NOISE_MEAN_DB, jnp.float32)[channel]
    noise = jax.vmap(lambda m, l: jax.random.poisson(
        jax.random.fold_in(key, m), l, ()))(members, lam)
    noise_w = 10.0 ** ((noise.astype(jnp.float32) + lam) / 10.0) * 1e-7
    rate = BANDWIDTH * jnp.log2(1.0 + TX_POWER * GAIN / noise_w)
    return e_cmp + N_COM * MODEL_BITS / jnp.maximum(rate, 1.0)


@functools.partial(jax.jit, static_argnames=("batch",))
def _draw(key, ids, lens, batch):
    """Each member draws ``batch`` row slots with replacement from its
    shard under ``fold_in(key, id)``."""
    return jax.vmap(lambda d, n: jax.random.randint(
        jax.random.fold_in(key, d), (batch,), 0, jnp.maximum(n, 1)))(
        ids, lens)


def _batches(key, parts, m, batch):
    lens = np.asarray([len(parts[i]) for i in m], np.int32)
    idx = np.zeros((len(m), int(lens.max())), np.int32)
    for j, i in enumerate(m):
        idx[j, :len(parts[i])] = parts[i]
    sel = _draw(key, jnp.asarray(m, jnp.int32), jnp.asarray(lens), batch)
    return jnp.take_along_axis(jnp.asarray(idx), sel, axis=1)


@functools.partial(jax.jit, static_argnames=("fedavg", "dtype"))
def _cluster_update(params, x_all, y_all, rows, lr, a, fdev, inter, rep_m,
                    pkt, iota, fedavg, dtype):
    """Local SGD of every member from the cluster's model, Eqns 4-5 for
    each member, and the Eqn-6 aggregate -> (aggregate, member losses,
    member reputations)."""
    m = rows.shape[0]
    x, y = x_all[rows].astype(dtype), y_all[rows]
    start = jax.tree.map(lambda l: jnp.broadcast_to(
        l.astype(dtype), (m,) + l.shape), params)
    new, losses = _local_sgd(start, x, y, lr.astype(dtype), a)
    upd = jnp.concatenate([(new[k] - start[k]).reshape(m, -1)
                           for k in sorted(new)], axis=1)
    dist = jnp.linalg.norm(upd - upd.mean(0, keepdims=True), axis=1)
    rel = dist / (jnp.sum(dist) + EPS)
    q = jnp.clip(1.0 - rel * (m / max(m - 1.0, 1.0)), EPS, 1.0)
    unit = upd / (jnp.linalg.norm(upd, axis=1, keepdims=True) + EPS)
    cos = unit @ unit.T - 2.0 * jnp.eye(m, dtype=unit.dtype)
    div = jnp.clip(1.0 - jnp.maximum(jnp.max(cos, axis=1), 0.0), EPS, 1.0)
    b = ((1.0 - pkt) * q.astype(jnp.float32) / (1.0 + fdev) * inter
         * 0.5 * (1.0 + div.astype(jnp.float32)))
    rep_m = rep_m + b + iota * pkt
    r = jnp.maximum(rep_m, 0.0)
    w = jnp.where(jnp.sum(r) > 1e-6, r / jnp.maximum(jnp.sum(r), 1e-6),
                  1.0 / m)
    if fedavg:
        w = jnp.full((m,), 1.0 / m)
    agg = {k: jnp.tensordot(w.astype(dtype), v, axes=1)
           for k, v in new.items()}
    return agg, losses.astype(jnp.float32), rep_m


def fleet_round(spec, fleet: Fleet, members, c, a, data, parts, dtype,
                fault=None):
    """Run cluster ``c`` for ``a`` local steps -> (fleet, metrics).
    ``fault="half_batch"`` trains and scores each member on the first half
    of its batch only (a planted fault, for the check's calibration)."""
    m = np.asarray(members[c])
    mj = jnp.asarray(m, jnp.int32)
    key, kb, ke, kc2, _ = jax.random.split(fleet.key, 5)
    pkt, iota = spec["channel"]["pkt_fail"], spec["iota"]
    rows = _batches(kb, parts, m, spec["local_batch"])
    if fault == "half_batch":
        rows = rows[:, :max(1, rows.shape[1] // 2)]
    fdev = jnp.abs(fleet.freq_dev[mj] - fleet.dev_est[mj])
    inter = fleet.alpha[mj] / (fleet.alpha[mj] + fleet.beta[mj] + EPS)
    agg, losses, rep_m = _cluster_update(
        fleet.cluster_params[c], data.x, data.y, rows,
        jnp.float32(spec["lr"]), jnp.int32(a), fdev, inter, fleet.rep[mj],
        jnp.float32(pkt), jnp.float32(iota),
        fedavg=spec["aggregator"]["kind"] == "fedavg", dtype=dtype)
    rep = fleet.rep.at[mj].set(rep_m)

    # Eqn 6 into cluster c, then the Eqn-19 staleness-weighted average
    rnd = fleet.round + 1
    ts = fleet.cluster_ts.at[c].set(float(rnd))
    sw = (math.e / 2) ** (-(float(rnd) - ts))
    sw = sw / (jnp.sum(sw) + EPS)
    stack = list(fleet.cluster_params)
    stack[c] = agg
    glob = {k: sum(sw[j].astype(dtype) * stack[j][k].astype(dtype)
                   for j in range(len(stack))) for k in agg}
    stack[c] = glob

    # Eqns 7-8, twins (Eqns 1-2), channel, Eqn-12 queue
    true_f = fleet.freq[m] + fleet.freq_dev[m]
    e = _energy(jnp.float32(a), true_f, fleet.channel[m], ke, mj)
    consumed = float(jnp.sum(e))
    dev_est = fleet.dev_est
    if spec["fleet"]["calibrate_dt"]:
        dev_est = 0.9 * dev_est + 0.1 * fleet.freq_dev
    trans_row = jnp.asarray([spec["channel"]["p_good"],
                             (1 - spec["channel"]["p_good"]) / 2,
                             (1 - spec["channel"]["p_good"]) / 2])
    channel = jax.random.categorical(
        kc2, jnp.log(trans_row[None, :] + 1e-12)
        + jnp.zeros((fleet.channel.shape[0], 3)), axis=-1)
    out = fleet._replace(
        dev_est=dev_est,
        loss=fleet.loss.at[mj].set(losses),
        energy=fleet.energy.at[mj].add(e), alpha=fleet.alpha + 1.0,
        rep=rep, channel=channel.astype(jnp.int32), cluster_params=stack,
        global_params=glob, cluster_ts=ts, queue=0.0, round=rnd, key=key)
    loss = float(jnp.mean(losses))
    return out, {"consumed": consumed, "loss": loss}


# --------------------------------------------------------------------- #
# following a run
# --------------------------------------------------------------------- #
def simulate(spec: dict, dims: dict, data, parts, rounds: int, *,
             force: Optional[dict] = None, clock: str = "f32",
             dtype=jnp.float32, fault: Optional[str] = None, key=None):
    """Run ``rounds`` rounds from the seeded fleet.

    ``key``: the rounds' random stream.  ``force``: the program's
    ``assign``ment and per-round ``cluster`` and ``a`` lists.  ``clock``:
    "f32" accumulates event times as the scan does, "f64" as the event
    loop's heap does.  ``fault`` plants a fault in a run that stands in
    the program's place: "half_batch", "action" (each round takes the next
    action after the one it chose), "schedule" (each round runs the
    cluster after the earliest pending one), "energy" (each round reports
    half as much energy again) or "untrained" (the DQN is not trained).
    Returns per-round records, the final Fleet, the decision scores, and
    for a DQN its Q-networks (trained and initial) and the observations
    it scored.
    """
    ctl = spec["controller"]
    qnet = qnet0 = None
    if ctl["kind"] == "dqn":
        params = dict(ctl.get("params", {}))
        if fault == "untrained":
            params["episodes"] = 0
        qnet, qnet0 = dqn_pretrain(params, dtype)
    ctx = (jax.default_matmul_precision("highest") if dtype == jnp.float32
           else contextlib.nullcontext())
    with ctx:
        out = _simulate(spec, dims, data, parts, rounds, qnet, force,
                        clock, dtype, fault, key)
    return dict(out, qnet=qnet, qnet0=qnet0)


def _simulate(spec, dims, data, parts, rounds, qnet, force, clock, dtype,
              fault, key):
    fleet, members, own = init_fleet(
        spec, dims, parts, None if force is None else force["assign"], key)
    init_global = fleet.global_params
    if dtype != jnp.float32:
        fleet = fleet._replace(cluster_params=[
            jax.tree.map(lambda l: l.astype(dtype), p)
            for p in fleet.cluster_params])
    ctl = spec["controller"]
    n_actions = ctl.get("params", {}).get("n_actions", 10)
    k = len(members)
    ftype = np.float32 if clock == "f32" else np.float64
    times = np.zeros(k, ftype)
    x256 = data.x[:256].astype(dtype)
    recs, sched, gaps, seen = [], [], [], []
    for i in range(rounds):
        c_own = int(np.argmin(times))   # heap order: lowest (t, c)
        c = c_own if force is None else int(force["cluster"][i])
        if fault == "schedule":
            c = (c_own + 1) % k
        # how much later than the earliest pending event the chosen
        # cluster's event lies (0 for the reference's own choice)
        sched.append(float(times[c] - times[c_own])
                     / max(1.0, abs(float(times[c_own]))))
        fc = _cluster_freqs(fleet, members)
        obs = None
        if ctl["kind"] == "dqn":
            obs = _dqn_obs(fleet, members, c, x256)
            seen.append(np.asarray(obs, np.float64))
        scores = action_scores(ctl, qnet, obs)
        cands = [_bounded(np.arange(1, n_actions + 1), fc[c], fc.max(),
                          fleet.round, spec["clustering"], n_actions, s)
                 for s in (0.0, -1e-5, 1e-5)]
        if force is None:
            a = int(cands[0][int(np.argmax(scores))])
            if fault == "action":
                a = a % n_actions + 1
        else:
            a = int(force["a"][i])
        ok = np.any([b == a for b in cands], axis=0)
        gaps.append(float(scores.max() - scores[ok].max()) if ok.any()
                    else math.inf)
        t = times[c]
        fleet, met = fleet_round(spec, fleet, members, c, a, data, parts,
                                 dtype, fault)
        if fault == "energy":
            met["consumed"] *= 1.5
        dur = ftype(np.float32(a) / np.float32(
            max(_cluster_freqs(fleet, members)[c], 1e-6)))
        times[c] = t + dur
        recs.append({"t": float(t), "cluster": c, "a": a, "dur": float(dur),
                     **met})
    return {"records": recs, "fleet": fleet, "members": members,
            "assign": own, "init_global": init_global,
            "schedule_gap": max(sched),
            "action_gap": max(gaps), "times": times, "obs": seen}
