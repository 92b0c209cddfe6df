"""Pluggable federation components and their registry entries.

Three component protocols, all duck-typed — and all **jit-safe**: the fused
`FleetState` round traces aggregator and task calls into one compiled
program, so their bodies must be pure jnp (no host syncs, no Python control
flow on traced values).

Aggregator        ``__call__(client_params, weights, mask=None) -> pytree``
                  (client_params leaves carry a leading client dim).  Class
                  attr ``supports_mask``: True means the rule understands a
                  (C,) validity mask and the engine may run it on *padded*
                  fixed-shape clusters sharing one compiled round; False
                  (the default for third-party callables) makes the engine
                  compile one exact-shape round per cluster size instead.
FrequencyController
                  ``select(ctx) -> int`` raw a_i before the Alg.-2 tolerance
                  bound (applied *inside* the jitted round); optional
                  ``observe(ctx, consumed, loss)`` feedback hook after the
                  round; ``n_actions`` caps a_i.  Class attr ``needs_ctx``:
                  False lets the engine skip materializing the host-side
                  `ControllerCtx` (device->host syncs) each round.  An
                  optional ``scan_policy() -> repro.control.ScanPolicy``
                  provides the in-jit twin of `select` that
                  `DeviceScaleEngine.run_scanned` traces into its
                  lax.scan-over-rounds (all built-ins implement it).
TaskAdapter       model/task plug: init / loss / local training / metrics.
                  ``local_train`` must accept a *traced* step count (the
                  tolerance bound is computed inside jit).

Registration makes every paper mechanism (trust Eqn 6, robust baselines,
DQN Alg. 1, Lyapunov Eqn 12-15) a named choice in `FederationSpec`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import policy as ctl_policy
from repro.control.scanned_dqn import train_on_env
from repro.core import dqn as dqn_lib
from repro.core import envs
from repro.core.autoencoder import (anomaly_auc, code_mean,
                                    init_mlp_autoencoder,
                                    reconstruction_errors,
                                    reconstruction_loss)
from repro.core.lyapunov import init_queue, step_queue
from repro.core.mlp import (classifier_loss, evaluate_classifier,
                            init_mlp_classifier, mlp_hidden_mean)
from repro.core.robust import AGGREGATORS as ROBUST_RULES
from repro.core.robust import MASKED_AGGREGATORS as MASKED_RULES
from repro.core.trust import trust_weighted_average
from repro.core.twin import calibrated_freq
from repro.kernels.ops import trust_aggregate_global_flat, trust_aggregate_tree

from .registry import (register_aggregator, register_controller,
                       register_task)


# --------------------------------------------------------------------- #
# controller context
# --------------------------------------------------------------------- #
class ControllerCtx(NamedTuple):
    """What a frequency controller may look at when choosing a_i."""
    round: int                       # global round counter
    cluster: int                     # cluster index being scheduled
    obs: Callable[[], jnp.ndarray]   # lazy DQN observation (OBS_DIM,)
    cluster_loss: float              # mean twin loss over the cluster
    cluster_freq: float              # straggler (min) calibrated frequency
    mean_freq: float                 # mean calibrated frequency in cluster
    channel_good_frac: float         # fraction of members in the good state
    energy_used: float               # cumulative energy so far


# --------------------------------------------------------------------- #
# aggregators (Eqn 6 + robust baselines)
# --------------------------------------------------------------------- #
class WeightedAggregator:
    """Trust/uniform weighted average; hot path through the Pallas
    ``trust_aggregate`` kernel (interpret=True on CPU), jnp fallback.
    Mask-aware: padded client rows carry zero weight, so ragged cluster
    memberships run as one fixed-shape compiled round."""

    supports_mask = True

    def __init__(self, uniform: bool = False, use_kernel: bool = True):
        self.uniform = uniform
        self.use_kernel = use_kernel
        # the kernel path can fold the Eqn-19 global average into the same
        # grid pass (`aggregate_flat_with_global`); the engine consults this
        self.supports_fused_global = use_kernel

    def _effective_weights(self, weights, mask):
        if not self.uniform:
            return weights
        if mask is None:
            return jnp.full_like(weights, 1.0 / weights.shape[0])
        m = mask.astype(weights.dtype)
        return m / jnp.maximum(jnp.sum(m), 1.0)

    def __call__(self, client_params, weights, mask=None):
        weights = self._effective_weights(weights, mask)
        if self.use_kernel:
            return trust_aggregate_tree(client_params, weights, mask)
        if mask is not None:
            weights = weights * mask.astype(weights.dtype)
        return trust_weighted_average(client_params, weights)

    def aggregate_flat_with_global(self, updates_flat, weights, mask,
                                   cluster_stack, staleness_w, c):
        """Fused Eqn 6 + Eqn 19: member updates -> the post-round global
        model in one `trust_aggregate_global` kernel pass (the Eqn-6
        aggregate replaces row ``c`` of the stacked cluster parameters
        in-VMEM before the staleness-weighted average).  The updates come
        as one (C, W) matrix (`kernels.ops.flatten_rows`), which the engine
        builds once at the kernel's padded width."""
        weights = self._effective_weights(weights, mask)
        return trust_aggregate_global_flat(
            updates_flat, weights, mask, cluster_stack, staleness_w, c)


class RobustAggregator:
    """Byzantine-robust rules from repro.core.robust; ignores trust weights
    (that is their point: no reputation signal needed).  Rules with a
    fixed-capacity masked variant (`median` / `trimmed_mean`, via the
    ±inf-padded sorts in `robust`) advertise ``supports_mask=True`` and
    join the engine's padded fused round; the remaining rank statistics
    (krum, multi-krum) run on exact-shape clusters — one compile per
    distinct cluster size."""

    def __init__(self, rule: str, **kw):
        self.rule_name = rule
        self._rule = ROBUST_RULES[rule]
        self._masked_rule = MASKED_RULES.get(rule)
        self.supports_mask = self._masked_rule is not None
        self._kw = kw

    def __call__(self, client_params, weights, mask=None):
        del weights
        if mask is not None:
            if self._masked_rule is None:
                raise ValueError(f"{self.rule_name} cannot run on padded "
                                 "clusters (supports_mask=False)")
            return self._masked_rule(client_params, mask, **self._kw)
        return self._rule(client_params, **self._kw)


@register_aggregator("trust")
def _trust(params: Dict[str, Any]):
    return WeightedAggregator(uniform=False,
                              use_kernel=params.get("use_kernel", True))


@register_aggregator("fedavg")
def _fedavg(params: Dict[str, Any]):
    return WeightedAggregator(uniform=True,
                              use_kernel=params.get("use_kernel", True))


def _register_robust(name):
    @register_aggregator(name)
    def _build(params: Dict[str, Any], _name=name):
        return RobustAggregator(_name, **{k: v for k, v in params.items()
                                          if k != "use_kernel"})


for _name in ROBUST_RULES:
    _register_robust(_name)


# --------------------------------------------------------------------- #
# frequency controllers
# --------------------------------------------------------------------- #
class FixedController:
    """Benchmark scheme: constant a_i (still tolerance-bounded by Alg. 2).
    ``needs_ctx=False``: the engine skips the per-round host-side ctx
    (device syncs) entirely — the fused-round fast path."""

    needs_ctx = False

    def __init__(self, a: int = 5, n_actions: int = 10):
        self.a = int(a)
        self.n_actions = int(n_actions)

    def select(self, ctx: ControllerCtx) -> int:
        return self.a

    def observe(self, ctx, consumed, loss):
        pass

    def scan_policy(self) -> ctl_policy.ScanPolicy:
        return ctl_policy.fixed_policy(self.a)


class DQNController:
    """Greedy policy of a trained Alg.-1 DQN agent.

    Build from a live agent (``DQNController(agent, cfg)``) or let the
    registry factory train one on the DT-simulated environment — the paper's
    headline mechanism: the agent interacts with the twins, not the devices.
    """

    needs_ctx = True                    # select() reads the DQN observation

    def __init__(self, agent: dqn_lib.DQNState, cfg: dqn_lib.DQNConfig):
        self.agent = agent
        self.cfg = cfg
        self.n_actions = cfg.n_actions

    def select(self, ctx: ControllerCtx) -> int:
        q = dqn_lib.q_values(self.agent.eval_params, ctx.obs())
        return int(jnp.argmax(q)) + 1

    def observe(self, ctx, consumed, loss):
        pass

    def scan_policy(self) -> ctl_policy.ScanPolicy:
        return ctl_policy.dqn_policy(self.agent.eval_params)

    def distill(self, **kw) -> ctl_policy.PolicyTable:
        """Freeze the greedy head into a lookup table
        (`repro.control.distill_table`) for microsecond selects."""
        return ctl_policy.distill_table(self.agent.eval_params, **kw)

    def restore_policy_state(self, eval_params) -> None:
        """Adopt a checkpointed scan-policy carry (`repro.serve` restores
        the exact deployed net rather than relying on the registry's
        deterministic re-pretrain)."""
        self.agent = self.agent._replace(eval_params=eval_params)

    @classmethod
    def pretrain(cls, seed: int = 0, episodes: int = 4, horizon: int = 25,
                 p_good: float = 0.5, calibrate_dt: bool = True,
                 buffer_size: int = 512, batch_size: int = 32,
                 lr: float = 2e-3) -> "DQNController":
        """Train a fresh agent on the DT environment (§IV-C, Alg. 1).

        The whole run — episodes of epsilon-greedy interaction, replay
        writes, TD steps, target syncs — lowers into one nested `lax.scan`
        (`repro.control.scanned_dqn.train_on_env`); no host episode loop.
        """
        p = envs.EnvParams(horizon=horizon, p_good=p_good,
                           calibrate_dt=calibrate_dt)
        cfg = dqn_lib.DQNConfig(buffer_size=buffer_size,
                                batch_size=batch_size, lr=lr)
        agent = dqn_lib.init_dqn(jax.random.PRNGKey(seed), cfg)
        agent, _ = train_on_env(jax.random.PRNGKey(seed + 1), agent, cfg, p,
                                episodes=episodes)
        return cls(agent, cfg)


class LyapunovGreedyController:
    """One-step drift-plus-penalty greedy controller (Eqns 12-15).

    No learned policy: each slot it scores every a in {1..n_actions} with
    the paper's P2 objective  v·ΔF̂(a) − Q(i)·(a·Ê_cmp + Ê_com)  using the
    twin-estimated energy and an exponential loss-decay model, picks the
    argmax, and advances the deficit queue with the realized consumption.
    A model-free baseline between `fixed` and the trained DQN.

    Scoring goes through `repro.control.policy.lyapunov_scores` — the same
    f32 device math the in-jit `scan_policy` traces into the fused round —
    so the event-heap and scanned execution paths pick identical actions
    (jnp.argmax and the old strict-greater host loop both keep the earliest
    maximum on ties).
    """

    needs_ctx = True          # select() scores the P2 objective from ctx

    def __init__(self, budget: float = 250.0, horizon: int = 100,
                 kappa: float = 0.08, f_star: float = 0.1,
                 v0: float = 1.0, v_growth: float = 0.02,
                 n_actions: int = 10):
        self.queue = init_queue(budget, horizon)
        self.kappa = kappa
        self.f_star = f_star
        self.v0 = v0
        self.v_growth = v_growth
        self.n_actions = int(n_actions)

    def select(self, ctx: ControllerCtx) -> int:
        scores = ctl_policy.lyapunov_scores(
            self.queue.q, jnp.float32(ctx.round),
            jnp.float32(ctx.cluster_loss), jnp.float32(ctx.mean_freq),
            jnp.float32(ctx.channel_good_frac), n_actions=self.n_actions,
            kappa=self.kappa, f_star=self.f_star, v0=self.v0,
            v_growth=self.v_growth)
        return int(jnp.argmax(scores)) + 1

    def observe(self, ctx, consumed, loss):
        self.queue = step_queue(self.queue, consumed)

    def scan_policy(self) -> ctl_policy.ScanPolicy:
        """In-jit twin reading the Eqn-12 backlog off `FleetState.queue`
        (the engine advances that leaf with the same realized consumption
        `observe` sees on the host path)."""
        return ctl_policy.lyapunov_policy(
            n_actions=self.n_actions, kappa=self.kappa, f_star=self.f_star,
            v0=self.v0, v_growth=self.v_growth)

    def sync_queue(self, q) -> None:
        """Adopt the device-resident backlog after a scanned run so later
        host-side selects continue from the same deficit."""
        self.queue = self.queue._replace(q=jnp.asarray(q, jnp.float32))


@register_controller("fixed")
def _fixed(params: Dict[str, Any]):
    return FixedController(a=params.get("a", 5),
                           n_actions=params.get("n_actions", 10))


@register_controller("dqn")
def _dqn(params: Dict[str, Any]):
    agent = params.get("agent")
    if agent is not None:
        return DQNController(agent, params.get(
            "dqn_cfg", dqn_lib.DQNConfig()))
    kw = {k: v for k, v in params.items() if k not in ("agent", "dqn_cfg")}
    return DQNController.pretrain(**kw)


@register_controller("lyapunov")
def _lyapunov(params: Dict[str, Any]):
    return LyapunovGreedyController(**params)


# --------------------------------------------------------------------- #
# task adapters
# --------------------------------------------------------------------- #
class MLPTask:
    """The paper's device-scale MNIST-shaped classifier.

    jit-safe: ``local_train`` takes the step count as a *traced* scalar
    (fori_loop with a dynamic trip count), so the fused round can apply the
    Alg.-2 tolerance bound inside the compiled program without a per-value
    recompile."""

    def __init__(self, hidden: int = 200, n_classes: int = 10):
        self.hidden = hidden
        self.n_classes = n_classes
        self._client_sgd_v = jax.jit(
            jax.vmap(self._client_sgd, in_axes=(0, 0, None, None)))
        self._losses_v = jax.vmap(classifier_loss, in_axes=(0, 0))

    @staticmethod
    def _client_sgd(params, batch, lr, steps):
        def one(_, p):
            g = jax.grad(classifier_loss)(p, batch)
            return jax.tree.map(lambda a, b: a - lr * b, p, g)
        return jax.lax.fori_loop(0, steps, one, params)

    def init(self, key, dim: int):
        return init_mlp_classifier(key, dim=dim, hidden=self.hidden,
                                   n_classes=self.n_classes)

    def local_train(self, stacked_params, batch, lr: float, steps: int):
        """vmap-ed a_i SGD steps over the member dim."""
        return self._client_sgd_v(stacked_params, batch, lr, steps)

    def losses(self, stacked_params, batch):
        return self._losses_v(stacked_params, batch)

    def loss(self, params, batch):
        return classifier_loss(params, batch)

    def evaluate(self, params, data) -> Dict[str, float]:
        # one program, one fetch: the scanned engine calls this every segment
        acc, loss = jax.device_get(evaluate_classifier(params, data.x,
                                                       data.y)).tolist()
        return {"acc": acc, "loss": loss}

    def hidden_mean(self, params, x):
        return mlp_hidden_mean(params, x)

    def corrupt_labels(self, y):
        """Byzantine label flip used by malicious members."""
        return (y + 1) % self.n_classes


class AutoencoderAnomalyTask:
    """Federated autoencoder anomaly detection over IoT telemetry — the
    first non-classification workload (FedIoT-style, SNIPPETS.md §3).

    Same engine contract as `MLPTask` (jit-safe ``local_train`` with a
    traced step count, vmapped per-member losses), but the loss is the mean
    squared *reconstruction* error and training is unsupervised — batch
    labels carry the anomaly ground truth for evaluation only, so the
    Eqn-4/5 trust pipeline (learning quality, gradient diversity, belief)
    runs on reconstruction gradients exactly as it does on classification
    gradients.  ``evaluate`` reports the reconstruction loss plus the
    threshold-free detection AUC of per-sample errors against the labels
    (surfacing in the trace's ``acc`` field).

    Byzantine label-flipping has no lever here (the training loss never
    reads labels), so ``corrupt_labels`` is the identity — model input
    poisoning instead via a custom task if needed.
    """

    def __init__(self, hidden: int = 64, code: int = 8):
        self.hidden = hidden
        self.code = code
        self._client_sgd_v = jax.jit(
            jax.vmap(self._client_sgd, in_axes=(0, 0, None, None)))
        self._losses_v = jax.vmap(reconstruction_loss, in_axes=(0, 0))

    @staticmethod
    def _client_sgd(params, batch, lr, steps):
        def one(_, p):
            g = jax.grad(reconstruction_loss)(p, batch)
            return jax.tree.map(lambda a, b: a - lr * b, p, g)
        return jax.lax.fori_loop(0, steps, one, params)

    def init(self, key, dim: int):
        return init_mlp_autoencoder(key, dim=dim, hidden=self.hidden,
                                    code=self.code)

    def local_train(self, stacked_params, batch, lr: float, steps: int):
        """vmap-ed a_i SGD steps on the reconstruction loss."""
        return self._client_sgd_v(stacked_params, batch, lr, steps)

    def losses(self, stacked_params, batch):
        return self._losses_v(stacked_params, batch)

    def loss(self, params, batch):
        return reconstruction_loss(params, batch)

    def evaluate(self, params, data) -> Dict[str, float]:
        scores = reconstruction_errors(params, data.x)
        auc = float(anomaly_auc(scores, data.y))
        return {
            "acc": None if np.isnan(auc) else auc,   # detection AUC
            "loss": float(jnp.mean(scores[:1024])),
        }

    def hidden_mean(self, params, x):
        return code_mean(params, x)

    def corrupt_labels(self, y):
        return y          # unsupervised: labels never enter the loss


class LMTask:
    """Datacenter-scale LM task over the sharded fl_step modes.

    ``arch`` names a smoke config from repro.configs, or pass explicit tiny
    dims (d_model/num_layers/...) for a self-contained config.
    """

    def __init__(self, arch: Optional[str] = None, mode: str = "fedavg_replica",
                 seq: int = 16, micro_batch: int = 2, n_micro: int = 1,
                 local_steps: int = 1, lr: float = 3e-4, **dims):
        from repro.models import ArchConfig
        if arch:
            from repro.configs import get_smoke_config
            self.cfg = get_smoke_config(arch)
        else:
            base = dict(name="api-tiny", arch_type="dense", num_layers=2,
                        d_model=32, vocab_size=64, num_heads=2,
                        num_kv_heads=1, d_ff=64)
            base.update(dims)
            self.cfg = ArchConfig(**base)
        self.mode = mode
        self.seq = seq
        self.micro_batch = micro_batch
        self.n_micro = n_micro
        self.local_steps = local_steps
        self.lr = lr

    def make_batch(self, key, n_clusters: int, clients: int):
        from repro.core.fl_step import MODE_B
        from repro.data import token_stream
        if self.mode == MODE_B:
            shape = (n_clusters, self.n_micro, self.micro_batch, self.seq + 1)
        else:
            shape = (n_clusters, clients, self.n_micro, self.micro_batch,
                     self.seq + 1)
        if self.cfg.num_codebooks > 1:
            shape = shape[:-1] + (self.cfg.num_codebooks, self.seq + 1)
        toks = token_stream(key, int(np.prod(shape)),
                            self.cfg.vocab_size).reshape(shape)
        batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
        if self.mode == MODE_B:
            # trust enters as per-example loss weights in mode B
            batch["weights"] = jnp.ones(
                (n_clusters, self.n_micro, self.micro_batch))
        return batch


@register_task("mlp")
def _mlp(params: Dict[str, Any]):
    return MLPTask(**{k: v for k, v in params.items()
                      if k in ("hidden", "n_classes")})


@register_task("autoencoder-anomaly")
def _autoencoder(params: Dict[str, Any]):
    # data-generation params (n_samples/dim/n_types/...) are consumed by
    # `engine.default_device_data`; only the model dims reach the task
    return AutoencoderAnomalyTask(**{k: v for k, v in params.items()
                                     if k in ("hidden", "code")})


@register_task("lm")
def _lm(params: Dict[str, Any]):
    return LMTask(**params)
