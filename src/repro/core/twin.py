"""Digital twins of Industrial-IoT training devices (paper §III-A).

``DT_i(t) = {F(w_i^t), f_i(t), E_i(t)}``  (Eqn 1) — the twin mirrors each
device's training state (loss), compute capability (CPU/accelerator frequency)
and energy consumption.  The mapping has a deviation ``f̂_i(t)`` (Eqn 2);
calibration subtracts a running empirical estimate of that deviation.

Everything is a JAX-friendly struct-of-arrays over the device fleet so the
control plane (trust weights, DQN state) is computed with jnp ops and can be
jit'ed alongside the training step.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class TwinState(NamedTuple):
    """Struct-of-arrays digital twin of an n-device fleet."""
    loss: jnp.ndarray          # (n,)  F(w_i^t): per-client training loss
    freq: jnp.ndarray          # (n,)  mapped compute capability f_i(t) [GHz]
    freq_dev: jnp.ndarray      # (n,)  current mapping deviation f̂_i(t)
    dev_estimate: jnp.ndarray  # (n,)  running empirical deviation estimate
    energy: jnp.ndarray        # (n,)  cumulative energy E_i(t) [J]
    data_size: jnp.ndarray     # (n,)  |D_i| local dataset sizes
    alpha: jnp.ndarray         # (n,)  positive-interaction counts (Eqn 4)
    beta: jnp.ndarray          # (n,)  malicious/lazy-update counts (Eqn 4)
    router_entropy: jnp.ndarray  # (n,) MoE learning-quality extension


def init_twins(key, n: int, *, freq_lo=0.5, freq_hi=2.0,
               data_lo=256, data_hi=4096) -> TwinState:
    kf, kd = jax.random.split(key)
    freq = jax.random.uniform(kf, (n,), minval=freq_lo, maxval=freq_hi)
    data = jax.random.randint(kd, (n,), data_lo, data_hi).astype(jnp.float32)
    # a fresh zeros per leaf: the engines donate `FleetState`, and XLA
    # refuses to donate one buffer twice
    z = lambda: jnp.zeros((n,), jnp.float32)               # noqa: E731
    return TwinState(loss=jnp.full((n,), jnp.inf), freq=freq,
                     freq_dev=z(), dev_estimate=z(), energy=z(),
                     data_size=data, alpha=jnp.ones((n,)), beta=z(),
                     router_entropy=z())


def sample_deviation(key, twins: TwinState, max_dev: float = 0.2) -> TwinState:
    """Paper §V: DT mapping error ~ U(0, 0.2) of the true frequency."""
    dev = jax.random.uniform(key, twins.freq.shape, minval=0.0, maxval=max_dev)
    return twins._replace(freq_dev=dev * twins.freq)


def calibrate(twins: TwinState, ema: float = 0.9) -> TwinState:
    """Self-calibration (Eqn 2): fold the observed deviation into a running
    estimate; calibrated frequency = mapped + estimate."""
    est = ema * twins.dev_estimate + (1.0 - ema) * twins.freq_dev
    return twins._replace(dev_estimate=est)


def calibrated_freq(twins: TwinState) -> jnp.ndarray:
    return twins.freq + twins.dev_estimate


def observe_round(twins: TwinState, losses, energies, malicious_mask=None
                  ) -> TwinState:
    """Update twins after a federated round (real-time mapping)."""
    mal = (jnp.zeros_like(twins.beta) if malicious_mask is None
           else malicious_mask.astype(jnp.float32))
    return twins._replace(
        loss=losses,
        energy=twins.energy + energies,
        alpha=twins.alpha + (1.0 - mal),
        beta=twins.beta + mal,
    )


# ------------------------------------------------------------------ #
# fixed-shape member views for the fused FleetState round
# ------------------------------------------------------------------ #
# what a padding or dropped member slot reads of each twin array; the
# round masks these slots before any reduction (alpha=1 keeps the Eqn-4
# interaction ratio finite)
MEMBER_FILLS = TwinState(loss=0.0, freq=1.0, freq_dev=0.0, dev_estimate=0.0,
                         energy=0.0, data_size=1.0, alpha=1.0, beta=0.0,
                         router_entropy=0.0)


def observe_round_members(twins: TwinState, members, losses, energies,
                          malicious_mask=None) -> TwinState:
    """`observe_round` driven by one cluster's (M,) member slice.

    Scatters the member losses/energies into the fleet (padding sentinels
    drop) and applies the fleet-wide interaction-count update exactly as
    `observe_round` does.
    """
    full_loss = twins.loss.at[members].set(losses, mode="drop")
    full_e = jnp.zeros_like(twins.energy).at[members].set(
        energies, mode="drop")
    return observe_round(twins, full_loss, full_e, malicious_mask)
