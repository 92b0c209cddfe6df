"""Energy and channel models (paper §III-D, Eqns 7-8).

Compute energy per local training (Eqn 7):   E_cmp = n_cmp * F / f_i
OFDMA uplink communication energy (Eqn 8):
    E_com = n_com * M / sum_c l_{i,c} W log2(1 + p h / I)

The wireless channel follows the paper's §V setup: a finite-state Markov
channel over {good, medium, bad} whose noise means are {0.1, 0.3, 0.5} dB
(Poisson-distributed noise influence).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

GOOD, MEDIUM, BAD = 0, 1, 2
NOISE_MEAN_DB = (0.1, 0.3, 0.5)   # host tuple: importing starts no backend


class ChannelParams(NamedTuple):
    bandwidth: float = 1e5          # W: sub-channel bandwidth [Hz]
    n_subchannels: int = 8          # |C|
    tx_power: float = 0.2           # p_{i,c} [W]
    gain: float = 1.0               # h_{i,c}
    model_bits: float = 8e6         # M: model size [bits]
    n_com: float = 1.0              # comm normalization factor
    n_cmp: float = 1.0              # compute normalization factor
    train_cycles: float = 1.0       # F: CPU cycles for one local training [G]
    # defaults put E_com on the same order as E_cmp so the channel state
    # actually drives the aggregation-timing trade-off (paper §V regime)


def compute_energy(freq, params: ChannelParams = ChannelParams()):
    """Eqn 7 per local training, vectorized over clients. freq: (n,) [GHz]."""
    return params.n_cmp * params.train_cycles / jnp.maximum(freq, 1e-3)


def channel_rate(state, key, params: ChannelParams = ChannelParams(),
                 members=None):
    """Shannon rate per client given channel state (n,) in {0,1,2}.
    Noise ~ Poisson with the state's mean influence (paper §V).

    With ``members`` (the device ids behind each slot of ``state``) the
    noise draw is keyed per device id via `fold_in` instead of shaped by
    ``state.shape`` — a device's channel noise is then invariant to the
    padded membership width, which is what pins padded, sharded, and
    population-stacked rounds to the same realization."""
    lam = jnp.asarray(NOISE_MEAN_DB, jnp.float32)[state]
    if members is None:
        noise = jax.random.poisson(key, lam, state.shape)
    else:
        noise = jax.vmap(
            lambda m, l: jax.random.poisson(jax.random.fold_in(key, m),
                                            l, ()))(members, lam)
    noise_db = noise.astype(jnp.float32) + lam
    noise = 10.0 ** (noise_db / 10.0) * 1e-7
    snr = params.tx_power * params.gain / noise
    frac = 1.0 / params.n_subchannels
    return params.n_subchannels * frac * params.bandwidth * jnp.log2(1.0 + snr)


def comm_energy(state, key, params: ChannelParams = ChannelParams(),
                members=None):
    """Eqn 8 per aggregation upload, vectorized over clients."""
    rate = channel_rate(state, key, params, members=members)
    return params.n_com * params.model_bits / jnp.maximum(rate, 1.0)


def round_energy(a, true_freq, channel_state, key,
                 params: ChannelParams = ChannelParams(), members=None):
    """Eqns 7+8 for one cluster round: ``a`` local trainings plus one
    upload, per member.  ``a`` may be a traced scalar (the fused round
    applies the Alg.-2 tolerance bound inside jit); ``true_freq`` is the
    device's real frequency f + f̂ (the twin's mapped value plus deviation).
    ``members`` keys the channel-noise draw per device id (see
    `channel_rate`)."""
    e_cmp = a * compute_energy(true_freq, params)
    e_com = comm_energy(channel_state, key, params, members=members)
    return e_cmp + e_com


# ------------------------------------------------------------------ #
# finite-state Markov channel
# ------------------------------------------------------------------ #
def channel_transition(p_good: float):
    """3-state transition matrix parameterized by the stationary probability
    of the good state (benchmarks sweep p_good as in Fig. 4)."""
    rest = (1.0 - p_good) / 2.0
    row = jnp.array([p_good, rest, rest])
    return jnp.stack([row, row, row])


def step_channel(key, state, trans):
    """state: (n,) int; trans: (3,3) row-stochastic."""
    return jax.random.categorical(key, jnp.log(trans[state] + 1e-12), axis=-1)
