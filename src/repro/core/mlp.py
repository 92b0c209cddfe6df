"""The paper's device-scale model: a small MLP classifier (MNIST-shaped).

Used by the paper-repro benchmarks (Figs 3, 6-8) and the real-environment
validation of the DQN agent.  vmap-friendly functional params.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_mlp_classifier(key, dim=784, hidden=200, n_classes=10):
    k1, k2 = jax.random.split(key)
    s = lambda n: 1.0 / jnp.sqrt(n)
    return {
        "w1": jax.random.normal(k1, (dim, hidden)) * s(dim),
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, n_classes)) * s(hidden),
        "b2": jnp.zeros((n_classes,)),
    }


def mlp_logits(params, x):
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def mlp_hidden_mean(params, x):
    """tau(t): mean hidden-layer activation — part of the DQN state (§IV-B)."""
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h.mean()


def classifier_loss(params, batch):
    logits = mlp_logits(params, batch["x"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def accuracy(params, x, y):
    return jnp.mean(jnp.argmax(mlp_logits(params, x), -1) == y)


@jax.jit
def evaluate_classifier(params, x, y):
    """[accuracy over every sample, mean loss over the first 1,024] as one
    compiled program, in one (2,) array so the host fetches it in one
    transfer.  The data are arguments, not closed over, so the program
    holds no dataset as constants and one compile per shape serves every
    caller in the process."""
    return jnp.stack([accuracy(params, x, y), classifier_loss(
        params, {"x": x[:1024], "y": y[:1024]})])
