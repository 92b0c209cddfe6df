"""Seeded fleet data for the benchmark's deployments.

Copied from the program's generators (``repro.data.synthetic`` and
``repro.data.federated``) so that a later change to them cannot move the
yardstick: the benchmark hands the same arrays to the program under test
(``Federation.from_spec(spec, data=..., parts=...)``) and to the reference.

* `make_classification`: the MNIST-shaped prototype task, with the class
  count as a parameter (10 for the paper's §V set-up, 62 for FEMNIST).
* `dirichlet_partition`: class-skewed non-IID shards (paper §V).
* `writer_partition`: LEAF-style shards, one per writer, with sizes drawn
  to a published per-writer mean and spread.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Classification(NamedTuple):
    x: jnp.ndarray       # (n, dim) f32
    y: jnp.ndarray       # (n,) int32


def make_classification(key, n: int, dim: int, n_classes: int,
                        noise: float = 0.8,
                        unit: bool = False) -> Classification:
    """Prototype mixture: ``x = prototype[y] + noise * N(0, 1)``; with
    ``unit`` divided by sqrt(1 + noise^2), so that every feature has unit
    variance however hard ``noise`` makes the task."""
    kp, ky, kx = jax.random.split(key, 3)
    protos = jax.random.normal(kp, (n_classes, dim))
    y = jax.random.randint(ky, (n,), 0, n_classes)
    x = protos[y] + noise * jax.random.normal(kx, (n, dim))
    if unit:
        x = x / jnp.sqrt(1.0 + noise * noise)
    return Classification(x=x, y=y)


def _numpy_rng(key) -> np.random.Generator:
    return np.random.default_rng(
        int(jax.random.randint(key, (), 0, 2 ** 31 - 1)))


def dirichlet_partition(key, labels, n_clients: int, alpha: float,
                        n_classes: int):
    """-> list of sorted index arrays, one per client (class skew)."""
    labels = np.asarray(labels)
    rng = _numpy_rng(key)
    out = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cl, part in enumerate(np.split(idx, cuts)):
            out[cl].extend(part.tolist())
    return [np.asarray(sorted(ix), dtype=np.int64) for ix in out]


def writer_sizes(key, n_samples: int, n_writers: int, mean: float,
                 std: float):
    """Per-writer shard sizes: normal(mean, std) scaled so that they sum
    to ``n_samples``, every writer holding at least one sample."""
    rng = _numpy_rng(key)
    raw = np.maximum(rng.normal(mean, std, n_writers), 1.0)
    sizes = np.maximum(np.floor(raw * n_samples / raw.sum()), 1).astype(
        np.int64)
    # hand the rounding remainder out one sample at a time, largest first
    short = n_samples - int(sizes.sum())
    order = np.argsort(-raw, kind="stable")
    step = 1 if short > 0 else -1
    i = 0
    while short != 0:
        w = order[i % n_writers]
        if step > 0 or sizes[w] > 1:
            sizes[w] += step
            short -= step
        i += 1
    return sizes


def writer_partition(key, n_samples: int, n_writers: int, mean: float,
                     std: float):
    """LEAF partition by writer: a random permutation of the rows cut into
    contiguous shards of `writer_sizes`."""
    ks, kp = jax.random.split(key)
    sizes = writer_sizes(ks, n_samples, n_writers, mean, std)
    perm = _numpy_rng(kp).permutation(n_samples)
    cuts = np.cumsum(sizes)[:-1]
    return [np.sort(p).astype(np.int64) for p in np.split(perm, cuts)]


def build(cfg: dict, seed: int):
    """(data, parts) of a configuration file's ``data`` block."""
    d = cfg["data"]
    key = jax.random.PRNGKey(seed)
    data = make_classification(key, n=d["n_samples"], dim=d["dim"],
                               n_classes=d["n_classes"], noise=d["noise"],
                               unit=d["unit_variance"])
    n_devices = cfg["spec"]["fleet"]["n_devices"]
    if d["partition"] == "dirichlet":
        parts = dirichlet_partition(key, data.y, n_devices,
                                    alpha=d["dirichlet_alpha"],
                                    n_classes=d["n_classes"])
    elif d["partition"] == "writer":
        parts = writer_partition(key, d["n_samples"], n_devices,
                                 mean=d["writer_mean"],
                                 std=d["writer_std"])
    else:
        raise ValueError(f"unknown partition {d['partition']!r}")
    return data, parts
