"""Federated (non-IID) data partitioning.

``dirichlet_partition`` assigns class-skewed shards to clients — the standard
non-IID benchmark setup matching the paper's heterogeneous-device scenario;
``federated_batches`` materializes per-client fixed-size batches (struct-of-
arrays with a leading client dim) for the vmap-ed mode-A train step.

``padded_partition`` + ``sample_member_batch`` are the jit-safe pipeline the
fused `FleetState` cluster round gathers from: the ragged per-client index
lists become one (n, W) matrix at init, and batch selection is a fixed-shape
vmap of per-member randint draws — no Python list assembly, no host syncs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dirichlet_partition(key, labels, n_clients: int, alpha: float = 0.5,
                        n_classes: int | None = None):
    """-> list of index arrays, one per client (non-IID by class skew)."""
    labels = np.asarray(labels)
    n_classes = n_classes or int(labels.max()) + 1
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2 ** 31 - 1)))
    out = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cl, part in enumerate(np.split(idx, cuts)):
            out[cl].extend(part.tolist())
    return [np.asarray(sorted(ix), dtype=np.int64) for ix in out]


def uniform_cycle_partition(n_samples: int, n_devices: int):
    """Round-robin shards: device i owns rows {i, i+n, i+2n, ...}.

    The O(1)-per-device partition for fleets of n_devices >= 10^4, where
    a dirichlet draw (and its Python list assembly) dominates setup
    time.  Every shard is non-empty as long as ``n_samples >= n_devices``
    — smaller fleets wrap around so row ``i % n_samples`` seeds device i.
    """
    if n_samples >= n_devices:
        return [np.arange(i, n_samples, n_devices, dtype=np.int64)
                for i in range(n_devices)]
    return [np.asarray([i % n_samples], dtype=np.int64)
            for i in range(n_devices)]


def padded_partition(parts):
    """Pack ragged per-client index lists into one fixed-shape matrix.

    -> (part_idx (n, W) int32, part_len (n,) int32) with W = max shard size;
    rows are zero-padded past their length.  Precomputed once at engine init
    so the jitted round can gather batches without materializing Python
    lists.  An empty shard is rejected here, loudly: inside the fixed-shape
    round it would silently train that client on dataset row 0 forever
    (re-draw the partition, e.g. with a larger dirichlet alpha).
    """
    n = len(parts)
    empty = [i for i, p in enumerate(parts) if len(p) == 0]
    if empty:
        raise ValueError(f"clients {empty} have empty data shards; every "
                         "client needs >= 1 sample (re-draw the partition)")
    w = max((len(p) for p in parts), default=1)
    idx = np.zeros((n, max(w, 1)), dtype=np.int32)
    length = np.zeros((n,), dtype=np.int32)
    for i, p in enumerate(parts):
        p = np.asarray(p, dtype=np.int32)
        idx[i, :len(p)] = p
        length[i] = len(p)
    return jnp.asarray(idx), jnp.asarray(length)


def sample_member_batch(key, part_idx, part_len, members, batch: int):
    """Fixed-shape federated batch selection for one cluster round.

    members: (M,) device ids, possibly holding the out-of-range padding
    sentinel n (gathers fill, so padded rows draw from client 0's shard and
    are masked downstream).  Each member samples ``batch`` indices with
    replacement from its own shard under a per-member key
    ``fold_in(key, id)`` — the stream depends only on (key, id, shard), so
    padded and exact-shape execution draw identical batches.

    -> (M, batch) int32 row indices into the dataset.
    """
    def one(m):
        k = jax.random.fold_in(key, m)
        n_i = part_len.at[m].get(mode="fill", fill_value=1)
        sel = jax.random.randint(k, (batch,), 0, jnp.maximum(n_i, 1))
        row = part_idx.at[m].get(mode="fill", fill_value=0)
        return row[sel]

    return jax.vmap(one)(members)


def federated_batches(key, x, y, parts, batch: int):
    """Sample one (n_clients, batch, ...) federated batch."""
    n = len(parts)
    keys = jax.random.split(key, n)
    xs, ys = [], []
    for k, ix in zip(keys, parts):
        sel = jax.random.choice(k, jnp.asarray(ix), (batch,),
                                replace=len(ix) < batch)
        xs.append(x[sel])
        ys.append(y[sel])
    return jnp.stack(xs), jnp.stack(ys)
