"""Robust-aggregation baselines (the literature the paper positions
against: §II "aggregation strategy").

The paper's trust-weighted aggregation (Eqns 4-6) stands beside the
standard Byzantine-robust rules, each a registered aggregator:

  krum / multi-krum   (Blanchard et al., 2017)
  coordinate median   (Yin et al., 2018)
  trimmed mean        (Yin et al., 2018)
  fedavg              (unweighted mean — the vulnerable baseline)

All operate on a pytree with leading client dim, like
trust.trust_weighted_average.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _flat(tree):
    leaves = jax.tree.leaves(tree)
    C = leaves[0].shape[0]
    return jnp.concatenate([x.reshape(C, -1).astype(jnp.float32)
                            for x in leaves], axis=1)


def _unflat_like(vec, tree):
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for x in leaves:
        n = x[0].size
        out.append(vec[off:off + n].reshape(x.shape[1:]).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def krum_scores(flat, f: int):
    """Sum of distances to the C-f-2 nearest neighbours, per client."""
    C = flat.shape[0]
    d2 = jnp.sum((flat[:, None] - flat[None]) ** 2, axis=-1)     # (C,C)
    d2 = jnp.where(jnp.eye(C, dtype=bool), jnp.inf, d2)   # (0*inf = nan!)
    k = max(1, C - f - 2)
    nearest = jnp.sort(d2, axis=1)[:, :k]
    return nearest.sum(axis=1)


def krum(client_params, f: int = 1):
    """Select the single client closest to its neighbours (Krum)."""
    flat = _flat(client_params)
    best = jnp.argmin(krum_scores(flat, f))
    return jax.tree.map(lambda x: x[best], client_params)


def multi_krum(client_params, f: int = 1, m: int | None = None):
    """Average the m lowest-score clients (Multi-Krum)."""
    flat = _flat(client_params)
    C = flat.shape[0]
    m = m or max(1, C - f)
    scores = krum_scores(flat, f)
    sel = jnp.argsort(scores)[:m]
    mean = flat[sel].mean(axis=0)
    return _unflat_like(mean, client_params)


def coordinate_median(client_params):
    flat = _flat(client_params)
    return _unflat_like(jnp.median(flat, axis=0), client_params)


def masked_coordinate_median(client_params, mask):
    """Coordinate median over the ``mask``-valid client rows, at fixed shape.

    Padded rows are replaced with +inf so an ascending sort pushes them past
    the n valid entries; the median is then read at the traced indices
    (n-1)//2 and n//2 of the sorted prefix — the same two-middle average
    `jnp.median` takes on the compacted rows.  This is what lets `median`
    join the padded fused round (`supports_mask=True`) instead of compiling
    one exact-shape round per cluster size.
    """
    flat = _flat(client_params)
    big = jnp.where(mask[:, None], flat, jnp.inf)
    s = jnp.sort(big, axis=0)
    n = jnp.maximum(jnp.sum(mask.astype(jnp.int32)), 1)
    lo = jnp.take(s, (n - 1) // 2, axis=0)
    hi = jnp.take(s, n // 2, axis=0)
    return _unflat_like(0.5 * (lo + hi), client_params)


def trimmed_mean(client_params, beta: float = 0.2):
    """Drop the beta fraction of extremes per coordinate, then average."""
    flat = _flat(client_params)
    C = flat.shape[0]
    k = int(C * beta)
    s = jnp.sort(flat, axis=0)
    s = s[k:C - k] if C - 2 * k >= 1 else s
    return _unflat_like(s.mean(axis=0), client_params)


def masked_trimmed_mean(client_params, mask, beta: float = 0.2):
    """Trimmed mean over the ``mask``-valid client rows, at fixed shape.

    The same ±inf-padded-sort construction as `masked_coordinate_median`:
    padded rows sort past the n valid entries, so ranks [k, n-k) of the
    sorted prefix are exactly the coordinates `trimmed_mean` keeps on the
    compacted rows, with k = floor(n·beta) re-derived from the traced valid
    count (and the k = 0 fallback when trimming would drop everything).
    This gives `trimmed_mean` ``supports_mask=True``: one padded fused-round
    compile instead of one exact-shape compile per cluster size.
    """
    flat = _flat(client_params)
    big = jnp.where(mask[:, None], flat, jnp.inf)
    s = jnp.sort(big, axis=0)
    n = jnp.maximum(jnp.sum(mask.astype(jnp.int32)), 1)
    k = jnp.floor(n.astype(jnp.float32) * beta).astype(jnp.int32)
    k = jnp.where(n - 2 * k >= 1, k, 0)
    ranks = jnp.arange(s.shape[0], dtype=jnp.int32)[:, None]
    keep = ((ranks >= k) & (ranks < n - k)).astype(jnp.float32)
    mean = jnp.sum(jnp.where(keep > 0, s, 0.0), axis=0) / jnp.maximum(
        n - 2 * k, 1).astype(jnp.float32)
    return _unflat_like(mean, client_params)


AGGREGATORS = {
    "krum": krum,
    "multi_krum": multi_krum,
    "median": coordinate_median,
    "trimmed_mean": trimmed_mean,
}

# rules with a fixed-capacity masked variant: these can run on the engine's
# padded fixed-shape clusters (supports_mask=True) instead of forcing one
# exact-shape compile per cluster size
MASKED_AGGREGATORS = {
    "median": masked_coordinate_median,
    "trimmed_mean": masked_trimmed_mean,
}
