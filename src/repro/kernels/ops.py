"""Public jit'd wrappers for the Pallas kernels.

With ``interpret=None`` (the default) each call picks the mode from the
default backend: the Pallas interpreter on the CPU, which has no Mosaic
compiler, and the compiled kernel anywhere else.  The choice is made when
the wrapper is called, so importing this module starts no JAX backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .rglru_scan import rglru_scan
from .selective_scan import selective_scan
from .trust_aggregate import trust_aggregate, trust_aggregate_global


def _interpret(flag):
    return jax.default_backend() == "cpu" if flag is None else flag


def _flatten_rows(tree):
    leaves, treedef = jax.tree.flatten(tree)
    C = leaves[0].shape[0]
    flat = jnp.concatenate(
        [x.reshape(C, -1).astype(jnp.float32) for x in leaves], axis=1)
    return flat, leaves, treedef


def _unflatten_row(vec, leaves, treedef):
    out, off = [], 0
    for x in leaves:
        n = x[0].size
        out.append(vec[off:off + n].reshape(x.shape[1:]).astype(x.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def trust_aggregate_tree(client_params, weights, mask=None, *,
                         interpret=None):
    """Eqn 6 over a pytree with leading client dim, via the Pallas kernel.
    ``mask`` (C,) selects valid rows (padded fixed-shape cluster rounds)."""
    interpret = _interpret(interpret)
    flat, leaves, treedef = _flatten_rows(client_params)
    agg = trust_aggregate(flat, weights, mask, interpret=interpret)
    return _unflatten_row(agg, leaves, treedef)


def trust_aggregate_global_tree(client_params, weights, mask, cluster_stack,
                                global_weights, c, *, interpret=None):
    """Fused Eqn 6 + Eqn 19 over pytrees: member updates (leading dim C)
    plus the stacked cluster parameters (leading dim n_clusters) -> the
    staleness-weighted global model, in one kernel pass.  ``c`` is the
    (traced) cluster whose Eqn-6 aggregate replaces its stack row."""
    interpret = _interpret(interpret)
    upd_flat, _, _ = _flatten_rows(client_params)
    stack_flat, leaves, treedef = _flatten_rows(cluster_stack)
    glob = trust_aggregate_global(upd_flat, weights, mask, stack_flat,
                                  global_weights, c, interpret=interpret)
    return _unflatten_row(glob, leaves, treedef)


def attention(q, k, v, *, window=0, softcap=0.0, bq=256, bk=256,
              interpret=None):
    interpret = _interpret(interpret)
    return flash_attention(q, k, v, window=window, softcap=softcap,
                           bq=bq, bk=bk, interpret=interpret)


def mamba_scan(xc, dt, Bc, Cc, A, *, bd=512, interpret=None):
    interpret = _interpret(interpret)
    return selective_scan(xc, dt, Bc, Cc, A, bd=bd, interpret=interpret)


def lru_scan(a, bx, *, bw=1024, interpret=None):
    interpret = _interpret(interpret)
    return rglru_scan(a, bx, bw=bw, interpret=interpret)
