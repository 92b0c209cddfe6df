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


def _leaf_order(leaves):
    """The flat matrix's column order: largest leaves first (stable), so
    that the big weight blocks start on lane-aligned columns and the
    small bias blocks take the unaligned offsets."""
    return sorted(range(len(leaves)), key=lambda i: -leaves[i][0].size)


def _to_rows(x):
    """(C, ...) -> (C, n), each row's weight matrix with its last two axes
    swapped.  The chip keeps a stack of weight matrices with the
    second-to-last axis minor (the batched local-SGD matmuls write it so),
    so the swap is a bitcast and flattening is one relayout, not a
    transpose and then a relayout."""
    return (x.swapaxes(-1, -2) if x.ndim >= 3 else x).reshape(x.shape[0], -1)


def _from_row(v, like):
    """`_to_rows` undone for one row ``v`` of the (C, ...) leaf ``like``."""
    s = like.shape[1:]
    if len(s) < 2:
        return v.reshape(s)
    return v.reshape(s[:-2] + (s[-1], s[-2])).swapaxes(-1, -2)


def flatten_rows(tree, width=None):
    """A pytree with a leading row dim C as one (C, width) f32 matrix: the
    leaves in `_leaf_order`, each as `_to_rows` lays it out, then zeros up
    to ``width`` (default: no tail).  `unflatten_row` is its inverse for
    one row."""
    leaves = jax.tree.leaves(tree)
    cols = [_to_rows(leaves[i]).astype(jnp.float32)
            for i in _leaf_order(leaves)]
    tail = (width or 0) - sum(x.shape[1] for x in cols)
    if tail > 0:
        cols.append(jnp.zeros((cols[0].shape[0], tail), jnp.float32))
    return jnp.concatenate(cols, axis=1)


def unflatten_row(vec, like):
    """One row of `flatten_rows` (any zero tail ignored) back into the
    pytree structure of ``like`` without its leading row dim."""
    leaves, treedef = jax.tree.flatten(like)
    out, off = [None] * len(leaves), 0
    for i in _leaf_order(leaves):
        x = leaves[i]
        n = x[0].size
        out[i] = _from_row(vec[off:off + n], x).astype(x.dtype)
        off += n
    return jax.tree.unflatten(treedef, out)


def trust_aggregate_tree(client_params, weights, mask=None, *,
                         interpret=None):
    """Eqn 6 over a pytree with leading client dim, via the Pallas kernel.
    ``mask`` (C,) selects valid rows (padded fixed-shape cluster rounds)."""
    interpret = _interpret(interpret)
    agg = trust_aggregate(flatten_rows(client_params), weights, mask,
                          interpret=interpret)
    return unflatten_row(agg, client_params)


def trust_aggregate_global_tree(client_params, weights, mask, cluster_stack,
                                global_weights, c, *, interpret=None):
    """Fused Eqn 6 + Eqn 19 over pytrees: member updates (leading dim C)
    plus the stacked cluster parameters (leading dim n_clusters) -> the
    staleness-weighted global model, in one kernel pass.  ``c`` is the
    (traced) cluster whose Eqn-6 aggregate replaces its stack row."""
    return trust_aggregate_global_flat(
        flatten_rows(client_params), weights, mask, cluster_stack,
        global_weights, c, interpret=interpret)


def trust_aggregate_global_flat(updates_flat, weights, mask, cluster_stack,
                                global_weights, c, *, interpret=None):
    """`trust_aggregate_global_tree` with the member updates already one
    (C, W) matrix, ``flatten_rows(client_params, W)``.  Built at
    ``padded_width(C + n_clusters, N)`` columns it streams into the kernel
    as it is; the cluster stack is flattened to the same W here."""
    interpret = _interpret(interpret)
    stack_flat = flatten_rows(cluster_stack, updates_flat.shape[1])
    glob = trust_aggregate_global(updates_flat, weights, mask, stack_flat,
                                  global_weights, c, interpret=interpret)
    return unflatten_row(glob, cluster_stack)


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
