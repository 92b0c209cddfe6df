"""Pallas TPU kernel: trust-weighted parameter aggregation (paper Eqn 6/19).

The aggregation hot spot of the framework: reduce C client parameter vectors
into one, weighted by normalized trust.  A naive jnp einsum sweeps HBM once
per client; this kernel streams one (C, block) tile through VMEM per grid
step and emits the weighted sum in a single pass — HBM traffic = C·N reads +
N writes, compute on the VPU, no MXU needed.

Tiling: grid over N // block; each instance holds a (rows, block) tile +
the (rows, 1) weight column in VMEM, where rows is C (or C + B for the
global kernel).  `lane_block` sizes block from rows: the tile is
double-buffered and the weighted product is a temporary of the same shape,
so each tile gets `TILE_BYTES` (an eighth of v5e's 16 MiB default scoped
VMEM limit).  That is 8192 lanes up to 64 rows, 1024 at 512 rows and the
128-lane floor from 4096 rows on.  Compiled for v5e at the paper's
N = 159,010, the floor still fits 12,000 rows; at 16,000 rows the (C, N)
operand itself outgrows the chip's 16 GB of HBM first.

The masked variant takes an extra (C,) validity column so *padded* client
rows (ragged cluster memberships run as fixed-shape grids in the fused
`FleetState` round) contribute exactly zero: the kernel multiplies the
weight column by the mask before the reduction, keeping one compiled grid
shape for every cluster regardless of its true membership count.

``trust_aggregate_global`` extends the grid with the cluster batch dim the
engine's aggregation path needs: each (B + C, block) step reduces the C
member updates of the round's cluster (Eqn 6) *and* substitutes the result
into the (B, block) stacked-cluster tile for the Eqn-19 staleness-weighted
global average — one VMEM pass instead of kernel + jnp re-read, and the
unit the placement layer partitions per shard.

Each ``pallas_call`` carries a fixed ``name`` (``trust_aggregate``,
``trust_aggregate_masked``, ``trust_aggregate_global``), which the chip's
profiler trace names the kernel's operation by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_BYTES = 2 << 20
MAX_BLOCK = 8192


def lane_block(rows: int, n: int) -> int:
    """Lanes per grid step for a (rows, n) f32 stream: the largest multiple
    of 128 whose (rows, block) tile fits `TILE_BYTES`, at most `MAX_BLOCK`
    and no wider than n rounded up to 128.  Every lane reduces on its own,
    so the block changes the tiling and never the result."""
    fit = TILE_BYTES // (4 * rows) // 128 * 128
    return max(128, min(MAX_BLOCK, fit, -(-n // 128) * 128))


def _kernel(w_ref, x_ref, o_ref):
    # x_ref: (C, block); w_ref: (C, 1); o_ref: (1, block) — a 2-D output
    # row, so a vmapped call's (batch, 1, block) blocks stay tileable
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # (C, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def _masked_kernel(w_ref, m_ref, x_ref, o_ref):
    # identical reduction with the weight column zeroed at padded rows
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32) * m_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def _global_kernel(c_ref, w_ref, m_ref, gw_ref, x_ref, s_ref, o_ref):
    # x_ref: (C, block) member updates; s_ref: (B, block) cluster stack;
    # w_ref/m_ref: (C, 1) weights/mask; gw_ref: (B, 1) Eqn-19 staleness
    # weights; c_ref: (1, 1) i32 index of the cluster being updated, a
    # whole-array VMEM block read once per step.
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32) * m_ref[...].astype(jnp.float32)
    agg = jnp.sum(x * w, axis=0)                       # Eqn 6, (block,)
    s = s_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
    s = jnp.where(rows == c_ref[0, 0], agg[None, :], s)
    gw = gw_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(s * gw, axis=0,                      # Eqn 19
                         keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def trust_aggregate(params_flat, weights, mask=None, *,
                    interpret: bool = False):
    """(C, N) x (C,) -> (N,).  N is padded to a multiple of the lane block.

    ``mask`` (C,) marks valid client rows; None means all rows are valid
    (the dense kernel).  Masked and dense agree exactly when the masked-out
    rows carry zero weight — the kernel-equivalence property test pins it.
    """
    C, N = params_flat.shape
    block = lane_block(C, N)
    pad = (-N) % block
    x = jnp.pad(params_flat, ((0, 0), (0, pad))) if pad else params_flat
    Np = N + pad
    grid = (Np // block,)
    out_spec = pl.BlockSpec((1, block), lambda i: (0, i))
    out_shape = jax.ShapeDtypeStruct((1, Np), params_flat.dtype)
    w_spec = pl.BlockSpec((C, 1), lambda i: (0, 0))
    x_spec = pl.BlockSpec((C, block), lambda i: (0, i))
    if mask is None:
        out = pl.pallas_call(
            _kernel, grid=grid, in_specs=[w_spec, x_spec],
            out_specs=out_spec, out_shape=out_shape, interpret=interpret,
            name="trust_aggregate",
        )(weights[:, None], x)
    else:
        out = pl.pallas_call(
            _masked_kernel, grid=grid,
            in_specs=[w_spec, pl.BlockSpec((C, 1), lambda i: (0, 0)), x_spec],
            out_specs=out_spec, out_shape=out_shape, interpret=interpret,
            name="trust_aggregate_masked",
        )(weights[:, None], mask.astype(jnp.float32)[:, None], x)
    return out[0, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def trust_aggregate_global(updates_flat, weights, mask, stack_flat,
                           global_weights, c, *, interpret: bool = False):
    """Fused Eqn 6 + Eqn 19: member updates -> the post-round global model.

    (C, N) member updates with (C,) weights/mask reduce to the round
    cluster's aggregate, which replaces row ``c`` of the (B, N) stacked
    cluster parameters before the (B,) staleness-weighted global average —
    all inside one grid pass over N.  Returns the (N,) global vector (the
    async-pull engine writes it back to both ``global_params`` and row
    ``c`` of the stack, so the intermediate Eqn-6 aggregate never
    round-trips through HBM).
    """
    C, N = updates_flat.shape
    B, Ns = stack_flat.shape
    assert Ns == N, (Ns, N)
    block = lane_block(C + B, N)
    pad = (-N) % block
    if pad:
        updates_flat = jnp.pad(updates_flat, ((0, 0), (0, pad)))
        stack_flat = jnp.pad(stack_flat, ((0, 0), (0, pad)))
    Np = N + pad
    col = lambda r: pl.BlockSpec((r, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        _global_kernel, grid=(Np // block,),
        in_specs=[col(1), col(C), col(C), col(B),
                  pl.BlockSpec((C, block), lambda i: (0, i)),
                  pl.BlockSpec((B, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), stack_flat.dtype),
        interpret=interpret, name="trust_aggregate_global",
    )(jnp.asarray(c, jnp.int32).reshape(1, 1), weights[:, None],
      mask.astype(jnp.float32)[:, None], global_weights[:, None],
      updates_flat, stack_flat)
    return out[0, :N]
