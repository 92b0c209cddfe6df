"""Pallas TPU kernel: Mamba-1 selective scan (falcon-mamba-7b hot loop).

TPU adaptation of the CUDA selective-scan: instead of warp-level parallel
prefix sums, channels are tiled over the grid — each kernel instance owns a
(BD,) slice of d_inner for one batch element, keeps its (BD, N) state
resident in VMEM, and walks the sequence with a fori_loop.  HBM traffic is
one linear sweep over the (S, BD) inputs/outputs; the O(S·BD·N) state
updates never leave VMEM (the jnp fallback materializes (B,S,Di,N)-shaped
intermediates in HBM on the backward path).

Grid: (B, Di // BD); BD = 512 keeps state + per-step operands << VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(xc_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, h_ref, *,
            seq_len: int):
    # xc,dt: (1, S, BD); b,c: (1, S, N); a: (BD, N); y: (1, S, BD)
    h_ref[...] = jnp.zeros_like(h_ref)                 # (BD, N) fp32
    A = a_ref[...].astype(jnp.float32)

    def step(t, _):
        xc_t = xc_ref[0, t, :].astype(jnp.float32)     # (BD,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)     # (BD,)
        B_t = b_ref[0, t, :].astype(jnp.float32)       # (N,)
        C_t = c_ref[0, t, :].astype(jnp.float32)       # (N,)
        dA = jnp.exp(dt_t[:, None] * A)                # (BD, N)
        h = dA * h_ref[...] + (dt_t * xc_t)[:, None] * B_t[None, :]
        h_ref[...] = h
        y_ref[0, t, :] = (h @ C_t).astype(y_ref.dtype)  # (BD,)
        return ()

    jax.lax.fori_loop(0, seq_len, step, ())
    hout_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def selective_scan(xc, dt, Bc, Cc, A, *, bd: int = 512,
                   interpret: bool = False):
    """xc,dt: (B,S,Di); Bc,Cc: (B,S,N); A: (Di,N)
    -> (y (B,S,Di), h_last (B,Di,N))."""
    B, S, Di = xc.shape
    N = A.shape[1]
    bd = min(bd, Di)
    assert Di % bd == 0, (Di, bd)
    kernel = functools.partial(_kernel, seq_len=S)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, Di // bd),
        in_specs=[
            pl.BlockSpec((1, S, bd), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, S, bd), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, S, N), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, N), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bd, N), lambda b, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, bd), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, bd, N), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Di), xc.dtype),
            jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xc, dt, Bc, Cc, A)
    return y, h
