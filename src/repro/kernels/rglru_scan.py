"""Pallas TPU kernel: RG-LRU gated linear recurrence (recurrentgemma-2b).

h_t = a_t * h_{t-1} + bx_t, elementwise over the LRU width.  Channels tile
over the grid; the (BW,) state stays in VMEM across the sequence walk.
Gates a/bx are precomputed by the surrounding block (they are dense matmuls
that belong on the MXU via XLA); the kernel is the serial dependency only.

Grid: (B, W // BW).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(a_ref, bx_ref, y_ref, hout_ref, h_ref, *, seq_len: int):
    h_ref[...] = jnp.zeros_like(h_ref)                 # (1, BW) fp32

    def step(t, _):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        bx_t = bx_ref[0, t, :].astype(jnp.float32)
        h = a_t * h_ref[0] + bx_t
        h_ref[0] = h
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, seq_len, step, ())
    hout_ref[0] = h_ref[0]


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def rglru_scan(a, bx, *, bw: int = 1024, interpret: bool = False):
    """a, bx: (B,S,W) -> (hs (B,S,W), h_last (B,W))."""
    B, S, W = a.shape
    bw = min(bw, W)
    assert W % bw == 0, (W, bw)
    kernel = functools.partial(_kernel, seq_len=S)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, W // bw),
        in_specs=[
            pl.BlockSpec((1, S, bw), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, S, bw), lambda b, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, bw), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, bw), lambda b, i: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), a.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, bx)
    return y, h
