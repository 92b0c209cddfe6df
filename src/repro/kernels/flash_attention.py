"""Pallas TPU kernel: blockwise causal flash attention (online softmax).

Serves prefill_32k (quadratic scores never hit HBM) and the sliding-window
long-context variant.  TPU-native design: the MXU consumes (BQ, d) x (d, BK)
tiles; running max/sum/accumulator live in VMEM scratch that persists across
the minormost (arbitrary-semantics) KV grid dimension.

Grid: (B*H, S//BQ, S//BK), KV innermost.  Causal + window block skipping via
pl.when — fully-masked KV blocks are never computed (a 2x FLOP saving for
causal, ~S/window x for sliding windows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, window: int, softcap: float,
            n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level causal/window reachability
    reachable = k_start <= q_start + bq - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, k_start + bk - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (BQ, d)
        k = k_ref[0].astype(jnp.float32)              # (BK, d)
        v = v_ref[0].astype(jnp.float32)              # (BK, dv)
        s = (q @ k.T) * scale                         # (BQ, BK)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)               # (BQ, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bk", "window", "softcap", "interpret"))
def flash_attention(q, k, v, *, bq: int = 256, bk: int = 256,
                    window: int = 0, softcap: float = 0.0,
                    interpret: bool = False):
    """q,k: (B,S,H,d), v: (B,S,H,dv) -> (B,S,H,dv); causal (+window).

    H folds into the leading grid dim; within a (B*H) slice the kernel walks
    KV blocks with online softmax.  GQA callers repeat K/V heads first.
    """
    B, S, H, d = q.shape
    dv = v.shape[-1]
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = d ** -0.5
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, x.shape[-1])
    qf, kf, vf = fold(q), fold(k), fold(v)
    n_k = S // bk

    kernel = functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                               window=window, softcap=softcap, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running sum l
            pltpu.VMEM((bq, dv), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, dv).transpose(0, 2, 1, 3)
