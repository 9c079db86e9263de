"""Pallas TPU flash attention (online-softmax, block-tiled).

Used by the 32k-prefill hot path on the TPU target.  Grid is
(batch·heads, q_blocks, kv_blocks) with the kv dimension *sequential*
(TPU grid semantics), carrying running max `m`, normalizer `l` and
accumulator in VMEM scratch across kv steps — the canonical
flash/splash-attention schedule.  Causal and sliding-window masks are
applied blockwise; fully-masked kv blocks still execute (no early-exit
in interpret mode) but contribute zeros.

Block shapes default to (128, 128) q×kv tiles — MXU-aligned on the
(tile × head_dim) matmuls.  Oracle: ref.attention_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import pallas_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            num_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # (bq, bk)
    correction = jnp.exp(m_prev - m_new)              # (bq, 1)
    l_new = correction * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * correction + p @ v
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq",
                                             "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """q/k/v: (B, S, H, hd) (same head count — GQA is pre-broadcast).
    Returns (B, S, H, hd)."""
    interpret = pallas_interpret(interpret)
    B, S, H, hd = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / math.sqrt(hd)
    nq, nk = S // bq, S // bk

    def flat(x):   # (B,S,H,hd) -> (B*H, S, hd)
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    qf, kf, vf = flat(q), flat(k), flat(v)
    grid = (B * H, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, num_kv=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
