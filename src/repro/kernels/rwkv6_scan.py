"""Pallas TPU kernel for the RWKV6 WKV recurrence (data-dependent decay).

    S_t = diag(w_t) S_{t-1} + k_tᵀ v_t
    o_t = r_t (S_{t-1} + diag(u) k_tᵀ v_t)

Grid: (B·H, T/chunk) with the chunk axis sequential; the (hd × hd) state
lives in VMEM scratch and is carried across chunk steps, so HBM traffic
is exactly one read of r/k/v/w and one write of o per token (the scan
state never round-trips).  Inside a chunk the recurrence is stepped with
an in-VMEM fori_loop of rank-1 updates (VPU FMA); hd = 64 keeps the
state at 16 KB — far under VMEM.

Oracle: ref.rwkv6_ref (lax.scan).  The model's forward pass uses the
oracle on CPU; this kernel is the TPU-target hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import pallas_interpret


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, S_scr, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        S_scr[...] = jnp.zeros_like(S_scr)

    u = u_ref[0]                                     # (hd,)

    def step(t, _):
        rt = r_ref[0, t, :].astype(jnp.float32)      # (hd,)
        kt = k_ref[0, t, :].astype(jnp.float32)
        vt = v_ref[0, t, :].astype(jnp.float32)
        lwt = lw_ref[0, t, :].astype(jnp.float32)
        S = S_scr[...]                               # (hd, hd)
        kv = kt[:, None] * vt[None, :]
        out = rt @ (S + u[:, None] * kv)             # (hd,)
        S_scr[...] = jnp.exp(lwt)[:, None] * S + kv
        o_ref[0, t, :] = out.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, logw, u, *, chunk: int = 64,
               interpret: bool | None = None):
    """r/k/v/logw: (B, T, H, hd); u: (H, hd).  Returns out (B, T, H, hd).

    T % chunk == 0 required (pad upstream)."""
    interpret = pallas_interpret(interpret)
    B, T, H, hd = r.shape
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    def flat(x):   # (B,T,H,hd) -> (B*H, T, hd)
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, hd)

    rf, kf, vf, lwf = map(flat, (r, k, v, logw))
    tile = pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B * H, nc),
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec((1, hd), lambda b, c: (b % H, 0))],
        out_specs=tile,
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), jnp.float32),
        interpret=interpret,
    )(rf, kf, vf, lwf, u)
    return out.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
