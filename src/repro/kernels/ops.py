"""Jit'd public entry points for the Pallas kernels.

`pallas_mode(True)` (a context manager) switches the hot paths from the
pure-jnp oracles (CPU default / dry-run path) to the Pallas kernels for
the duration of the `with` block, restoring the previous mode on exit —
no state leaks between tests.  `use_pallas(...)` remains as the
imperative form for scripts that flip the mode for a whole process.

Whether a Pallas kernel runs in interpret mode is decided in one place,
`pallas_interpret()`: the platform decides — compiled on a TPU,
interpreted everywhere else (the CPU) — unless a caller passes an
explicit ``interpret=`` (tests).  Every kernel and every `interpret`
field (`MixingSpec.interpret`, `MixingOp(interpret=)`) defaults to
None, meaning "let the platform decide".  The kernels resolve None
inside their jit, so the answer may depend only on the platform, which
cannot change within a process: no global setting can make a cached
trace stale.

`repro.topology.ops.MixingOp` consults `pallas_enabled()` so that
flipping this one switch upgrades every circulant / sparse-gather
mixing mat-vec in the DAGM hot loop to the Pallas backend as well.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from . import ref

_USE_PALLAS = False


def use_pallas(enabled: bool) -> None:
    """Imperative mode switch (whole-process scripts; tests should use
    `pallas_mode`)."""
    global _USE_PALLAS
    _USE_PALLAS = enabled


def pallas_enabled() -> tuple[bool, bool]:
    """(enabled, interpret) — read by MixingOp's "auto" backend."""
    return _USE_PALLAS, pallas_interpret()


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Effective interpret flag: an explicit `interpret` wins, else the
    platform — compiled on a TPU, interpreted on any other backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def pallas_mode(enabled: bool):
    """Scoped Pallas toggle: `with pallas_mode(True): ...` runs the
    block with Pallas kernels enabled and restores the previous state
    on exit, exception or not."""
    global _USE_PALLAS
    saved = _USE_PALLAS
    _USE_PALLAS = enabled
    try:
        yield
    finally:
        _USE_PALLAS = saved


def ring_laplacian(y, w_self: float, w_edge: float):
    """(I−W)Y for ring W — DAGM/DIHGP mixing primitive; y (n, d)."""
    from .mixing_matvec import ring_laplacian_matvec
    # dtype-aware sublane minimum — must agree with MixingOp._pallas_ok
    # (bf16 stripes need 16 sublanes on TPU, f32 needs 8)
    sub = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}.get(
        jnp.dtype(y.dtype))
    if _USE_PALLAS and sub is not None and y.ndim == 2 \
            and y.shape[0] % sub == 0 and y.shape[1] % 128 == 0:
        return ring_laplacian_matvec(y, w_self=w_self, w_edge=w_edge,
                                     interpret=pallas_interpret())
    return ref.ring_laplacian_ref(y, w_self, w_edge)


def attention(q, k, v, *, causal: bool = True, window: int = 0):
    """Softmax attention (same-head-count q/k/v)."""
    from .flash_attention import flash_attention
    if _USE_PALLAS and q.shape[1] % 128 == 0:
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=pallas_interpret())
    return ref.attention_ref(q, k, v, causal=causal, window=window)


def wkv(r, k, v, logw, u, *, chunk: int = 64):
    """RWKV6 WKV mix."""
    from .rwkv6_scan import rwkv6_scan
    if _USE_PALLAS and r.shape[1] % chunk == 0:
        return rwkv6_scan(r, k, v, logw, u, chunk=chunk,
                          interpret=pallas_interpret()).astype(jnp.float32)
    return ref.rwkv6_ref(r, k, v, logw, u)[0]
