"""Plain reference of DAGM (arXiv:2211.04088, Algorithm 2) on a ring.

Per-agent objectives f_i, g_i; stacked iterates x (n, d1), y (n, d2).
Ring gossip with Metropolis weights: every agent has two neighbours and
w_ii = w_ij = 1/3, so (W z)_i = (z_{i-1} + z_i + z_{i+1}) / 3.  Each
outer round k:

    y ← W y − β ∇_y g(x, y)                         M times
    p = ∇_y f(x, ỹ),  D = β c + 2 (1 − w_ii)        c bounds λmax ∇²_y g
    h ← −p / D;  h ← (D h − (I−W) h − β ∇²_y g h − p) / D    U times
    x ← x − α (γ (I−W) x + ∇_x f(x, ỹ) + β ∇²_xy g h),  γ = 1/α

Every product runs at the precision the caller sets (`precision`), and
every array at `dtype`: the reference is float32 at "highest"; its
control is the same code in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

W_SELF = 1.0 / 3.0


def ring_mix(z):
    return (jnp.roll(z, 1, axis=0) + z + jnp.roll(z, -1, axis=0)) / 3


def run(f, g, data, x0, y0, *, alpha, beta, K, M, U, curvature,
        precision="highest", dtype=jnp.float32):
    """K rounds from (x0, y0); returns the final (x, y) in float32."""
    cast = lambda t: jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, t)
    with jax.default_matmul_precision(precision):
        return _run(f, g, cast(data), cast(x0), cast(y0), dtype(alpha),
                    dtype(beta), dtype(curvature), K, M, U)


@functools.partial(jax.jit, static_argnums=(0, 1, 8, 9, 10))
def _run(f, g, data, x0, y0, alpha, beta, curvature, K, M, U):
    grad_y_g = jax.vmap(jax.grad(g, argnums=1))
    grad_y_f = jax.vmap(jax.grad(f, argnums=1))
    grad_x_f = jax.vmap(jax.grad(f, argnums=0))

    def hvp(x, y, v):
        def one(xi, yi, di, vi):
            return jax.jvp(lambda yy: jax.grad(g, argnums=1)(xi, yy, di),
                           (yi,), (vi,))[1]
        return jax.vmap(one)(x, y, data, v)

    def cross(x, y, h):
        def one(xi, yi, di, hi):
            return jax.grad(lambda xx: jnp.vdot(
                jax.grad(g, argnums=1)(xx, yi, di), hi))(xi)
        return jax.vmap(one)(x, y, data, h)

    D = beta * curvature + 2 * (1 - jnp.asarray(W_SELF, alpha.dtype))
    gamma = 1 / alpha

    def round_(carry, _):
        x, y = carry
        for _ in range(M):
            y = ring_mix(y) - beta * grad_y_g(x, y, data)
        p = grad_y_f(x, y, data)
        h = -p / D
        for _ in range(U):
            h = (D * h - (h - ring_mix(h)) - beta * hvp(x, y, h) - p) / D
        step = gamma * (x - ring_mix(x)) + grad_x_f(x, y, data) \
            + beta * cross(x, y, h)
        return (x - alpha * step, y), None

    (x, y), _ = jax.lax.scan(round_, (x0, y0), None, length=K)
    return x.astype(jnp.float32), y.astype(jnp.float32)
