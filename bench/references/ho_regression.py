"""Plain reference of the hyper-parameter regression problem
(arXiv:2211.04088, §6.1), per agent:

    g_i(x, y) = mean((Z_tr y − b_tr)²) + Σ_j exp(x_j) y_j²
    f_i(x, y) = mean((Z_val y − b_val)²)

The program builds each job's data itself from the job's seed, so the
reference makes the same arrays by the same published recipe (§6.1
synthetic regression: z ~ N(0, I), b = z·w + 0.25·|z·w| + ε), written
out here in numpy from the seed.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def sizes(p: dict) -> tuple[int, int]:
    return p["d"], p["d"]


def objectives(p: dict):
    def g(x, y, di):
        r = di["Ztr"] @ y - di["btr"]
        return jnp.mean(r * r) + jnp.sum(jnp.exp(x) * y * y)

    def f(x, y, di):
        r = di["Zval"] @ y - di["bval"]
        return jnp.mean(r * r)

    return f, g


def make_data(seed: int, p: dict, noise: float = 0.25) -> dict:
    """One job's train and validation splits, (n, m_per, d) and (n, m_per)."""
    n, d, m = p["n"], p["d"], p["m_per"]
    rng = np.random.default_rng(seed)
    w = rng.standard_normal(d)
    Z = rng.standard_normal((n * m * 2, d))
    eps = rng.standard_normal(n * m * 2)
    b = Z @ w + noise * np.abs(Z @ w) + eps
    split = {"Ztr": Z[: n * m].reshape(n, m, d),
             "btr": b[: n * m].reshape(n, m),
             "Zval": Z[n * m:].reshape(n, m, d),
             "bval": b[n * m:].reshape(n, m)}
    return {k: v.astype(np.float32) for k, v in split.items()}
