"""Plain reference of the hyper-representation problem (arXiv:2211.04088,
§6.2): a two-layer MLP whose hidden layer is the outer variable x and
whose output head is the inner variable y, per agent.

    features  = relu(Z W1 + b1)            x = [W1 (d×hidden), b1]
    logits    = features W2 + b2           y = [W2 (hidden×C), b2]
    g_i(x, y) = CE(train) + ridge/2 ‖y‖²   f_i(x, y) = CE(validation)

The data is the benchmark's own MNIST-shaped stand-in: C Gaussian
clusters in d dimensions (class means N(0, margin²), unit noise), labels
uniform, made on the device from the seed in one jitted call.  The
program under test is given the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sizes(p: dict) -> tuple[int, int]:
    """(d1, d2): outer and inner variable lengths per agent."""
    return p["d"] * p["hidden"] + p["hidden"], \
        p["hidden"] * p["n_classes"] + p["n_classes"]


def objectives(p: dict):
    """(f, g) per agent: (x_i, y_i, data_i) -> scalar."""
    d, hidden, C, ridge = p["d"], p["hidden"], p["n_classes"], p["ridge"]

    def features(x, Z):
        return jax.nn.relu(Z @ x[:d * hidden].reshape(d, hidden)
                           + x[d * hidden:])

    def cross_entropy(y, feats, labels):
        logits = feats @ y[:hidden * C].reshape(hidden, C) + y[hidden * C:]
        true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - true)

    def g(x, y, di):
        return cross_entropy(y, features(x, di["Ztr"]), di["ltr"]) \
            + 0.5 * ridge * jnp.sum(y * y)

    def f(x, y, di):
        return cross_entropy(y, features(x, di["Zval"]), di["lval"])

    return f, g


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _make_data(key, n, m_per, d, C, margin):
    k_mean, k_tr, k_val = jax.random.split(key, 3)
    means = margin * jax.random.normal(k_mean, (C, d), jnp.float32)

    def split(k):
        k_lab, k_noise = jax.random.split(k)
        labels = jax.random.randint(k_lab, (n, m_per), 0, C, jnp.int32)
        return means[labels] + jax.random.normal(
            k_noise, (n, m_per, d), jnp.float32), labels

    Ztr, ltr = split(k_tr)
    Zval, lval = split(k_val)
    return {"Ztr": Ztr, "ltr": ltr, "Zval": Zval, "lval": lval}


def make_data(key, p: dict) -> dict:
    """Train and validation splits of m_per rows per agent, on device."""
    return _make_data(key, p["n"], p["m_per"], p["d"], p["n_classes"],
                      float(p["margin"]))


def init_x(key, p: dict):
    """One random backbone, the same on every agent (x = 0 is a dead
    ReLU with zero hyper-gradient)."""
    d1, _ = sizes(p)
    x = jax.random.normal(key, (d1,), jnp.float32) / jnp.sqrt(
        jnp.float32(p["d"]))
    return jnp.broadcast_to(x, (p["n"], d1))


def flops_per_round(p: dict, solver: dict) -> float:
    """Matrix-multiply FLOPs one DAGM round needs, all agents together.

    N = n·m_per rows per split.  Per round x is fixed, so the train and
    validation features are each needed once (2·N·d·h each), and the
    outer gradients ∇ₓf and ∇ₓ⟨∇_y g, h⟩ each need one backward product
    into W1 (2·N·d·h each).  The head costs 2·N·h·C per product: M inner
    steps of forward and backward (2 each), ∇_y f (2), U Hessian-vector
    products (forward-over-backward: 4 each), the two outer gradients'
    head products (2 each, plus the backward into the features, 1 each)
    and the g and f values reported each round (shared with the above).
    Recomputation by the program is not counted."""
    N = p["n"] * p["m_per"]
    d, h, C = p["d"], p["hidden"], p["n_classes"]
    backbone = 4 * 2 * N * d * h
    head_products = 2 * solver["M"] + 2 + 4 * solver["U"] + 2 * 3
    return float(backbone + head_products * 2 * N * h * C)
