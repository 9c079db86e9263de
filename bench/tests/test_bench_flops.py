"""The analytic FLOPs per round against XLA's own count of one round.

XLA's `cost_analysis()` counts the body of every while loop once.  The
round's M inner steps and U Neumann steps are `fori_loop`s, so XLA
counts one of each where `flops_per_round` counts M and U; the four
backbone products (train and validation features, and the two backward
products into W1) appear exactly once each in both.  So with M = U = 1
the two counts agree up to the elementwise work, and with the cells'
M = 5, U = 3 XLA's is lower by 16 head products.  The pinned ratios are
those of jax 0.9.0 on the CPU at these sizes."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from harness.spec import BENCH_DIR, load_module

HR = load_module(BENCH_DIR / "references" / "hyper_representation.py",
                 "bench_ref_hyper_representation")


def xla_flops_of_one_round(p: dict, s: dict) -> float:
    from repro.core import make_network
    from repro.core.dagm import RoundHP, dagm_init_carry, dagm_run_chunk
    from repro.core.mixing import make_mixing_op
    from repro.core.problems import hyper_representation
    from repro.solve import dagm_spec
    from repro.solve.spec import mixing_kwargs

    data = HR.make_data(jax.random.PRNGKey(0), p)
    prob = hyper_representation(
        p["n"], d=p["d"], hidden=p["hidden"], n_classes=p["n_classes"],
        m_per=2, ridge=p["ridge"]).with_data(data)
    spec = dagm_spec(K=1, dihgp="matrix_free", mixing="auto", **s)
    W = make_mixing_op(make_network("ring", p["n"]), **mixing_kwargs(spec))
    carry = dagm_init_carry(prob, W, spec,
                            HR.init_x(jax.random.PRNGKey(1), p), None, 0)
    hp = RoundHP(*(jnp.full((1,), v, jnp.float32)
                   for v in (s["alpha"], s["beta"], 1 / s["alpha"])))
    step = jax.jit(lambda c, hp, dt: dagm_run_chunk(
        prob.with_data(dt), W, spec, c, 1, hp=hp))
    cost = step.lower(carry, hp, data).compile().cost_analysis()
    return float((cost[0] if isinstance(cost, list) else cost)["flops"])


@pytest.mark.parametrize("M,U,ratio", [(1, 1, 0.9994), (5, 3, 0.9528)])
def test_analytic_flops_against_xla(M, U, ratio):
    p = dict(n=2, d=784, hidden=200, n_classes=10, m_per=512, margin=2.0,
             ridge=0.01)
    s = dict(alpha=0.5, beta=0.1, M=M, U=U, curvature=28.16)
    got = xla_flops_of_one_round(p, s) / HR.flops_per_round(p, s)
    assert got == pytest.approx(ratio, abs=5e-4)


@pytest.mark.parametrize("name", ["hyperrep_mnist16", "hyperrep_mnist4"])
def test_config_file_holds_the_analytic_count(name):
    cfg = json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())
    assert cfg["flops_per_round"] == HR.flops_per_round(cfg["problem"],
                                                        cfg["solver"])
