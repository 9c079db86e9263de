"""Decentralized hyper-parameter optimization (paper §6.1, Fig. 3).

Each of n agents holds a private shard of a classification dataset and
tunes per-feature regularization strengths x (via exp(x), so they stay
positive) for a softmax classifier trained decentralized:

    inner  g_i(x, y) = CE(y; D_i^train) + yᵀ diag(exp(x)) y
    outer  f_i(x, y) = CE(y; D_i^val)

    PYTHONPATH=src python examples/decentralized_hyperopt.py \
        [--loss softmax|svm|logistic] [--agents 20] [--rounds 150]
"""
import argparse

import numpy as np

from repro.core import make_network
from repro.core.problems import ho_logistic, ho_softmax, ho_svm
from repro.solve import dagm_spec, solve

MAKERS = {"softmax": lambda n, s: ho_softmax(n, d=16, n_classes=10,
                                             m_per=30, seed=s),
          "svm": lambda n, s: ho_svm(n, d=16, m_per=30, seed=s),
          "logistic": lambda n, s: ho_logistic(n, d=16, m_per=30, seed=s)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--loss", default="softmax", choices=sorted(MAKERS))
    ap.add_argument("--agents", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--inner-steps", type=int, default=5)
    ap.add_argument("--neumann-order", type=int, default=3,
                    help="U — paper uses 3")
    args = ap.parse_args()

    net = make_network("erdos_renyi", args.agents, r=0.5, seed=0)
    prob = MAKERS[args.loss](args.agents, 0)
    spec = dagm_spec(alpha=0.05, beta=0.05, K=args.rounds,
                     M=args.inner_steps, U=args.neumann_order)
    res = solve(prob, net, spec)

    obj = np.asarray(res.metrics["outer_obj"])
    print(f"loss={args.loss} n={args.agents} sigma={net.sigma:.3f}")
    print(f"validation loss: {obj[0]:.4f} -> {obj[-1]:.4f}")
    print(f"consensus_x: {float(res.metrics['consensus_x'][-1]):.2e}")
    xbar = np.asarray(res.x).mean(0)
    print(f"learned log-regularizers: mean={xbar.mean():.3f} "
          f"min={xbar.min():.3f} max={xbar.max():.3f}")
    assert obj[-1] < obj[0], "validation loss should improve"
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
