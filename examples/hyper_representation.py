"""Decentralized representation learning (paper §6.2, Fig. 4).

2-layer MLP on non-iid agent shards: the outer problem learns the shared
hidden-layer representation, the inner problem fits each agent's output
head.  Compares DAGM against DGBO / DGTBO / FedNest and reports the
per-round communication (the paper's Fig. 4 CPU-time story: DAGM wins
because it never ships matrices).

    PYTHONPATH=src python examples/hyper_representation.py [--rounds 60]
"""
import argparse
import time

import numpy as np

from repro.core import (DAGMConfig, dagm_run, dgbo_run, dgtbo_run,
                        fednest_run, make_network)
from repro.core.problems import hyper_representation, hyperrep_accuracy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--hidden", type=int, default=40)
    args = ap.parse_args()

    net = make_network("erdos_renyi", args.agents, r=0.5, seed=0)
    prob = hyper_representation(args.agents, d=20, hidden=args.hidden,
                                n_classes=10, m_per=30, seed=0)
    print(f"outer dim d1={prob.d1}, inner dim d2={prob.d2}, "
          f"n={args.agents}")

    # x = the MLP hidden layer: the all-zeros default start is a dead
    # ReLU init (zero hyper-gradient) — every method starts from the
    # same small random backbone, as in the paper.
    import jax, jax.numpy as jnp
    x0 = jnp.broadcast_to(
        0.3 * jax.random.normal(jax.random.PRNGKey(42), (prob.d1,)),
        (args.agents, prob.d1)).astype(jnp.float32)

    results = {}
    t0 = time.time()
    res = dagm_run(prob, net, DAGMConfig(
        alpha=0.1, beta=0.1, K=args.rounds, M=5, U=3,
        dihgp="matrix_free"), x0=x0)
    results["DAGM"] = (hyperrep_accuracy(prob, np.asarray(res.x),
                                         np.asarray(res.y)),
                       time.time() - t0,
                       5 * prob.d2 + 3 * prob.d2 + prob.d1)

    for name, runner, kw in [("DGBO", dgbo_run, dict(b=3)),
                             ("DGTBO", dgtbo_run, dict(N=3)),
                             ("FedNest", fednest_run, dict(U=3))]:
        t0 = time.time()
        r = runner(prob, net, alpha=0.1, beta=0.1, K=args.rounds, M=5,
                   x0=x0, **kw)
        results[name] = (hyperrep_accuracy(prob, np.asarray(r.x),
                                           np.asarray(r.y)),
                         time.time() - t0, r.comm_floats_per_round)

    print(f"{'method':10s} {'val_acc':>8s} {'seconds':>8s} "
          f"{'floats/round':>13s}")
    for name, (acc, sec, comm) in results.items():
        print(f"{name:10s} {acc:8.3f} {sec:8.1f} {comm:13d}")
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
