"""Hyperopt-as-a-service demo: a (α, β) × topology sweep of paper-§6.1
hyper-parameter-optimization jobs served by the always-on
`repro.serve.admission.AdmissionLoop`.

Each job is one small independent DAGM instance (regularized linear
regression, per-job data shard and penalty/step-size point — half the
grid runs decaying alpha_k ~ 1/sqrt(k) schedules, which share the same
bucket/compile as the constant jobs because schedules are runtime
operands).  Where the wave-mode engine would take the whole grid up
front and drain it in one `run()`, this demo exercises the service
pattern: a background feeder thread submits sweep points on a
schedule (as a hyperopt driver proposing trials would), jobs join live
buckets at chunk boundaries, and the main thread consumes results
*as they retire* via `as_completed` — printing each topology's running
best the moment it improves, not after the queue drains.

    PYTHONPATH=src python examples/serve_hyperopt.py \
        [--grid 4] [--agents 8] [--dim 16] [--rounds 40] \
        [--chunk-rounds 10] [--max-width 64] [--hp-mode traced] \
        [--submit-hz 200]
"""
import argparse
import dataclasses
import threading
import time

import numpy as np

from repro.optim import inverse_sqrt_schedule
from repro.serve import JobSpec
from repro.serve.admission import AdmissionLoop
from repro.solve import ScheduleSpec, dagm_spec


def build_specs(args) -> list[JobSpec]:
    base = dagm_spec(alpha=0.02, beta=0.02, K=args.rounds, M=5, U=3,
                     dihgp="matrix_free", curvature=60.0)
    alphas = np.linspace(0.008, 0.02, args.grid)
    betas = np.linspace(0.008, 0.02, args.grid)

    specs = []
    for graph in ("ring", "erdos_renyi"):
        gkw = {"r": 0.4, "seed": 0} if graph == "erdos_renyi" else {}
        for i, a in enumerate(alphas):
            for j, b in enumerate(betas):
                # half the grid sweeps constants, half the decaying
                # alpha_k = a/sqrt(k) schedule — same compile signature,
                # so ALL of them share one bucket (and, in traced mode,
                # one compiled program)
                alpha = float(a) if (i + j) % 2 == 0 else \
                    inverse_sqrt_schedule(float(a))
                specs.append(JobSpec(
                    "ho_regression",
                    {"n": args.agents, "d": args.dim, "m_per": 10,
                     "seed": 17},
                    dataclasses.replace(base, schedule=ScheduleSpec(
                        alpha=alpha, beta=float(b))),
                    graph=graph, graph_kwargs=gkw, seed=3,
                    tol=args.tol,
                    job_id=f"{graph}/a{a:.3f}/b{b:.3f}"))
    return specs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=4,
                    help="sweep side: grid x grid (alpha, beta) points")
    ap.add_argument("--agents", type=int, default=8)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--chunk-rounds", type=int, default=10)
    ap.add_argument("--max-width", type=int, default=64)
    ap.add_argument("--hp-mode", default="traced",
                    choices=("traced", "static"))
    ap.add_argument("--tol", type=float, default=None,
                    help="early-retirement threshold on the Eq. (17b) "
                         "hyper-gradient estimate (norm squared)")
    ap.add_argument("--submit-hz", type=float, default=200.0,
                    help="feeder thread's submission rate (trials/s)")
    args = ap.parse_args()

    specs = build_specs(args)
    n_jobs = len(specs)
    ids: list[str] = [s.job_id for s in specs]

    t0 = time.perf_counter()
    with AdmissionLoop(chunk_rounds=args.chunk_rounds,
                       max_width=args.max_width,
                       hp_mode=args.hp_mode) as loop:
        # the hyperopt driver: a background schedule of trial submits
        # landing while earlier trials are already in flight
        def feeder():
            gap = 1.0 / args.submit_hz
            for spec in specs:
                loop.submit(spec)
                time.sleep(gap)

        threading.Thread(target=feeder, daemon=True).start()

        # consume results as they retire — running best per topology
        by_graph: dict[str, object] = {}
        results = []
        for res in loop.as_completed(ids, timeout=600):
            results.append(res)
            graph = res.job_id.split("/", 1)[0]
            best = by_graph.get(graph)
            if best is None or res.final_gap < best.final_gap:
                by_graph[graph] = res
                print(f"[serve] new best {graph}: {res.job_id}  "
                      f"gap={res.final_gap:.3e}  rounds={res.rounds}  "
                      f"({len(results)}/{n_jobs} retired)")
        wall = time.perf_counter() - t0
        stats = loop.stats

    print(f"[serve] {n_jobs} jobs ({args.grid}x{args.grid} grid x 2 "
          f"topologies), {stats.buckets} buckets, "
          f"{stats.traces} traces, {stats.chunks} chunks")
    print(f"[serve] {wall:.2f}s wall -> {n_jobs / wall:.1f} jobs/s "
          f"(hp_mode={args.hp_mode}, async admission)")
    for graph, res in by_graph.items():
        print(f"[serve] best {graph}: {res.job_id}  "
              f"gap={res.final_gap:.3e}  rounds={res.rounds}  "
              f"wire={res.wire_bytes / 1e3:.1f} kB")

    total_bytes = sum(r.wire_bytes for r in results)
    assert len(results) == n_jobs
    assert all(np.isfinite(r.final_gap) for r in results)
    print(f"[serve] total gossip: {total_bytes / 1e6:.2f} MB across "
          f"{sum(sum(r.sends.values()) for r in results)} sends")
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
