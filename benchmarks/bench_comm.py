"""Compressed-gossip benchmark: bytes-to-suboptimality on the quadratic
bilevel problem (the repro.comm subsystem's acceptance harness).

Sweeps compressor spec × topology through `repro.solve` and records, per
run, the byte-accurate per-round traffic from the attached `CommLedger`
together with the true suboptimality trajectory gap_k = ‖∇Φ(x̄_k)‖²
(closed form: the quadratic problem's consensus inner solution is
y*(x) = S x + t with S = Ā⁻¹P̄, t = Ā⁻¹b̄, so ∇Φ(x̄) =
Sᵀ(y*(x̄) − c̄) + μ_f x̄ — one d2×d2 factorization for the whole trace).
Derived per row:

  * bytes_per_round / floats_per_round  — measured wire traffic,
  * reduction_x                         — f32 bytes / wire bytes,
  * final_gap, gap_vs_identity          — trajectory quality,
  * bytes_to_target                     — cumulative bytes until the
    gap first reaches 1.1× the *uncompressed* run's final gap (the
    "matched final gap" column: compression only counts if it still
    gets there).

Headline (checked-in JSON, ring topology): int8+EF cuts bytes/round
≈4× (3.98× exactly — the per-send bf16 scale+zero-point metadata is
charged, so 8-bit payloads bound the ratio just under 4) and int4+EF
7.9×, both at a final gap within 10% of the uncompressed run.

The `lm_bf16_drift` section runs examples/train_lm_dagm.py's `main`
twice (f32 vs bf16 gossip) in this process at the smoke size and
records the loss-curve delta — the measurement half of the ROADMAP
bf16-drift item.  Everything runs in one process: a child could not
reach a chip this process already holds.

Budgets: "smoke" (scripts/ci.sh tier 2: tiny dims, no sharded/LM rows,
no JSON rewrite), "small" (checked-in results), "full" (adds star/
larger-d2 rows).  JSON: benchmarks/results/bench_comm.json.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import make_network, quadratic_bilevel
from repro.solve import dagm_spec, solve

from .common import Row, timed

SMOKE_AWARE = True   # genuine cheap smoke tier (benchmarks.run contract)
RESULTS = os.path.join(os.path.dirname(__file__), "results",
                       "bench_comm.json")
WIRE_SPECS = ("identity", "bf16", "int8", "int4", "top_k:0.1",
              "rand_k:0.25")


def _gap_trace(prob, xbar_trace: np.ndarray) -> np.ndarray:
    """‖∇Φ(x̄_k)‖² for the whole (K, d1) trace, one factorization."""
    d = prob.data
    Abar = np.asarray(d["A"]).mean(0)
    Pbar = np.asarray(d["P"]).mean(0)
    bbar = np.asarray(d["b"]).mean(0)
    cbar = np.asarray(d["c"]).mean(0)
    S = np.linalg.solve(Abar, Pbar)                  # (d2, d1)
    t = np.linalg.solve(Abar, bbar)                  # (d2,)
    ystar = xbar_trace @ S.T + t                     # (K, d2)
    # mu_f = 0.1: the quadratic_bilevel default (the per-run hypergrad
    # cross-check in _dagm_case would catch a mismatch)
    grad = (ystar - cbar) @ S + 0.1 * xbar_trace
    return np.sum(grad ** 2, axis=-1)


def _xbar_metrics(prob, W, x, y):
    return {"xbar": jnp.mean(x, axis=0),
            "outer_obj": jnp.mean(prob.f_stacked(x, y))}


def _dagm_case(prob, net, spec: str, K: int, M: int, U: int,
               curvature: float, seed: int = 0):
    cfg = dagm_spec(alpha=0.05, beta=0.1, K=K, M=M, U=U,
                    dihgp="matrix_free", curvature=curvature,
                    comm=spec)
    # start far from stationarity (the default x0 = 0 is near the bias
    # floor already) so the bytes-to-target curve has a real descent
    x0 = jnp.broadcast_to(
        2.0 * jax.random.normal(jax.random.PRNGKey(7), (prob.d1,)),
        (prob.n, prob.d1)).astype(jnp.float32)
    res, us = timed(lambda: solve(prob, net, cfg, x0=x0,
                                  metrics_fn=_xbar_metrics,
                                  seed=seed), iters=1)
    gaps = _gap_trace(prob, np.asarray(res.metrics["xbar"]))
    # closed-form gap must agree with the problem's autodiff hypergrad
    check = float(jnp.sum(
        prob.hypergrad(jnp.asarray(res.metrics["xbar"][-1])) ** 2))
    assert abs(check - gaps[-1]) <= 1e-4 * max(check, 1e-12) + 1e-8, \
        (check, gaps[-1])
    return res, us, gaps


def _sweep(prob, net, specs, K, M, U, curvature, tag) -> list[Row]:
    rows, runs = [], {}
    for spec in specs:
        res, us, gaps = _dagm_case(prob, net, spec, K, M, U, curvature)
        runs[spec] = (res, us, gaps)
    id_res, _, id_gaps = runs["identity"]
    target = 1.1 * float(id_gaps[-1])
    id_bpr = id_res.ledger.bytes_per_round(K)
    for spec, (res, us, gaps) in runs.items():
        bpr = res.ledger.bytes_per_round(K)
        # bytes until the gap reaches the target *and stays there*
        above = np.nonzero(gaps > target)[0]
        if float(gaps[-1]) > target:
            to_target = None
        else:
            k = 0 if above.size == 0 else int(above[-1]) + 1
            to_target = int((k + 1) * bpr)
        derived = {
            "bytes_per_round": bpr,
            "floats_per_round": res.ledger.floats_per_round(K),
            "reduction_x": round(res.ledger.reduction_vs_f32(), 3),
            "final_gap": f"{float(gaps[-1]):.3e}",
            "gap_vs_identity": round(float(gaps[-1])
                                     / max(float(id_gaps[-1]), 1e-30), 3),
            "bytes_to_target": to_target,
            "bytes_reduction_vs_identity": round(id_bpr / bpr, 3),
        }
        rows.append(Row(f"comm/{tag}/{spec}", us, derived))
    return rows


def _sharded_ef_rows(rounds: int = 200) -> list[Row]:
    """Persistent vs per-round-reset EF replicas on the *sharded* tier
    (ROADMAP "EF state across outer rounds" item): the reference tier
    warm-starts its inner_y/outer_x replicas across the whole K-round
    scan, while the historical sharded step reopened its channels each
    round; `persist_ef` threads them as an extra carry.  One agent per
    device of this process, up to 8 (benchmarks.run gives the CPU eight
    host devices)."""
    from jax.sharding import Mesh
    from repro.distributed.dagm_sharded import (make_sharded_dagm,
                                                open_sharded_channels,
                                                sharded_comm_ledger)
    from repro.solve import sharded_spec
    devices = jax.devices()
    n = min(8, len(devices))
    if n < 2:
        raise RuntimeError(
            f"comm/sharded_ef needs at least 2 devices for a ring, found "
            f"{len(devices)} {devices[0].platform} device(s)")
    d1, d2 = 8, 128
    mesh = Mesh(np.array(devices[:n]), ("data",))
    prob = quadratic_bilevel(n, d1, d2, seed=0)
    curv = float(max(np.linalg.eigvalsh(np.asarray(prob.data["A"][i])).max()
                     for i in range(n)))
    x0 = jnp.broadcast_to(
        2.0 * jax.random.normal(jax.random.PRNGKey(7), (d1,)),
        (n, d1)).astype(jnp.float32)
    y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, d2))

    out = {}
    for label, spec, persist in (("identity", "identity", False),
                                 ("reset", "top_k:0.1+ef", False),
                                 ("persist", "top_k:0.1+ef", True)):
        cfg = sharded_spec(alpha=0.05, beta=0.1, M=5, U=3,
                           curvature=curv, comm=spec,
                           persist_ef=persist)
        step, _ = make_sharded_dagm(lambda x, y, b: prob.g(x, y, b),
                                    lambda x, y, b: prob.f(x, y, b),
                                    cfg, mesh)
        x, y = x0, y0
        if persist:
            cs = open_sharded_channels(cfg, x, y, seed=0)
            for _ in range(rounds):
                x, y, _m, cs = step(x, y, prob.data, cs)
        else:
            for _ in range(rounds):
                x, y, _m = step(x, y, prob.data)
        led = sharded_comm_ledger(cfg, x[0], y[0], rounds=1)
        out[label] = {
            "final_gap": float(jnp.sum(
                prob.hypergrad(jnp.mean(x, 0)) ** 2)),
            "bytes_per_round": led.total_bytes,
        }
    gid = out["identity"]["final_gap"]
    g_reset, g_persist = out["reset"]["final_gap"], \
        out["persist"]["final_gap"]
    return [Row("comm/sharded_ef/top_k:0.1+ef", 0.0, {
        "agents": n,
        "rounds": rounds,
        "final_gap_identity": f"{gid:.3e}",
        "final_gap_reset": f"{g_reset:.3e}",
        "final_gap_persist": f"{g_persist:.3e}",
        "gap_vs_identity_reset": round(g_reset / max(gid, 1e-30), 3),
        "gap_vs_identity_persist": round(g_persist / max(gid, 1e-30), 3),
        "persist_closes_gap": bool(abs(g_persist - gid)
                                   <= abs(g_reset - gid)),
        "bytes_per_round": out["reset"]["bytes_per_round"],
        "bytes_per_round_identity": out["identity"]["bytes_per_round"],
    })]


def _lm_drift_rows(rounds: int = 10) -> list[Row]:
    """f32 vs bf16 gossip on the LM smoke run (ROADMAP bf16 item),
    through examples/train_lm_dagm.py's `main` in this process."""
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "train_lm_dagm.py")
    spec = importlib.util.spec_from_file_location("train_lm_dagm", path)
    lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lm)
    out = {dtype: lm.main(["--rounds", str(rounds),
                           "--mixing-dtype", dtype])
           for dtype in ("f32", "bf16")}
    f32 = np.asarray(out["f32"]["outer_loss"])
    b16 = np.asarray(out["bf16"]["outer_loss"])
    return [Row("comm/lm_bf16_drift", 0.0, {
        "rounds": rounds,
        "max_abs_delta": f"{np.abs(f32 - b16).max():.2e}",
        "final_delta": f"{abs(f32[-1] - b16[-1]):.2e}",
        "final_f32": round(float(f32[-1]), 4),
        "final_bf16": round(float(b16[-1]), 4),
        "bytes_per_round_f32":
            out["f32"]["ledger"]["bytes_per_round"],
        "bytes_per_round_bf16":
            out["bf16"]["ledger"]["bytes_per_round"],
    })]


def run(budget: str = "small") -> list[Row]:
    rows = []
    # ---- static wire table (exact per-send bytes at a d=1024 payload)
    for spec in WIRE_SPECS:
        from repro.comm import parse_comm_spec
        comp = parse_comm_spec(spec).compressor
        b = comp.payload_bytes((1024,))
        rows.append(Row(f"comm/wire/{spec}", 0.0, {
            "payload_bytes_d1024": b,
            "reduction_vs_f32": round(4 * 1024 / b, 3)}))

    curvature = 5.5          # quadratic_bilevel spectrum ⊂ [1, 5]
    if budget == "smoke":
        # scripts/ci.sh tier 2: every compressor row once, tiny dims,
        # keep the checked-in JSON untouched
        prob = quadratic_bilevel(8, 4, 32, seed=0)
        net = make_network("ring", 8)
        rows += _sweep(prob, net,
                       ["identity", "bf16", "int8+ef", "top_k:0.25+ef",
                        "rand_k:0.5+ef"],
                       K=40, M=5, U=3, curvature=curvature,
                       tag="ring_smoke")
        return rows

    # ---- headline: ring, LM-ish d2, full spec sweep ----
    n, d1, d2, K, M, U = 8, 16, 1024, 300, 10, 3
    prob = quadratic_bilevel(n, d1, d2, seed=0)
    net = make_network("ring", n)
    specs = ["identity", "bf16", "int8", "int8+ef", "int4+ef",
             "top_k:0.1+ef", "rand_k:0.25+ef"]
    rows += _sweep(prob, net, specs, K, M, U, curvature,
                   tag=f"ring_n{n}_d{d2}")

    # ---- irregular topology: Erdős–Rényi on the sparse-gather backend
    prob_er = quadratic_bilevel(16, 8, 256, seed=1)
    net_er = make_network("erdos_renyi", 16, r=0.3, seed=0)
    rows += _sweep(prob_er, net_er, ["identity", "int8+ef", "int4+ef"],
                   K=300, M=10, U=3, curvature=curvature,
                   tag="er_n16_d256")

    if budget == "full":
        net_star = make_network("star", 16)
        rows += _sweep(prob_er, net_star, ["identity", "int8+ef"],
                       K=300, M=10, U=3, curvature=curvature,
                       tag="star_n16_d256")

    # a failing row raises before the checked-in JSON is rewritten
    # (benchmarks.run turns the raise into a module ERROR + exit 1)
    rows += _sharded_ef_rows(rounds=200)
    rows += _lm_drift_rows(rounds=10)

    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump([{"name": r.name,
                    "us_per_call": round(r.us_per_call, 1),
                    "derived": r.derived} for r in rows], f, indent=1)
    return rows


if __name__ == "__main__":
    for row in run(sys.argv[1] if len(sys.argv) > 1 else "small"):
        print(row.csv())
