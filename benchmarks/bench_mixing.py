"""Micro-benchmark for the mixing backend (the DAGM hot primitive).

Compares, per (n agents, d features, k-hop circulant topology):

  * dense    — `mix_apply` as W @ Y (O(n²·d) matmul, the old default),
  * circulant — MixingOp's O(n·k·d) weighted-cyclic-shift XLA path,
  * pallas   — the banded-circulant Pallas kernel (interpreted off a
               TPU — those rows are suffixed `_interpret` and their
               wall-clock validates, not measures),

and, per irregular (Erdős–Rényi) topology:

  * dense         — the same O(n²·d) matmul fallback,
  * sparse_gather — MixingOp's O((nnz+n)·d) padded row-gather XLA path,
  * sparse Pallas — the per-row scalar-prefetched gather kernel
                    (interpret-mode validation timing),

plus the fused vs unfused DIHGP Neumann step, the comm-fused quantize+
mix kernels vs the XLA compress→mix→decompress compose (with modeled
HBM traffic from benchmarks.roofline.mixing_traffic_model and a
`retraces` count — 0 means the second call with fresh operands hit the
jit cache), the row-tiled halo kernels at n = 4096 (past the full-
stripe VMEM budget), and an end-to-end int8+EF DAGM run fused vs
unfused (gap ratio must sit inside the bench_comm 1.1× tolerance).
Each row reports the
FLOPs of both formulations; `speedup_vs_dense` is measured wall-clock,
`work_ratio` (= dense FLOPs / sparse FLOPs; n/(2k+1) circulant,
n²/(nnz+n) irregular) is the FLOPs-proportional speedup the backend
realizes on hardware where both paths run at the same arithmetic
intensity.

Also dumps the rows as JSON to benchmarks/results/bench_mixing.json
(same record schema as the CSV contract: name / us_per_call / derived)
so the BENCH trajectory captures the speedup.  The "smoke" budget is
the scripts/ci.sh tier-2 invocation: tiny cases, no JSON rewrite.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from repro.comm import channel_init
from repro.core import make_mixing_op, make_network, quadratic_bilevel
from repro.core.mixing import circulant_structure, fused_neumann_step
from repro.kernels import ops as kops
from repro.kernels.mixing_matvec import (circulant_mix_matvec,
                                         circulant_mix_matvec_halo,
                                         pick_halo_bn,
                                         sparse_mix_matvec,
                                         stripe_vmem_bytes,
                                         VMEM_BUDGET_BYTES)
from repro import obs
from repro.solve import dagm_spec, solve
from repro.topology import sparse_structure

from .common import Row, timed
from .roofline import mixing_traffic_model

SMOKE_AWARE = True   # genuine cheap smoke tier (benchmarks.run contract)
RESULTS = os.path.join(os.path.dirname(__file__), "results",
                       "bench_mixing.json")


def _pallas_row(name: str, us: float, derived: dict) -> Row:
    """A Pallas timing row: named and noted `_interpret` when the
    platform interprets the kernel (see kernels.ops.pallas_interpret),
    whose wall-clock then validates and does not measure."""
    if kops.pallas_interpret():
        return Row(name + "_interpret", us,
                   {**derived, "note": "interpret-mode validation timing"})
    return Row(name, us, derived)


def _paired_best(base_fn, fn, y, iters: int,
                 repeats: int = 9) -> tuple[float, float]:
    """(best µs of base_fn, best µs of fn) over short *interleaved*
    repeats.  Contention on a shared box only ever adds time, so the
    minimum of many short windows approximates the quiet-machine cost
    for both sides under matched conditions — far more stable than one
    long run or independently-timed minima."""
    tb = min(timed(base_fn, y, iters=iters, warmup=1)[1]
             for _ in range(2))
    tf = min(timed(fn, y, iters=iters, warmup=1)[1] for _ in range(2))
    for _ in range(repeats):
        tb = min(tb, timed(base_fn, y, iters=iters, warmup=0)[1])
        tf = min(tf, timed(fn, y, iters=iters, warmup=0)[1])
    return tb, tf


def _flops(n: int, d: int, k_offsets: int) -> dict[str, float]:
    dense = 2.0 * n * n * d                    # matmul MACs×2
    sparse = 2.0 * (k_offsets + 1) * n * d     # k shifts + self, FMA×2
    return {"flops_dense": dense, "flops_sparse": sparse,
            "work_ratio": dense / sparse}


def _bench_case(n: int, d: int, hops: int, iters: int,
                with_pallas: bool) -> list[Row]:
    net = make_network("circulant", n, offsets=tuple(range(1, hops + 1)))
    s = circulant_structure(net.W)
    W = net.W_jnp()
    y = jax.random.normal(jax.random.PRNGKey(n + d), (n, d), jnp.float32)
    fl = _flops(n, d, len(s.offsets))
    tag = f"mixing/n{n}_d{d}_k{len(s.offsets)}"

    dense = jax.jit(lambda z: z - W.astype(z.dtype) @ z)
    op = make_mixing_op(net, backend="circulant")
    circ = jax.jit(op.laplacian)
    us_dense, us_circ = _paired_best(dense, circ, y, iters)
    rows = [Row(f"{tag}/dense", us_dense,
                {"flops": fl["flops_dense"], "work_ratio": 1.0,
                 "speedup_vs_dense": 1.0}),
            Row(f"{tag}/circulant", us_circ,
                {"flops": fl["flops_sparse"],
                 "work_ratio": round(fl["work_ratio"], 2),
                 "speedup_vs_dense": round(us_dense / us_circ, 3)})]

    if with_pallas and d % 128 == 0 and n % 8 == 0:
        def pk(z):
            return circulant_mix_matvec(z, w_self=s.w_self,
                                        offsets=s.offsets,
                                        weights=s.weights, laplacian=True)
        _, us_pk = timed(pk, y, iters=max(1, iters // 10), warmup=1)

        rows.append(_pallas_row(f"{tag}/pallas", us_pk,
                                {"flops": fl["flops_sparse"],
                                 "work_ratio": round(fl["work_ratio"], 2)}))
    return rows


def _bench_er_case(n: int, d: int, r: float, iters: int,
                   with_pallas: bool, seed: int = 0) -> list[Row]:
    """Irregular-topology rows: dense vs the CSR gather backend on an
    Erdős–Rényi graph (the paper's Figs. 2–3 run r = 0.5; low r is where
    the O((nnz+n)·d) path pulls away from the matmul)."""
    net = make_network("erdos_renyi", n, r=r, seed=seed)
    sp = sparse_structure(net.W)
    W = net.W_jnp()
    y = jax.random.normal(jax.random.PRNGKey(n + d), (n, d), jnp.float32)
    fl_dense = 2.0 * n * n * d
    tag = f"mixing/er_n{n}_d{d}_r{r}"

    dense = jax.jit(lambda z: z - W.astype(z.dtype) @ z)
    op = make_mixing_op(net, backend="sparse_gather")
    # report the FLOPs of the formulation the op actually executes:
    # padded row-gather loop does n·k_max MACs per feature, CSR
    # segment-sum nnz (both + n for the diagonal)
    macs = (n * sp.k if op._sp_use_padded else sp.nnz) + n
    fl_sparse = 2.0 * macs * d
    sparse = jax.jit(op.laplacian)
    us_dense, us_sparse = _paired_best(dense, sparse, y, iters)
    rows = [Row(f"{tag}/dense", us_dense,
                {"flops": fl_dense, "work_ratio": 1.0,
                 "speedup_vs_dense": 1.0}),
            Row(f"{tag}/sparse_gather", us_sparse,
                {"flops": fl_sparse, "k_max": sp.k,
                 "mean_degree": round(sp.nnz / n, 1),
                 "formulation": ("padded_gather" if op._sp_use_padded
                                 else "csr_segment_sum"),
                 "work_ratio": round(n * n / macs, 2),
                 "speedup_vs_dense": round(us_dense / us_sparse, 3)})]

    if with_pallas and d % 128 == 0 and n % 8 == 0:
        wself = jnp.asarray(sp.w_self)
        idx = jnp.asarray(sp.neighbors)
        wts = jnp.asarray(sp.weights)

        def pk(z):
            return sparse_mix_matvec(z, wself, idx, wts, laplacian=True)
        _, us_pk = timed(pk, y, iters=max(1, iters // 20), warmup=1)
        rows.append(_pallas_row(
            f"{tag}/sparse_pallas", us_pk,
            {"flops": 2.0 * (n * sp.k + n) * d,
             "work_ratio": round(n * n / (n * sp.k + n), 2)}))
    return rows


def _bench_fused_neumann(n: int, d: int, iters: int) -> list[Row]:
    net = make_network("ring", n)
    W = net.W_jnp()
    op = make_mixing_op(net, backend="circulant")
    key = jax.random.PRNGKey(0)
    h, hvp_h, p = (jax.random.normal(k, (n, d), jnp.float32)
                   for k in jax.random.split(key, 3))
    dsc = jnp.full((n, 1), 2.5, jnp.float32)
    beta = 0.1

    def unfused(h):
        lap = h - W @ h
        bh = dsc * h - (lap + beta * hvp_h)
        return (bh - p) / dsc

    fused = jax.jit(lambda h: fused_neumann_step(op, h, hvp_h, p, dsc,
                                                 beta))
    us_un, us_fu = _paired_best(jax.jit(unfused), fused, h, iters)
    tag = f"mixing/neumann_n{n}_d{d}"
    return [
        Row(f"{tag}/unfused_dense", us_un, {"speedup_vs_unfused": 1.0}),
        Row(f"{tag}/fused_circulant", us_fu,
            {"speedup_vs_unfused": round(us_un / us_fu, 3)}),
    ]


def _counting_jit(fn, name: str):
    """jit(fn) through the shared `repro.obs.TraceCounter`: `retraces`
    per bench row is calls_with_fresh_operands − 1 and must be 0 (the
    fused kernels keep seed/zp/scale as traced operands, so new values
    never respecialize)."""
    tc = obs.TraceCounter(name)
    return tc.wrap(fn), tc


def _bench_fused_comm(n: int, d: int, iters: int) -> list[Row]:
    """Comm-fused kernel vs the XLA compress→mix→decompress compose,
    through MixingOp.mix_c so dispatch (and the ChannelState protocol)
    is part of what's timed."""
    net = make_network("circulant", n, offsets=(1, 2))
    y = jax.random.normal(jax.random.PRNGKey(n + d), (n, d), jnp.float32)
    rows = []
    for spec in ("int8", "int8+ef"):
        ef = spec.endswith("+ef")
        model = mixing_traffic_model(n, d, ef=ef)
        tag = f"mixing/fused_n{n}_d{d}/{spec}"
        xla_op = make_mixing_op(net, backend="circulant", comm=spec)
        st0 = channel_init(xla_op.comm, "x", y, jax.random.PRNGKey(0))
        unfused, c_un = _counting_jit(
            lambda z, op=xla_op: op.mix_c(z, st0)[0],
            f"mixing_unfused_{spec}")
        with kops.pallas_mode(True):
            fop = make_mixing_op(net, comm=spec)
            assert fop._fused_plan(y) is not None
            fused, c_fu = _counting_jit(
                lambda z, op=fop: op.mix_c(z, st0)[0],
                f"mixing_fused_{spec}")
            us_un, us_fu = _paired_best(unfused, fused, y, iters)
            # second operand value, same shape: must hit the jit cache
            fused(y + 1.0).block_until_ready()
            unfused(y + 1.0).block_until_ready()
        common = {"modeled_unfused_bytes": model["unfused_bytes"],
                  "modeled_fused_bytes": model["fused_bytes"],
                  "traffic_reduction": model["traffic_reduction"]}
        if kops.pallas_interpret():
            common["note"] = "interpret-mode validation timing"
        rows.append(Row(f"{tag}/unfused", us_un,
                        {**common, "retraces": c_un.retraces}))
        rows.append(Row(f"{tag}/fused", us_fu,
                        {**common, "retraces": c_fu.retraces,
                         "speedup_vs_unfused": round(us_un / us_fu, 3)}))
    return rows


def _bench_halo(n: int, d: int, iters: int) -> list[Row]:
    """Row-tiled halo kernel rows past (or at smoke size, below) the
    full-stripe VMEM ceiling: plain laplacian vs the XLA circulant path
    and the comm-fused int8 variant."""
    net = make_network("circulant", n, offsets=(1, 2))
    s = circulant_structure(net.W)
    y = jax.random.normal(jax.random.PRNGKey(n + d), (n, d), jnp.float32)
    over = stripe_vmem_bytes(n) > VMEM_BUDGET_BYTES
    bn = pick_halo_bn(n, h_lo=2, h_hi=2) or min(n, 256)
    tag = f"mixing/halo_n{n}_d{d}"
    xla_op = make_mixing_op(net, backend="circulant")
    plain, c_pl = _counting_jit(
        lambda z: circulant_mix_matvec_halo(
            z, w_self=s.w_self, offsets=s.offsets, weights=s.weights,
            laplacian=True, bn=bn),
        "halo_plain")
    us_xla, us_halo = _paired_best(jax.jit(xla_op.laplacian), plain, y,
                                   iters)
    plain(y + 1.0).block_until_ready()
    rows = [Row(f"{tag}/circulant_xla", us_xla,
                {"full_stripe_exceeds_vmem": over}),
            _pallas_row(f"{tag}/halo", us_halo,
                        {"bn": bn, "full_stripe_exceeds_vmem": over,
                         "retraces": c_pl.retraces})]

    model = mixing_traffic_model(n, d, ef=False)
    from repro.comm import row_quant_params
    zp, sc = row_quant_params(y, 8)
    seed = jnp.zeros((1,), jnp.int32)
    fused, c_fu = _counting_jit(
        lambda z, zp_, sc_, sd: circulant_mix_matvec_halo(
            z, zp_, sc_, sd, w_self=s.w_self, offsets=s.offsets,
            weights=s.weights, bn=bn, comm="int8"),
        "halo_fused_int8")
    _, us_fu = timed(lambda z: fused(z, zp, sc, seed), y,
                     iters=max(1, iters // 10), warmup=1)
    fused(y + 1.0, zp, sc, seed + 1).block_until_ready()
    rows.append(_pallas_row(f"{tag}/halo_fused_int8", us_fu,
                            {"bn": bn, "retraces": c_fu.retraces,
                             "modeled_fused_bytes": model["fused_bytes"],
                             "traffic_reduction":
                                 model["traffic_reduction"]}))
    return rows


def _bench_fused_dagm(K: int, M: int, U: int) -> list[Row]:
    """End-to-end DAGM with int8+EF gossip, fused kernels vs the XLA
    compose: same key-advance protocol, different stochastic-rounding
    draws, so the final hypergradient gaps must agree within the
    bench_comm matched-final-gap tolerance (1.1×)."""
    prob = quadratic_bilevel(8, 128, 128, seed=0)
    net = make_network("ring", 8)
    cfg = dagm_spec(alpha=0.05, beta=0.1, K=K, M=M, U=U,
                    dihgp="matrix_free", curvature=5.5, comm="int8+ef")
    x0 = jnp.broadcast_to(
        2.0 * jax.random.normal(jax.random.PRNGKey(7), (prob.d1,)),
        (prob.n, prob.d1)).astype(jnp.float32)

    def gap(res):
        xbar = jnp.mean(res.x, axis=0)
        return float(jnp.sum(prob.hypergrad(xbar) ** 2))

    res_u, us_u = timed(lambda: solve(prob, net, cfg, x0=x0, seed=0),
                        iters=1)
    with kops.pallas_mode(True):
        res_f, us_f = timed(lambda: solve(prob, net, cfg, x0=x0, seed=0),
                            iters=1)
    g_u, g_f = gap(res_u), gap(res_f)
    ratio = g_f / max(g_u, 1e-30)
    return [Row(f"mixing/dagm_e2e_int8ef_K{K}/unfused", us_u,
                {"final_gap": f"{g_u:.3e}"}),
            Row(f"mixing/dagm_e2e_int8ef_K{K}/fused", us_f,
                {"final_gap": f"{g_f:.3e}",
                 "gap_vs_unfused": round(ratio, 3),
                 "tolerance": 1.1,
                 "within_tolerance": bool(ratio <= 1.1
                                          and 1 / ratio <= 1.1)})]


def run(budget: str = "small") -> list[Row]:
    write_json = True
    if budget == "full":
        cases = [(n, d, hops) for n in (8, 64, 256)
                 for d in (1024, 4096, 16384) for hops in (1, 2)]
        er_cases = [(64, 1024, 0.1), (256, 1024, 0.05), (256, 2048, 0.05),
                    (256, 1024, 0.1), (256, 4096, 0.05)]
        fused_cases, halo_case = [(64, 4096), (256, 4096)], (4096, 1024)
        dagm_K, iters, with_pallas = 100, 100, True
    elif budget == "smoke":
        # scripts/ci.sh tier-2 smoke: exercise every backend row once
        # (fused, halo and e2e rows included), keep the checked-in JSON
        # (measured on a quiet box) untouched
        cases = [(8, 512, 1)]
        er_cases = [(16, 512, 0.3)]
        fused_cases, halo_case = [(16, 512)], (64, 256)
        dagm_K, iters, with_pallas, write_json = 20, 5, True, False
    else:
        cases = [(8, 4096, 1), (64, 4096, 1), (64, 4096, 2),
                 (256, 4096, 1)]
        er_cases = [(256, 1024, 0.05), (256, 2048, 0.05),
                    (256, 1024, 0.1)]
        fused_cases, halo_case = [(64, 4096), (256, 4096)], (4096, 1024)
        dagm_K, iters, with_pallas = 60, 100, True
    rows = []
    for n, d, hops in cases:
        rows.extend(_bench_case(n, d, hops, iters, with_pallas))
    for n, d, r in er_cases:
        rows.extend(_bench_er_case(n, d, r, iters, with_pallas))
    rows.extend(_bench_fused_neumann(64, 4096, iters))
    for n, d in fused_cases:
        rows.extend(_bench_fused_comm(n, d, max(2, iters // 10)))
    rows.extend(_bench_halo(*halo_case, max(1, iters // 20)))
    rows.extend(_bench_fused_dagm(dagm_K, 5, 3))

    if write_json:
        os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
        with open(RESULTS, "w") as f:
            json.dump([{"name": r.name,
                        "us_per_call": round(r.us_per_call, 1),
                        "derived": r.derived} for r in rows], f, indent=1)
    return rows


if __name__ == "__main__":
    import sys
    for row in run(sys.argv[1] if len(sys.argv) > 1 else "small"):
        print(row.csv())
