"""On-chip smoke test: DAGM's main path, compiled, on a TPU.

    python chip_smoke.py             # one chip: phases a, b, c
    python chip_smoke.py --chips 4   # a 4-chip host: the sharded phase only

Phases on one chip, each through the entry points a user calls
(`repro.solve.solve`, `repro.serve.admission.AdmissionLoop`):

  a. reference tier at the paper's §6.2 widths: DAGM on the
     hyper-representation MLP (d=784, 200 hidden, 10 classes; outer x of
     157,000 and inner y of 2,010 per agent) over a 16-agent ring,
     checked against the same `solve()` on the host CPU;
  b. the Pallas mixing kernels compiled (never interpreted): circulant
     full-stripe and halo, sparse gather, identity and int8+EF gossip,
     each against the XLA backend of the same op on the chip;
  c. serve: an `AdmissionLoop` answers 8 jobs with round budgets 40
     and 80 packed into one bucket, each against a solo `solve()`.

With ``--chips 4`` only the sharded tier runs: one agent per chip,
ring gossip by `ppermute`, against the reference tier on one chip.

Problem data is generated from seeds.  Every check that fails makes
the exit code non-zero; the last line of standard output is the JSON
result only when every phase passed.  Without a TPU the script exits
non-zero before running any phase.  Wall, compile and peak-memory
figures printed per phase are set-up diagnostics, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the phase-a oracle runs on the host CPU in this same process, so the
# CPU backend must be up beside the TPU even where the platform list is
# pinned
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# --- tolerances, fixed before any chip run -------------------------------
# a: chip and CPU both run at matmul precision HIGHEST, so the two differ
#    only in f32 reduction order and transcendental implementations
#    (~1e-7 relative per op) carried through ~10^3 dependent ops of a
#    contracting iteration.
TOL_REFERENCE = 1e-3
# b: one gossip, Pallas vs XLA on the same chip: the same f32 products,
#    summed in possibly another order (a few ulp of the output scale).
TOL_MIX_OP = 1e-6
#    whole runs: those per-gossip ulps carried through K·(M+U+1) mixes.
#    Both runs use matmul precision HIGHEST: at the default one-pass
#    bf16 f32 dots, a 1-ulp difference can flip the bf16 rounding of a
#    dot input (2^-9 relative): 5e-4 run errors on a v5e at default
#    precision.
TOL_MIX_RUN = 1e-4
#    int8+EF: kernel and XLA draw different stochastic-rounding uniforms,
#    so the runs differ; the kernel's final gap may exceed the XLA
#    compose path's by at most the 1.1x bench_comm allows.
GAP_RATIO = 1.1
# c: batched (vmapped) vs solo programs of the same job may reduce in
#    another order on the chip; K=80 rounds of f32 rounding.
TOL_SERVE = 1e-4
# sharded: ring ppermute vs the reference tier's circulant mixing, both
#    at HIGHEST precision.
TOL_SHARDED = 1e-4


class Checks:
    """Records every check of a phase; failures make the exit non-zero."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failures: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        print(f"[{self.phase}] {'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)


class CompileClock:
    """Sums jax's backend-compile durations (a persistent-cache hit
    counts only its retrieval)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# a. reference tier at published widths, against the CPU
# ---------------------------------------------------------------------------

def phase_reference(ck: Checks, *, n=16, d=784, hidden=200, n_classes=10,
                    m_per=30, K=20):
    from repro.core import make_network
    from repro.core.problems import hyper_representation
    from repro.solve import dagm_spec, solve

    spec = dagm_spec(alpha=0.5, beta=0.1, K=K, M=5, U=3)
    net = make_network("ring", n)

    def run():
        prob = hyper_representation(n, d=d, hidden=hidden,
                                    n_classes=n_classes, m_per=m_per,
                                    seed=0)
        # x = the hidden layer: a random backbone (x = 0 is a dead ReLU
        # init with zero hyper-gradient), the same for every agent
        x0 = jnp.broadcast_to(
            jax.random.normal(jax.random.PRNGKey(42), (prob.d1,))
            / np.sqrt(d), (n, prob.d1)).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            res = solve(prob, net, spec, x0=x0, seed=0)
        return jax.device_get((res.x, res.y, res.metrics["outer_obj"]))

    x, y, obj = run()
    print(f"[a] d1={hidden * (d + 1)} d2={n_classes * (hidden + 1)} "
          f"outer_obj {obj[0]:.6f} -> {obj[-1]:.6f}")
    ck.expect(bool(np.all(np.isfinite(x)) and np.all(np.isfinite(y))),
              "iterates finite")
    ck.expect(bool(obj[-1] < obj[0]), "outer objective falls")
    with jax.default_device(jax.devices("cpu")[0]):
        xc, yc, objc = run()
    ex, ey = rel_err(x, xc), rel_err(y, yc)
    print(f"[a] max relative error vs CPU oracle: x {ex:.3e} y {ey:.3e} "
          f"outer_obj {rel_err(obj, objc):.3e} (tol {TOL_REFERENCE})")
    ck.expect(max(ex, ey) <= TOL_REFERENCE,
              f"chip agrees with the CPU oracle within {TOL_REFERENCE}")


# ---------------------------------------------------------------------------
# b. Pallas mixing kernels compiled, against the XLA backends
# ---------------------------------------------------------------------------

def _kernel_in_program(W, y, comm: bool) -> bool:
    """Does the op's compiled program hold a Mosaic kernel?  An
    interpreted kernel lowers to plain HLO loops instead."""
    if comm:
        st = W.comm_channel("probe", y, jax.random.PRNGKey(0))
        fn = jax.jit(lambda yy, ss: W.laplacian_c(yy, ss)[0])
        text = fn.lower(y, st).compile().as_text()
    else:
        text = jax.jit(W.laplacian).lower(y).compile().as_text()
    return "tpu_custom_call" in text


def _mixing_case(ck, tag, prob, net, *, pallas, xla, comm, K, M, U,
                 curvature, x0):
    from repro.solve import dagm_spec, solve
    from repro.topology import make_mixing_op
    y = jax.random.normal(jax.random.PRNGKey(3), (prob.n, prob.d2))
    Wp = make_mixing_op(net, backend=pallas, comm=comm)
    Wx = make_mixing_op(net, backend=xla, comm=comm)
    ck.expect(_kernel_in_program(Wp, y, comm != "identity"),
              f"{tag}: {pallas} program holds a compiled kernel")
    if comm == "identity":
        e = rel_err(Wp.laplacian(y), Wx.laplacian(y))
        print(f"[b] {tag}: one gossip rel err {e:.3e}")
        ck.expect(e <= TOL_MIX_OP, f"{tag}: one gossip within {TOL_MIX_OP}")
    runs = {}
    for backend in (xla, pallas):
        spec = dagm_spec(alpha=0.05, beta=0.1, K=K, M=M, U=U,
                         dihgp="matrix_free", curvature=curvature,
                         mixing=backend, comm=comm)
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            res = solve(prob, net, spec, x0=x0, seed=0)
        runs[backend] = jax.device_get(
            (res.x, res.y, res.metrics["true_hypergrad_norm_sq"]))
        print(f"[b] {tag}: {backend} solve {time.perf_counter() - t0:.1f} s")
    (xx, yx, gx), (xp, yp, gp) = runs[xla], runs[pallas]
    finite = all(np.all(np.isfinite(a)) for a in (xx, yx, xp, yp))
    ck.expect(finite, f"{tag}: iterates finite")
    print(f"[b] {tag}: final gap {xla} {gx[-1]:.6e} {pallas} {gp[-1]:.6e} "
          f"(start {gx[0]:.6e})")
    if comm == "identity":
        e = max(rel_err(xp, xx), rel_err(yp, yx))
        print(f"[b] {tag}: run rel err {e:.3e}")
        ck.expect(e <= TOL_MIX_RUN, f"{tag}: run within {TOL_MIX_RUN}")
    else:
        ratio = float(gp[-1] / gx[-1])
        print(f"[b] {tag}: gap ratio kernel/xla {ratio:.4f}")
        ck.expect(ratio <= GAP_RATIO and gp[-1] < gp[0],
                  f"{tag}: gap falls and is within {GAP_RATIO}x of XLA")


def phase_kernels(ck: Checks, *, n=64, d2=1024, n_halo=8192, d2_halo=128,
                  K=30, K_halo=10, d1=128):
    from repro import obs
    from repro.core import make_network, quadratic_bilevel
    from repro.kernels import pallas_interpret
    from repro.kernels.mixing_matvec import VMEM_BUDGET_BYTES, \
        stripe_vmem_bytes

    ck.expect(pallas_interpret() is False,
              "interpret mode is off on this platform")
    # every gossiped width (d1 for x, d2 for y and h) is a multiple of
    # the 128-lane tile, so no gossip may leave the kernels
    curvature = 5.5                       # quadratic spectrum ⊂ [1, 5]

    def x0_for(prob):
        return jnp.broadcast_to(
            2.0 * jax.random.normal(jax.random.PRNGKey(7), (prob.d1,)),
            (prob.n, prob.d1)).astype(jnp.float32)

    prob = quadratic_bilevel(n, d1, d2, seed=0)
    ring = make_network("ring", n)
    for comm in ("identity", "int8+ef"):
        _mixing_case(ck, f"circulant n={n} d2={d2} {comm}", prob, ring,
                     pallas="circulant_pallas", xla="circulant", comm=comm,
                     K=K, M=5, U=3, curvature=curvature, x0=x0_for(prob))
    er = make_network("erdos_renyi", n, r=0.1, seed=0)
    _mixing_case(ck, f"sparse_gather ER n={n} d2={d2} identity", prob, er,
                 pallas="sparse_gather_pallas", xla="sparse_gather",
                 comm="identity", K=K, M=5, U=3, curvature=curvature,
                 x0=x0_for(prob))

    # one agent count past the full-stripe -> halo switch
    ck.expect(stripe_vmem_bytes(n_halo) > VMEM_BUDGET_BYTES,
              f"n={n_halo} is past the full-stripe VMEM budget")
    prob = quadratic_bilevel(n_halo, d1, d2_halo, seed=1)
    ring = make_network("ring", n_halo)
    for comm in ("identity", "int8+ef"):
        _mixing_case(ck, f"circulant halo n={n_halo} d2={d2_halo} {comm}",
                     prob, ring, pallas="circulant_pallas",
                     xla="circulant", comm=comm, K=K_halo, M=3, U=2,
                     curvature=curvature, x0=x0_for(prob))

    fallbacks = sum(s.value for s in obs.fused_fallback_counter().samples())
    print(f"[b] mixing_fused_fallbacks_total = {fallbacks:g}")
    ck.expect(fallbacks == 0, "no fused-kernel fallbacks")


# ---------------------------------------------------------------------------
# c. serve: the admission loop against solo solves
# ---------------------------------------------------------------------------

def phase_serve(ck: Checks, *, jobs=8, n=16, d=1024, budgets=(40, 80)):
    import dataclasses
    from repro.serve import JobSpec, build_network, build_problem
    from repro.serve.admission import AdmissionLoop
    from repro.solve import ScheduleSpec, dagm_spec, solve

    # λmax(∇²g) ≤ 2·λmax(ZᵀZ/m) + 2·max exp(x) ≈ 95 + 2 for d=1024, m=30
    cfg = dagm_spec(alpha=0.01, beta=0.005, K=budgets[0], M=5, U=3,
                    dihgp="matrix_free", curvature=200.0)
    specs = [JobSpec("ho_regression", {"n": n, "d": d, "seed": s},
                     dataclasses.replace(
                         cfg, K=budgets[s % len(budgets)],
                         schedule=ScheduleSpec(alpha=0.01 + 0.001 * s,
                                               beta=0.005)),
                     seed=s, job_id=f"job{s}") for s in range(jobs)]
    loop = AdmissionLoop(chunk_rounds=20, max_width=jobs,
                         bucket_width=jobs, hp_mode="traced",
                         telemetry=False)
    loop.submit(specs)
    results = {r.job_id: r for r in loop.run()}
    ck.expect(loop.stats.buckets == 1 and loop.stats.cache_misses == 1,
              f"budgets {budgets} packed into one bucket, one program "
              f"(buckets={loop.stats.buckets}, "
              f"programs={loop.stats.cache_misses})")
    worst, bitwise = 0.0, True
    for spec in specs:
        r = results[spec.job_id]
        solo = solve(build_problem(spec), build_network(spec), spec.config,
                     seed=spec.seed)
        xs, ys = jax.device_get((solo.x, solo.y))
        xb, yb = np.asarray(r.x), np.asarray(r.y)
        same = np.array_equal(xb, xs) and np.array_equal(yb, ys)
        bitwise &= same
        e = max(rel_err(xb, xs), rel_err(yb, ys))
        worst = max(worst, e)
        finite = bool(np.all(np.isfinite(xb)) and np.all(np.isfinite(yb)))
        ck.expect(finite and r.rounds == spec.config.K,
                  f"{spec.job_id}: K={spec.config.K} rounds, finite")
        print(f"[c] {spec.job_id} K={r.rounds} bitwise={same} "
              f"rel err {e:.3e}")
    print(f"[c] all {jobs} jobs bitwise equal to solo: {bitwise}")
    ck.expect(worst <= TOL_SERVE,
              f"every job within {TOL_SERVE} of its solo solve")


# ---------------------------------------------------------------------------
# sharded tier on four chips
# ---------------------------------------------------------------------------

def phase_sharded(ck: Checks, *, n=4, d1=128, d2=4096, K=20):
    from jax.sharding import Mesh
    from repro.core import make_network, quadratic_bilevel
    from repro.solve import dagm_spec, sharded_spec, solve

    devices = jax.devices()[:n]
    ck.expect(len(devices) == n, f"{n} devices for {n} agents")
    mesh = Mesh(np.array(devices), ("data",))
    prob = quadratic_bilevel(n, d1, d2, seed=0)
    curvature = 5.5
    y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (n, d2),
                                  jnp.float32)
    x0 = jnp.zeros((n, d1), jnp.float32)
    for comm in ("identity", "int8+ef"):
        sspec = sharded_spec(alpha=0.05, beta=0.1, M=5, U=3,
                             curvature=curvature, comm=comm, K=K)
        rspec = dagm_spec(alpha=0.05, beta=0.1, K=K, M=5, U=3,
                          dihgp="matrix_free", curvature=curvature,
                          comm=comm)
        with jax.default_matmul_precision("highest"):
            sh = solve(prob, None, sspec, mesh=mesh, x0=x0, y0=y0, seed=0)
            ref = solve(prob, make_network("ring", n), rspec, x0=x0, y0=y0,
                        seed=0)
        spans = len(sh.x.sharding.device_set), len(sh.y.sharding.device_set)
        ck.expect(spans == (n, n),
                  f"{comm}: sharded x/y span {spans} devices")
        xs, ys, xr, yr = jax.device_get((sh.x, sh.y, ref.x, ref.y))
        finite = all(np.all(np.isfinite(a)) for a in (xs, ys))
        ck.expect(finite, f"{comm}: sharded iterates finite")
        gs = float(jnp.sum(prob.hypergrad(jnp.mean(jnp.asarray(xs), 0)) ** 2))
        gr = float(jnp.sum(prob.hypergrad(jnp.mean(jnp.asarray(xr), 0)) ** 2))
        g0 = float(jnp.sum(prob.hypergrad(jnp.mean(x0, 0)) ** 2))
        print(f"[sharded] {comm}: gap start {g0:.6e} sharded {gs:.6e} "
              f"reference {gr:.6e}")
        if comm == "identity":
            e = max(rel_err(xs, xr), rel_err(ys, yr))
            print(f"[sharded] {comm}: rel err vs reference {e:.3e}")
            ck.expect(e <= TOL_SHARDED,
                      f"{comm}: matches the reference tier within "
                      f"{TOL_SHARDED}")
        else:
            ratio = gs / gr
            print(f"[sharded] {comm}: gap ratio sharded/reference "
                  f"{ratio:.4f}")
            ck.expect(gs < g0 and ratio <= GAP_RATIO,
                      f"{comm}: gap falls and is within {GAP_RATIO}x of "
                      f"the reference tier")


# ---------------------------------------------------------------------------

def run_phase(name: str, fn, clock: CompileClock) -> list[str]:
    ck = Checks(name)
    t0, c0 = time.perf_counter(), clock.seconds
    try:
        fn(ck)
    except Exception:  # noqa: BLE001 — reported, and fails the run
        traceback.print_exc()
        ck.failures.append("raised")
    wall = time.perf_counter() - t0
    print(f"[{name}] wall {wall:.1f} s, compile {clock.seconds - c0:.1f} s, "
          f"peak_bytes_in_use {peak_bytes()}")
    return [f"{name}: {f}" for f in ck.failures]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, one agent per "
                         "chip of a 4-chip host")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; "
              f"no phase run", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {dev.device_kind} x {len(jax.devices())}")
    clock = CompileClock()
    if args.chips == 4:
        phases = [("sharded", phase_sharded)]
    else:
        phases = [("a", phase_reference), ("b", phase_kernels),
                  ("c", phase_serve)]
    failures = []
    for name, fn in phases:
        failures += run_phase(name, fn, clock)
    print(f"compile total {clock.seconds:.1f} s, persistent-cache hits "
          f"{clock.cache_hits}")
    if failures:
        print("chip_smoke FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
