"""repro.solve — the unified front-end: spec validation, runtime
hyper-parameter schedules, bit-exact constant-schedule regression
against inline legacy literal-hyper-parameter loops, cross-tier
bit-exactness (serve vs reference), and the deprecation-shim
contracts (exactly-once warnings, clean internals under
-W error::DeprecationWarning)."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_mixing_op, make_network, quadratic_bilevel
from repro.optim import inverse_sqrt_schedule, power_schedule
from repro.solve import (METHODS, TIERS, CommSpec, MixingSpec,
                         ScheduleSpec, SolverSpec, dagm_spec,
                         reset_deprecation_state, solve, validate_spec)


@pytest.fixture(scope="module")
def ring_setup():
    n, d1, d2 = 8, 3, 6
    return (make_network("ring", n),
            quadratic_bilevel(n, d1, d2, seed=0, mu_f=0.4))


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------

def test_unknown_method_and_tier_raise():
    with pytest.raises(ValueError, match="unknown method .*dagm"):
        validate_spec(SolverSpec(method="sgd"))
    with pytest.raises(ValueError, match="unknown tier .*reference"):
        validate_spec(SolverSpec(tier="cloud"))
    assert "dagm" in METHODS and "serve" in TIERS


@pytest.mark.parametrize("field,val", [("K", 0), ("M", -1), ("b", 0),
                                       ("N", -3)])
def test_nonpositive_loop_counts_raise(field, val):
    with pytest.raises(ValueError, match=f"SolverSpec.{field} must be "
                                         f"a positive iteration count"):
        validate_spec(SolverSpec(**{field: val}))


def test_negative_u_raises_but_zero_is_legal():
    with pytest.raises(ValueError, match="non-negative Neumann"):
        validate_spec(SolverSpec(U=-1))
    validate_spec(SolverSpec(U=0))       # truncation order 0 is a run


def test_schedule_length_must_match_k():
    with pytest.raises(ValueError, match="3 entries but the run is "
                                         "K=5 rounds"):
        validate_spec(SolverSpec(
            K=5, schedule=ScheduleSpec(alpha=(0.1, 0.05, 0.033))))
    # exact-length tuples are fine
    validate_spec(SolverSpec(
        K=3, schedule=ScheduleSpec(alpha=(0.1, 0.05, 0.033))))


def test_nonpositive_step_sizes_raise():
    with pytest.raises(ValueError, match="alpha must be positive"):
        validate_spec(SolverSpec(K=2, schedule=ScheduleSpec(alpha=0.0)))
    with pytest.raises(ValueError, match="beta must be positive"):
        validate_spec(SolverSpec(
            K=2, schedule=ScheduleSpec(beta=(0.1, -0.1))))


def test_conflicting_comm_settings_raise():
    with pytest.raises(ValueError, match="persist_ef.*sharded-tier"):
        validate_spec(SolverSpec(
            comm=CommSpec(spec="top_k:0.1+ef", persist_ef=True)))
    with pytest.raises(ValueError, match="no error-feedback state"):
        validate_spec(SolverSpec(
            tier="sharded", curvature=4.0,
            comm=CommSpec(spec="identity", persist_ef=True)))
    with pytest.raises(ValueError, match="no gossip to compress"):
        validate_spec(SolverSpec(dihgp="exact",
                                 comm=CommSpec(spec="int8+ef")))


def test_method_tier_and_gamma_conflicts_raise():
    with pytest.raises(ValueError, match="only executes method='dagm'"):
        validate_spec(SolverSpec(method="dgbo", tier="serve"))
    with pytest.raises(ValueError, match="has no penalty term"):
        validate_spec(SolverSpec(
            method="dgtbo", schedule=ScheduleSpec(gamma=2.0)))
    with pytest.raises(ValueError, match="inexpressible"):
        validate_spec(SolverSpec(
            tier="sharded", curvature=4.0,
            schedule=ScheduleSpec(gamma=2.0)))
    with pytest.raises(ValueError, match="needs an explicit curvature"):
        validate_spec(SolverSpec(tier="sharded"))


def test_specs_are_static_pytree_nodes():
    """Frozen specs ride through jit closures/arguments as statics."""
    spec = dagm_spec(alpha=0.05, K=3)
    leaves, treedef = jax.tree_util.tree_flatten(spec)
    assert leaves == []                  # all-static: nothing traced
    assert treedef.unflatten([]) == spec


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def test_schedule_materialization_forms_agree():
    K = 6
    sched_fn = ScheduleSpec(alpha=inverse_sqrt_schedule(0.1),
                            beta=0.2).materialize(K)
    explicit = ScheduleSpec(alpha=tuple(np.asarray(
        inverse_sqrt_schedule(0.1)(jnp.arange(K)))),
        beta=0.2).materialize(K)
    np.testing.assert_array_equal(sched_fn.alpha, explicit.alpha)
    assert sched_fn.alpha[0] == np.float32(0.1)
    assert np.all(np.diff(sched_fn.alpha) < 0)          # decaying
    grow = ScheduleSpec(gamma=power_schedule(10.0, 0.5)).materialize(K)
    assert np.all(np.diff(grow.gamma) > 0)              # growing γₖ


def test_default_gamma_is_f32_reciprocal_of_alpha():
    sched = ScheduleSpec(alpha=0.007).materialize(4)
    assert np.array_equal(
        sched.gamma, np.float32(1.0) / np.full(4, np.float32(0.007)))


def test_decaying_alpha_changes_trajectory_and_stays_finite(ring_setup):
    net, prob = ring_setup
    const = solve(prob, net, dagm_spec(alpha=0.05, beta=0.1, K=25, M=5,
                                       U=3))
    dec = solve(prob, net, dataclasses.replace(
        dagm_spec(alpha=0.05, beta=0.1, K=25, M=5, U=3),
        schedule=ScheduleSpec(alpha=inverse_sqrt_schedule(0.05),
                              beta=0.1)))
    assert not np.array_equal(np.asarray(const.x), np.asarray(dec.x))
    assert np.isfinite(np.asarray(dec.x)).all()
    assert np.isfinite(dec.metrics["true_hypergrad_norm_sq"][-1])
    # round 0 uses the same α — the trajectories fork at round 1
    np.testing.assert_array_equal(const.metrics["outer_obj"][0],
                                  dec.metrics["outer_obj"][0])


def test_decoupled_gamma_runs_dagm_and_madbo(ring_setup):
    net, prob = ring_setup
    for method in ("dagm", "ma_dbo"):
        spec = SolverSpec(
            method=method, K=10, M=5, U=2,
            schedule=ScheduleSpec(alpha=0.05, beta=0.1,
                                  gamma=power_schedule(20.0, 0.25)))
        res = solve(prob, net, spec)
        assert np.isfinite(np.asarray(res.x)).all(), method


# ---------------------------------------------------------------------------
# constant-schedule bit-exactness vs legacy literal programs
# ---------------------------------------------------------------------------

def test_constant_schedule_bitexact_vs_literal_division_loop(ring_setup):
    """Acceptance pin: the traced-operand program reproduces the
    pre-redesign literal-hyper-parameter DAGM — including the
    `(I−Ŵ)x / alpha` literal *division* the old
    hot loop used — bit-for-bit."""
    net, prob = ring_setup
    alpha, beta, K, M, U = 0.007, 0.1, 20, 5, 3   # α with an inexact 1/α
    res = solve(prob, net, dagm_spec(alpha=alpha, beta=beta, K=K, M=M,
                                     U=U))

    from repro.core import dihgp_dense
    from repro.core.mixing import laplacian_apply, mix_apply
    W = make_mixing_op(net)
    x0 = jnp.zeros((prob.n, prob.d1), jnp.float32)
    y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(0),
                                  (prob.n, prob.d2), jnp.float32)

    def legacy(carry, _):                 # pre-redesign body, verbatim
        x, y = carry
        def inner(t, yy):
            return mix_apply(W, yy) - beta * prob.grad_y_g(x, yy)
        y1 = jax.lax.fori_loop(0, M, inner, y)
        h = dihgp_dense(prob, W, beta, x, y1, U)
        d = laplacian_apply(W, x) / alpha + prob.grad_x_f(x, y1) \
            + beta * prob.cross_xy_g_times(x, y1, h)
        return (x - alpha * d, y1), None

    (x_old, y_old), _ = jax.jit(lambda c: jax.lax.scan(
        legacy, c, None, length=K))((x0, y0))
    assert np.array_equal(np.asarray(res.x), np.asarray(x_old))
    assert np.array_equal(np.asarray(res.y), np.asarray(y_old))


def test_constant_tuple_schedule_bitexact_vs_float(ring_setup):
    """A tuple schedule repeating one value is the same program as the
    float constant — the schedule axis adds no numerics."""
    net, prob = ring_setup
    base = dagm_spec(alpha=0.05, beta=0.1, K=12, M=5, U=2)
    tup = dataclasses.replace(base, schedule=ScheduleSpec(
        alpha=(0.05,) * 12, beta=(0.1,) * 12))
    a = solve(prob, net, base)
    b = solve(prob, net, tup)
    assert np.array_equal(np.asarray(a.x), np.asarray(b.x))


# ---------------------------------------------------------------------------
# cross-tier: serve through the same front-end
# ---------------------------------------------------------------------------

def test_serve_tier_bitexact_with_reference_incl_schedules(ring_setup):
    """tier="serve" routes through the batched engine yet reproduces
    the reference trajectory bit-for-bit — the retirement of ROADMAP
    serve follow-up (d), now also under a decaying schedule."""
    net, prob = ring_setup
    spec = dataclasses.replace(
        dagm_spec(alpha=0.05, beta=0.1, K=20, M=5, U=2,
                  dihgp="matrix_free", curvature=6.0),
        schedule=ScheduleSpec(alpha=inverse_sqrt_schedule(0.05),
                              beta=0.1))
    ref = solve(prob, net, spec, seed=7)
    srv = solve(prob, net, dataclasses.replace(spec, tier="serve"),
                seed=7)
    assert np.array_equal(np.asarray(ref.x), np.asarray(srv.x))
    assert np.array_equal(np.asarray(ref.y), np.asarray(srv.y))
    np.testing.assert_array_equal(
        np.asarray(ref.metrics["outer_obj"]),
        srv.metrics["outer_obj"])
    assert srv.extras["rounds"] == spec.K
    assert srv.extras["wire_bytes"] == ref.ledger.total_bytes
    assert srv.tier == "serve" and ref.tier == "reference"


def test_solve_baselines_match_legacy_shims(ring_setup):
    import repro.core.baselines as B
    net, prob = ring_setup
    for method, runner, kw in [
            ("dgbo", B.dgbo_run, {"b": 2}),
            ("dgtbo", B.dgtbo_run, {"N": 2}),
            ("ma_dbo", B.madbo_run, {"U": 2}),
            ("fednest", B.fednest_run, {"U": 2})]:
        spec = SolverSpec(method=method, K=4, M=3,
                          schedule=ScheduleSpec(alpha=0.05, beta=0.1),
                          **kw)
        res = solve(prob, net, spec)
        old = runner(prob, net, alpha=0.05, beta=0.1, K=4, M=3, **kw)
        assert np.array_equal(np.asarray(res.x), np.asarray(old.x)), \
            method
        assert res.extras["comm_floats_per_round"] == \
            old.comm_floats_per_round


def test_solve_rejects_metrics_fn_for_baselines(ring_setup):
    net, prob = ring_setup
    with pytest.raises(ValueError, match="only supported for "
                                         "method='dagm'"):
        solve(prob, net, SolverSpec(method="dgbo", K=2),
              metrics_fn=lambda *a: {})


def test_sharded_tier_requires_mesh(ring_setup):
    net, prob = ring_setup
    with pytest.raises(ValueError, match="pass the jax\nMesh|mesh"):
        solve(prob, net, SolverSpec(tier="sharded", curvature=4.0,
                                    K=2, M=2))


# ---------------------------------------------------------------------------
# deprecation hygiene
# ---------------------------------------------------------------------------

def test_legacy_shims_warn_exactly_once():
    from repro.core import DAGMConfig
    from repro.distributed.dagm_sharded import ShardedDAGMConfig
    reset_deprecation_state()
    for ctor, kw in ((DAGMConfig, {}),
                     (ShardedDAGMConfig, {}),):
        with pytest.deprecated_call():
            ctor(**kw)
        with warnings.catch_warnings():   # second construction: silent
            warnings.simplefilter("error", DeprecationWarning)
            ctor(**kw)


def test_baseline_shims_warn_exactly_once(ring_setup):
    import repro.core.baselines as B
    net, prob = ring_setup
    reset_deprecation_state()
    with pytest.deprecated_call():
        B.dgbo_run(prob, net, alpha=0.05, beta=0.1, K=1, M=1, b=1)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        B.dgbo_run(prob, net, alpha=0.05, beta=0.1, K=1, M=1, b=1)


def test_internal_paths_clean_under_error_filter(ring_setup):
    """No internal call site constructs a deprecated surface: a full
    modern-API pass (solve reference + baselines + serve engine with
    SolverSpec jobs) survives -W error::DeprecationWarning."""
    from repro.serve import JobSpec, ServeEngine
    net, prob = ring_setup
    reset_deprecation_state()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        spec = dagm_spec(alpha=0.05, beta=0.1, K=4, M=3, U=2,
                         dihgp="matrix_free", curvature=6.0)
        solve(prob, net, spec)
        solve(prob, net, SolverSpec(method="dgtbo", K=2, M=2, N=1,
                                    schedule=ScheduleSpec(0.05, 0.1)))
        eng = ServeEngine(chunk_rounds=2)
        eng.submit([JobSpec("quadratic",
                            {"n": 6, "d1": 3, "d2": 4, "seed": s},
                            spec, seed=s) for s in range(2)])
        eng.run()


def test_mixing_spec_roundtrip_through_legacy_config():
    from repro.solve import as_solver_spec, silently
    from repro.core import DAGMConfig
    with silently():
        cfg = DAGMConfig(alpha=0.03, beta=0.2, K=7, M=4, U=2,
                         mixing="circulant", mixing_dtype="bf16",
                         comm="int8+ef", dihgp="matrix_free",
                         curvature=5.0)
    spec = as_solver_spec(cfg)
    # interpret unset on both sides: the platform decides
    assert spec.mixing == MixingSpec(backend="circulant",
                                     interpret=None, dtype="bf16")
    assert spec.comm.spec == "int8+ef"
    assert spec.K == 7 and spec.curvature == 5.0
    sched = spec.schedule.materialize(7)
    assert np.all(sched.alpha == np.float32(0.03))


def test_prebuilt_networks_with_different_w_do_not_share_buckets():
    """Two prebuilt Networks with equal (name, n) but different W must
    land in different buckets — a shared bucket would silently solve
    the second job on the first job's topology."""
    from repro.serve import JobSpec, ServeEngine, compile_signature, \
        build_problem
    from repro.core import make_network
    net0 = make_network("erdos_renyi", 8, r=0.4, seed=0)
    net1 = make_network("erdos_renyi", 8, r=0.4, seed=3)
    assert not np.array_equal(net0.W, net1.W)
    # dense mixing + matrix_free dihgp: the bit-exact-under-vmap
    # combination the serve tier documents (the "auto" ER gather path
    # and batched cholesky each wobble ~1 ulp under a job axis); this
    # test pins bucket *separation*, so keep execution deterministic
    spec = dagm_spec(alpha=0.05, beta=0.1, K=6, M=3, U=2,
                     mixing="dense", dihgp="matrix_free", curvature=8.0)
    jobs = [JobSpec("quadratic", {"n": 8, "d1": 3, "d2": 4, "seed": 0},
                    spec, graph=net, seed=1) for net in (net0, net1)]
    sigs = [compile_signature(j, build_problem(j)) for j in jobs]
    assert sigs[0] != sigs[1]
    eng = ServeEngine(chunk_rounds=3)
    eng.submit(jobs)
    results = eng.run()
    for net, res in zip((net0, net1), results):
        ref = solve(build_problem(jobs[0]), net, spec, seed=1)
        assert np.array_equal(res.x, np.asarray(ref.x))


def test_engine_cache_misses_on_metrics_fn_swap(ring_setup):
    """Swapping engine.metrics_fn must not serve a stale compiled
    chunk that still records the old metrics."""
    from repro.serve import JobSpec, ServeEngine
    net, prob = ring_setup
    spec = dagm_spec(alpha=0.05, beta=0.1, K=4, M=2, U=1)

    def metrics_a(prob, W, x, y):
        return {"custom_a": jnp.float32(0.0)}

    def metrics_b(prob, W, x, y):
        return {"custom_b": jnp.float32(0.0)}

    def job(s):
        return JobSpec("quadratic", {"n": 6, "d1": 3, "d2": 4,
                                     "seed": s}, spec, seed=s)
    eng = ServeEngine(chunk_rounds=2, metrics_fn=metrics_a,
                      record_metrics=True)
    eng.submit([job(0)])
    (r1,) = eng.run()
    eng.metrics_fn = metrics_b
    eng.submit([job(1)])
    (r2,) = eng.run()
    assert "custom_a" in r1.metrics and "custom_a" not in r2.metrics
    assert "custom_b" in r2.metrics


def test_shared_engine_cache_hits_across_serve_solves(ring_setup):
    """solve(tier='serve', serve_engine=eng) on the same problem twice
    reuses the engine's compiled bucket program (the inline family and
    default metrics_fn have stable identities), and the engine's own
    metrics_fn is restored afterwards."""
    from repro.serve import ServeEngine
    net, prob = ring_setup
    spec = dataclasses.replace(
        dagm_spec(alpha=0.05, beta=0.1, K=4, M=2, U=1), tier="serve")
    eng = ServeEngine(chunk_rounds=2, record_metrics=True)
    before = eng.metrics_fn
    solve(prob, net, spec, seed=0, serve_engine=eng)
    traces = eng.stats.traces
    solve(prob, net, spec, seed=1, serve_engine=eng)
    assert eng.stats.traces == traces      # cache hit, no retrace
    assert eng.stats.cache_hits > 0
    assert eng.metrics_fn is before        # side effect undone
