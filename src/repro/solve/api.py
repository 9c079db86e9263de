"""`solve(problem, network, spec)` — the single solver front-end.

One call signature dispatches every method × tier combination:

    from repro.solve import SolverSpec, ScheduleSpec, solve
    res = solve(prob, net, SolverSpec(
        method="dagm", tier="reference", K=200, M=10, U=3,
        schedule=ScheduleSpec(alpha=inverse_sqrt_schedule(0.05),
                              beta=0.1)))

* ``tier="reference"`` — one jitted K-round scan (methods "dagm",
  "dgbo", "dgtbo", "ma_dbo", "fednest").  Hyper-parameter schedules
  enter the compiled program as traced (K,) operands, so the program
  itself is schedule-agnostic; callers that hold a compiled runner
  (the serve engine's chunk cache, or your own jit around
  `dagm_run_chunk`) sweep α/β/γ with zero retraces.  A bare `solve()`
  call builds a fresh closure per invocation and does not cache
  compiles across calls — route sweeps through ``tier="serve"`` (one
  engine, one compile per bucket program).
* ``tier="serve"``   — the run rides the `repro.serve` engine as a
  one-job bucket (same chunk machinery, width-padded).  Because solo
  and serve now share the traced-operand program, the trajectories are
  bit-exact across tiers.
* ``tier="sharded"`` — the `distributed` shard_map step over a
  caller-supplied mesh, scanned over all K rounds in one jitted
  program; the schedules enter it as (K,) coefficient operands.

Every tier returns a `SolveResult` (final iterates, per-round metric
trajectory, byte-accurate CommLedger, final gossip channel states).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from .spec import (SolverSpec, as_solver_spec, mixing_kwargs,
                   validate_spec)

Array = jnp.ndarray


@dataclasses.dataclass
class SolveResult:
    """Unified outcome of a `solve` call, across methods and tiers."""
    x: Array                     # final stacked outer iterates (n, d1)
    y: Array                     # final stacked inner iterates (n, d2)
    metrics: dict[str, Array]    # per-outer-round traces
    ledger: Any = None           # repro.comm.CommLedger (measured)
    channels: Any = None         # final gossip ChannelStates (or None)
    method: str = "dagm"
    tier: str = "reference"
    extras: dict = dataclasses.field(default_factory=dict)
    #   method/tier specifics: baselines put the Appendix-S1
    #   "comm_floats_per_round" closed form + display "name" here; the
    #   serve tier puts rounds/converged/final_gap/wire bytes.


def solve(problem, network, spec, *, x0=None, y0=None, seed: int = 0,
          metrics_fn: Callable | None = None, mesh=None,
          g_fn: Callable | None = None, f_fn: Callable | None = None,
          batch=None, serve_engine=None, recorder=None) -> SolveResult:
    """Run `spec` on (problem, network) and return a `SolveResult`.

    problem:  a `core.problems.BilevelProblem` (stacked per-agent
              objectives).  The sharded tier can instead take raw
              `g_fn`/`f_fn` pytree objectives (+ explicit x0/y0/batch).
    network:  a `repro.topology.Network`; ignored by tier="sharded"
              (the mesh's ring is the topology) and "fednest" (star).
    spec:     `SolverSpec` (legacy DAGMConfig/ShardedDAGMConfig configs
              are lowered transparently).
    x0/y0:    optional initial stacked iterates (reference/sharded).
    seed:     y0 draw + gossip channel keys.
    metrics_fn: per-round metrics callback (method="dagm" only).
    mesh:     jax Mesh, required by tier="sharded".
    serve_engine: optional pre-built `repro.serve.ServeEngine` to run
              tier="serve" solves through (shares its compile cache).
              A `repro.serve.admission.AdmissionLoop` works too: the
              solve is submitted into the live service and joins a
              bucket at the next chunk boundary, sharing slots with
              whatever jobs the loop is already running.
    recorder: optional `repro.obs.RecorderSpec` — threads the in-jit
              flight recorder through the run (the chunk carry on the
              reference/serve tiers, the shard_map step carry on the
              sharded tier) and returns the per-round rows in
              `extras["flight"]` (method="dagm", all three tiers).
              None (the default) leaves every program byte-for-byte
              as before.
    """
    spec = as_solver_spec(spec)
    validate_spec(spec)
    if metrics_fn is not None and spec.method != "dagm":
        raise ValueError(
            f"metrics_fn is only supported for method='dagm' (the "
            f"baselines record the fixed default_metrics trace); got "
            f"method={spec.method!r}")
    if recorder is not None and spec.method != "dagm":
        raise ValueError(
            "the flight recorder rides the dagm round carry: "
            "recorder= needs method='dagm' (the baselines record no "
            "flight rows) — got method=" + repr(spec.method))
    if spec.tier == "reference":
        if spec.method == "dagm":
            return _solve_dagm_reference(problem, network, spec, x0=x0,
                                         y0=y0, seed=seed,
                                         metrics_fn=metrics_fn,
                                         recorder=recorder)
        return _solve_baseline(problem, network, spec, x0=x0, y0=y0,
                               seed=seed)
    if spec.tier == "serve":
        return _solve_serve(problem, network, spec, x0=x0, y0=y0,
                            seed=seed, metrics_fn=metrics_fn,
                            engine=serve_engine, recorder=recorder)
    return _solve_sharded(problem, network, spec, x0=x0, y0=y0,
                          seed=seed, metrics_fn=metrics_fn, mesh=mesh,
                          g_fn=g_fn, f_fn=f_fn, batch=batch,
                          recorder=recorder)


# ---------------------------------------------------------------------------
# reference tier
# ---------------------------------------------------------------------------

def _schedule_hp(spec: SolverSpec):
    from repro.core.dagm import RoundHP
    sched = spec.schedule.materialize(spec.K)
    return RoundHP(alpha=sched.alpha, beta=sched.beta,
                   gamma=sched.gamma)


def _solve_dagm_reference(prob, net, spec: SolverSpec, *, x0, y0, seed,
                          metrics_fn, recorder=None) -> SolveResult:
    from repro.core.dagm import (RoundHP, dagm_init_carry,
                                 dagm_run_chunk)
    from repro.core.mixing import make_mixing_op
    from repro import obs
    tr = obs.tracer()
    with tr.span("solve", cat="solver", track="solver", method="dagm",
                 tier="reference", K=spec.K, seed=seed,
                 compile_stages=True):
        W = make_mixing_op(net, **mixing_kwargs(spec))
        with tr.span("init_carry", cat="solver", track="solver"):
            carry0 = dagm_init_carry(prob, W, spec, x0, y0, seed,
                                     recorder=recorder)
        hp = _schedule_hp(spec)

        # faults lower once (host-side) to a per-round mask operand;
        # like hp, the masks enter the program as traced arrays, so
        # resolving a different FaultSpec against a held compiled
        # runner costs zero retraces (the bare solve() closure is
        # still per-call).
        trace = None
        masks = None
        if spec.faults is not None:
            from repro.faults import lower_faults
            with tr.span("lower_faults", cat="solver", track="solver"):
                trace = lower_faults(spec.faults, net, spec.K)
                masks = jnp.asarray(trace.table_masks(W.sparse),
                                    jnp.float32)

        # hp enters as a jit *argument*: the program is
        # schedule-agnostic, and — because the serve tier scans the
        # very same traced operands — batched traced-hp runs are
        # bit-exact with this solo program.  (The closure itself is
        # per-call: solo solve() does not cache compiles across
        # invocations; sweeps belong on tier="serve".)  The problem
        # data is an argument too: closed over, it would be compiled
        # into the program as constants (1.4 GB for a 64-agent
        # quadratic with d2 = 1024), which is slow to compile and too
        # big for the persistent compile cache.
        @jax.jit
        def run(carry, hp, masks, data):
            return dagm_run_chunk(prob.with_data(data), W, spec, carry,
                                  spec.K, metrics_fn, hp=hp, masks=masks,
                                  recorder=recorder)

        # trace_compile: from the call until tracing, compiling (or
        # loading from the persistent cache) and dispatch returned;
        # chunk: the device execution after it (a sync the result read
        # below would force anyway — values are unchanged)
        with tr.span("trace_compile", cat="solver.compile",
                     track="solver", rounds=spec.K):
            out = run(
                carry0, RoundHP(*(jnp.asarray(a, jnp.float32)
                                  for a in hp)),
                masks, prob.data)
        with tr.span("chunk", cat="solver.chunk", track="solver",
                     rounds=spec.K):
            if tr.enabled:
                jax.block_until_ready(out)

        flight = None
        if recorder is not None:
            ((x, y), cs, rec), metrics = out
            flight = obs.recorder_rows(rec)
        else:
            ((x, y), cs), metrics = out
        W.ledger.charge_states(cs.values())

        extras = {}
        if trace is not None:
            # ledger sends stay nominal (channel counters tick whether
            # or not a given link carried the payload); the honest
            # wire scale for the faulted run is the trace's
            # realized-link fraction
            extras = {"fault_trace": trace,
                      "fault_alive_fraction": trace.alive_fraction()}
        if flight is not None:
            extras["flight"] = flight
        return SolveResult(x=x, y=y, metrics=metrics, ledger=W.ledger,
                           channels=cs, method="dagm",
                           tier="reference", extras=extras)


def _solve_baseline(prob, net, spec: SolverSpec, *, x0, y0, seed
                    ) -> SolveResult:
    from repro.core.baselines import BASELINE_SOLVERS
    hp = _schedule_hp(spec)
    x, y, metrics, cs, ledger, floats, name = \
        BASELINE_SOLVERS[spec.method](prob, net, spec, hp, x0=x0, y0=y0,
                                      seed=seed)
    return SolveResult(x=x, y=y, metrics=metrics, ledger=ledger,
                       channels=cs, method=spec.method, tier="reference",
                       extras={"comm_floats_per_round": floats,
                               "name": name})


# ---------------------------------------------------------------------------
# serve tier
# ---------------------------------------------------------------------------

#: problem-object → inline family callable.  The family object is part
#: of the serve compile signature, so re-solving the same problem must
#: hand the engine the SAME callable or a shared engine's compile cache
#: could never hit.  id-keyed (BilevelProblem holds arrays and is not
#: hashable) with an identity check against stale-id reuse; bounded
#: because each family closure keeps its problem alive.
_INLINE_FAMILIES: dict = {}
_INLINE_FAMILIES_CAP = 256


def _inline_family(prob):
    ent = _INLINE_FAMILIES.get(id(prob))
    if ent is not None and ent[0] is prob:
        return ent[1]
    fam = lambda: prob
    while len(_INLINE_FAMILIES) >= _INLINE_FAMILIES_CAP:
        _INLINE_FAMILIES.pop(next(iter(_INLINE_FAMILIES)))
    _INLINE_FAMILIES[id(prob)] = (prob, fam)
    return fam


def _default_serve_metrics(prob, W, x, y):
    """Module-level (stable identity: it is part of the engine's chunk
    compile key) default — the reference tier's default_metrics, so a
    serve-tier SolveResult carries the same trajectory."""
    from repro.core.dagm import default_metrics
    return default_metrics(prob, x, y)


def _solve_serve(prob, net, spec: SolverSpec, *, x0, y0, seed,
                 metrics_fn, engine, recorder=None) -> SolveResult:
    from repro.serve import JobSpec, ServeEngine
    if x0 is not None or y0 is not None:
        raise ValueError(
            "tier='serve' jobs initialize from their seed (the engine's "
            "slot-admission protocol); custom x0/y0 are a "
            "reference-tier feature — use tier='reference' or bake the "
            "init into the problem")
    if engine is None:
        engine = ServeEngine(record_metrics=True,
                             flight_recorder=recorder)
    elif not engine.record_metrics:
        raise ValueError(
            "the ServeEngine passed to solve(tier='serve') must be "
            "built with record_metrics=True so the SolveResult can "
            "carry the per-round metric trajectory")
    elif recorder is not None \
            and engine.flight_recorder != recorder:
        raise ValueError(
            "solve(recorder=...) on a pre-built engine needs the "
            "engine constructed with the same flight_recorder= spec "
            "(the recorder buffer is part of every bucket's carry)")
    mf = _default_serve_metrics if metrics_fn is None else metrics_fn
    job = JobSpec(family=_inline_family(prob), problem={},
                  config=dataclasses.replace(spec, tier="reference"),
                  graph=net, seed=seed)
    from repro import obs
    prev_mf = engine.metrics_fn
    engine.metrics_fn = mf
    try:
        with obs.span("solve", cat="solver", track="solver",
                      method="dagm", tier="serve", K=spec.K, seed=seed,
                      compile_stages=True):
            engine.submit(job)
            (res,) = engine.run()
    finally:
        engine.metrics_fn = prev_mf
    extras = {"rounds": res.rounds, "converged": res.converged,
              "final_gap": res.final_gap,
              "wire_bytes": res.wire_bytes,
              "wire_floats": res.wire_floats, "sends": res.sends}
    if recorder is not None:
        extras["flight"] = res.flight
    return SolveResult(
        x=jnp.asarray(res.x), y=jnp.asarray(res.y), metrics=res.metrics,
        ledger=engine.ledgers[res.signature], channels=None,
        method="dagm", tier="serve", extras=extras)


# ---------------------------------------------------------------------------
# sharded tier
# ---------------------------------------------------------------------------

def _solve_sharded(prob, net, spec: SolverSpec, *, x0, y0, seed,
                   metrics_fn, mesh, g_fn, f_fn, batch,
                   recorder=None) -> SolveResult:
    from repro.distributed.dagm_sharded import (ShardedRoundCoeffs,
                                                make_sharded_dagm,
                                                open_sharded_channels,
                                                sharded_comm_ledger,
                                                sharded_round_coeffs)
    if mesh is None:
        raise ValueError(
            "tier='sharded' runs a shard_map program: pass the jax "
            "Mesh via solve(..., mesh=...) (its "
            f"{spec.sharded.axis!r} axis sizes the agent ring); build "
            "one with jax.sharding.Mesh or repro.launch.mesh")
    if metrics_fn is not None:
        raise ValueError(
            "tier='sharded' records the fixed in-shard metrics "
            "(outer/inner loss, hypergrad norm, consensus, comm "
            "sends); a custom metrics_fn is a reference-tier feature")
    if g_fn is None or f_fn is None:
        if prob is None:
            raise ValueError(
                "tier='sharded' needs objectives: pass a BilevelProblem "
                "as `problem`, or explicit g_fn/f_fn pytree objectives "
                "(with x0/y0/batch)")
        g_fn = g_fn or prob.g
        f_fn = f_fn or prob.f
    if batch is None:
        if prob is None:
            raise ValueError(
                "tier='sharded' with raw g_fn/f_fn needs the stacked "
                "per-agent `batch` pytree (leading agent axis)")
        batch = prob.data

    step, w = make_sharded_dagm(g_fn, f_fn, spec, mesh,
                                schedule_hp=True, jit_step=False,
                                recorder=recorder)
    ax = spec.sharded.axis
    ax_names = ax if isinstance(ax, tuple) else (ax,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in ax_names:
        n *= sizes[a]
    if x0 is None:
        if prob is None:
            raise ValueError(
                "tier='sharded' with raw g_fn/f_fn needs explicit "
                "x0/y0 stacked iterates (the shapes are not inferable)")
        x0 = jnp.zeros((n, prob.d1), jnp.float32)
    if y0 is None:
        y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(seed),
                                      (n, prob.d2), jnp.float32)

    sched = spec.schedule.materialize(spec.K)
    pol = _sharded_policy(spec)
    channels = open_sharded_channels(spec, x0, y0, seed) \
        if spec.comm.persist_ef else None
    from repro import obs
    rec = obs.recorder_init(recorder) if recorder is not None else None
    # the round's scan inputs: the coefficients (float64 host math,
    # rounded to f32 once), γ for the flight rows, and the round index
    # that the stochastic policies fold into their key
    hp = ShardedRoundCoeffs(*(np.asarray(c, np.float32)
                              for c in sharded_round_coeffs(
                                  sched.alpha.astype(np.float64),
                                  sched.beta.astype(np.float64),
                                  spec.curvature, w.w_self)))
    gamma = sched.gamma if rec is not None else None
    base = jax.random.PRNGKey(seed ^ 0x5eed) \
        if pol.stochastic and channels is None else None

    # all K rounds in one program: the step scanned on the device, the
    # metrics stacked and read once.  The batch is an argument — closed
    # over, it would be compiled in as constants.
    def run(x, y, channels, rec, batch, base, hp, gamma):
        def body(carry, xs):
            x, y, cs, rec = carry
            k, hp, gamma = xs
            if cs is not None:
                args = (cs,)
            elif base is not None:
                args = (jax.random.fold_in(base, k),)
            else:
                args = ()
            tail = (gamma, rec) if rec is not None else ()
            out = step(x, y, batch, *args, hp, *tail)
            x, y, m = out[:3]
            if cs is not None:
                cs = out[3]
            if rec is not None:
                rec = out[-1]
            return (x, y, cs, rec), m

        ks = jnp.arange(spec.K, dtype=jnp.int32)
        return jax.lax.scan(body, (x, y, channels, rec), (ks, hp, gamma))

    run = obs.TraceCounter("sharded_dagm_run").wrap(run)
    tr = obs.tracer()
    with tr.span("solve", cat="solver", track="solver", method="dagm",
                 tier="sharded", K=spec.K, seed=seed,
                 compile_stages=True):
        with tr.span("trace_compile", cat="solver.compile",
                     track="solver", rounds=spec.K):
            out = run(x0, y0, channels, rec, batch, base, hp, gamma)
        with tr.span("chunk", cat="solver.chunk", track="solver",
                     rounds=spec.K):
            if tr.enabled:
                jax.block_until_ready(out)
        (x, y, channels, rec), ms = out
        metrics = jax.tree.map(np.asarray, ms)
    local = jax.tree.map(lambda a: a[0], (x0, y0))
    ledger = sharded_comm_ledger(spec, local[0], local[1],
                                 rounds=spec.K)
    extras = {"ring": w}
    if rec is not None:
        extras["flight"] = obs.recorder_rows(rec)
    return SolveResult(x=x, y=y, metrics=metrics, ledger=ledger,
                       channels=channels, method="dagm", tier="sharded",
                       extras=extras)


def _sharded_policy(spec: SolverSpec):
    from repro.comm import parse_comm_spec
    return parse_comm_spec(spec.comm.spec)
