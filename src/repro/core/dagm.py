"""DAGM — Decentralized Alternating Gradient Method (Algorithm 2).

Each outer iteration k (of K):
  1. M inner DGD steps on the penalized inner problem (Eq. 15–16):
         y ← W y − βₖ ∇_y g(x, y)           [M neighbor exchanges of d2]
  2. DIHGP (Algorithm 1) for h ≈ −H^{-1}∇_y f  [U neighbor exchanges]
  3. Outer step with the Eq. (17b) hyper-gradient estimate:
         ∇̂F = γₖ(I−Ẃ)x + ∇_x f(x, ỹ) + βₖ ∇²_xy g(x, ỹ) h
         x ← x − αₖ ∇̂F
                                             [1 neighbor exchange of d1]

Only matrix-vector products and vector communication — the paper's core
communication-efficiency claim, preserved structurally here: the mixing
ops are the only cross-agent operations.

Hyper-parameters enter the round body as **runtime operands** (a
`RoundHP` of traced f32 scalars, one slice per round of the
`repro.solve` schedules): one compiled program serves any (αₖ, βₖ, γₖ)
sequence, which is what makes the serve tier's traced-hp buckets
bit-exact with solo runs and the paper's decaying-step-size
corollaries runnable.  γ defaults to 1/α (the paper's penalty
coupling) computed as float32(1)/float32(α) — bit-identical to the
division-by-literal folding of the legacy Python-float configs, so
constant schedules reproduce the historical trajectories exactly
(regression-tested).

`repro.solve.solve` is the public entry point; `DAGMConfig`/`dagm_run`
survive as deprecation shims that lower onto `SolverSpec`.  The
pod-scale sharded version lives in `repro.distributed.dagm_sharded`
and reuses the same update algebra.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .dihgp import (dihgp_dense, dihgp_dense_c, dihgp_matrix_free,
                    dihgp_matrix_free_c)
from .mixing import (MixingOp, Network, laplacian_apply,
                     laplacian_apply_c)
from .penalty import consensus_error, inner_dgd_step, inner_dgd_step_c
from .problems import BilevelProblem

Array = jnp.ndarray


class RoundHP(NamedTuple):
    """One outer round's hyper-parameters, as jit operands.

    Scalars inside the round body; (rounds,) arrays when passed to
    `dagm_run_chunk` (the scan slices them per round).  `gamma` is the
    outer penalty coefficient multiplying (I−Ẃ)x — pass
    float32(1)/float32(alpha) for the paper's coupling (that product
    is bit-identical to the legacy `/ alpha` literal division)."""
    alpha: Any
    beta: Any
    gamma: Any


def constant_round_hp(cfg) -> RoundHP:
    """RoundHP of f32 constants from any config surface (round-0 values
    of the spec's schedules — the legacy single-step semantics)."""
    from repro.solve.spec import as_solver_spec
    sched = as_solver_spec(cfg).schedule.materialize(1)
    return RoundHP(alpha=sched.alpha[0], beta=sched.beta[0],
                   gamma=sched.gamma[0])


@dataclasses.dataclass(frozen=True)
class DAGMConfig:
    """DEPRECATED — construct a `repro.solve.SolverSpec` (or the
    `repro.solve.dagm_spec(...)` kwargs mirror) instead.

    Survives as a thin shim that lowers onto SolverSpec: every field
    keeps its meaning, but hyper-parameters are Python constants here —
    runtime schedules (decaying αₖ/βₖ, growing γₖ) need the SolverSpec
    surface.  Constructing one emits a DeprecationWarning once per
    process."""
    alpha: float = 1e-2          # outer step / outer penalty 1/α
    beta: float = 1e-2           # inner step / inner penalty 1/β
    K: int = 100                 # outer iterations
    M: int = 10                  # inner DGD steps per outer iteration
    U: int = 3                   # Neumann truncation order (paper uses 3)
    dihgp: str = "dense"         # "dense" | "matrix_free" | "exact"
    curvature: float | None = None   # fixed λmax bound for matrix_free
    mixing: str = "auto"         # MixingOp backend (repro.topology)
    mixing_interpret: bool | None = None   # None: platform decides
    mixing_dtype: str = "f32"    # "f32" | "bf16" storage/gossip dtype
    comm: str = "identity"       # repro.comm gossip spec

    def __post_init__(self):
        from repro.solve._compat import warn_once
        warn_once(
            "DAGMConfig",
            "DAGMConfig is deprecated: use repro.solve.SolverSpec "
            "(dagm_spec(...) mirrors these kwargs) with "
            "repro.solve.solve(problem, network, spec)")

    def comm_channels(self, d1: int, d2: int) -> list[tuple]:
        """(name, per-agent payload shape, sends per outer round) for
        the three Algorithm-2 gossip channels.  The `dihgp="exact"`
        backend solves densely and never gossips h — the hand-kept
        Appendix-S1 dict used to charge it U exchanges anyway."""
        h_sends = 0 if self.dihgp == "exact" else self.U
        return [("inner_y", (d2,), self.M),
                ("dihgp_h", (d2,), h_sends),
                ("outer_x", (d1,), 1)]

    def comm_ledger(self, d1: int, d2: int, rounds: int | None = None):
        """Static CommLedger preview for this config (the measured
        ledger attached to `DAGMResult` is charged from the actual
        traced send counters and must agree — tested)."""
        from repro.comm import static_ledger
        K = self.K if rounds is None else rounds
        return static_ledger(
            self.comm, [(name, shape, K * sends) for name, shape, sends
                        in self.comm_channels(d1, d2)], name="dagm")

    def comm_vectors_per_round(self) -> dict[str, int]:
        """Deprecated: per-agent vector exchanges per outer round.

        Kept for Appendix-S1 compatibility (legacy key names); derived
        from `comm_channels`, so it honours the configured dihgp
        backend.  Prefer `comm_ledger(d1, d2)` which also knows payload
        shapes and wire bytes.  Warns once per process."""
        from repro.solve._compat import warn_once
        warn_once(
            "comm_vectors_per_round",
            "DAGMConfig.comm_vectors_per_round() is deprecated; use "
            "DAGMConfig.comm_ledger(d1, d2) / DAGMResult.ledger")
        sends = {name: per_round for name, _, per_round
                 in self.comm_channels(1, 1)}
        return {"inner_d2": sends["inner_y"],
                "dihgp_d2": sends["dihgp_h"],
                "outer_d1": sends["outer_x"]}


@dataclasses.dataclass
class DAGMResult:
    x: Array                     # final stacked outer iterates (n, d1)
    y: Array                     # final stacked inner iterates (n, d2)
    metrics: dict[str, Array]    # per-outer-iteration traces, length K
    ledger: "object | None" = None   # repro.comm.CommLedger charged from
    #                                  the run's traced send counters


def _dihgp_h(prob: BilevelProblem, W, cfg, x: Array, y: Array,
             beta, curvature):
    """h ≈ −H⁻¹∇_y f with the configured DIHGP backend (uncompressed)."""
    if cfg.dihgp == "dense":
        return dihgp_dense(prob, W, beta, x, y, cfg.U)
    if cfg.dihgp == "matrix_free":
        hvp = lambda v: prob.hvp_yy_g(x, y, v)
        curv = None if curvature is None else \
            jnp.full((prob.n,), curvature, jnp.float32)
        return dihgp_matrix_free(hvp, prob.grad_y_f(x, y), W, beta,
                                 cfg.U, curvature=curv)
    if cfg.dihgp == "exact":
        from .penalty import exact_ihgp
        return exact_ihgp(prob, W, beta, x, y)
    raise ValueError(f"unknown dihgp backend {cfg.dihgp!r}")


def hypergrad_estimate(prob: BilevelProblem, W, cfg,
                       x: Array, y: Array, hp: RoundHP | None = None,
                       curvature=None) -> Array:
    """∇̂F(x, y) of Eq. (17b) with the configured DIHGP backend."""
    if hp is None:
        hp = constant_round_hp(cfg)
    if curvature is None:
        curvature = cfg.curvature
    with jax.named_scope("dihgp"):
        h = _dihgp_h(prob, W, cfg, x, y, hp.beta, curvature)
    return laplacian_apply(W, x) * hp.gamma + prob.grad_x_f(x, y) \
        + hp.beta * prob.cross_xy_g_times(x, y, h)


def default_metrics(prob: BilevelProblem, x: Array, y: Array
                    ) -> dict[str, Array]:
    m = {
        "outer_obj": jnp.mean(prob.f_stacked(x, y)),
        "inner_obj": jnp.mean(prob.g_stacked(x, y)),
        "consensus_x": consensus_error(x),
        "consensus_y": consensus_error(y),
    }
    if prob.hypergrad is not None:
        xbar = jnp.mean(x, axis=0)
        m["true_hypergrad_norm_sq"] = jnp.sum(prob.hypergrad(xbar) ** 2)
    return m


def hypergrad_estimate_c(prob: BilevelProblem, W, cfg,
                         x: Array, y: Array, h_st, x_st,
                         hp: RoundHP | None = None, curvature=None):
    """`hypergrad_estimate` with both gossips (the U DIHGP exchanges of
    h and the single (I−Ẃ)x exchange) routed through their compressed
    channels.  Returns (∇̂F, h-channel state, x-channel state)."""
    if hp is None:
        hp = constant_round_hp(cfg)
    if curvature is None:
        curvature = cfg.curvature
    with jax.named_scope("dihgp"):
        if cfg.dihgp == "dense":
            h, h_st = dihgp_dense_c(prob, W, hp.beta, x, y, cfg.U, h_st)
        elif cfg.dihgp == "matrix_free":
            hvp = lambda v: prob.hvp_yy_g(x, y, v)
            curv = None if curvature is None else \
                jnp.full((prob.n,), curvature, jnp.float32)
            h, h_st = dihgp_matrix_free_c(hvp, prob.grad_y_f(x, y), W,
                                          hp.beta, cfg.U, h_st,
                                          curvature=curv)
        elif cfg.dihgp == "exact":
            from .penalty import exact_ihgp
            h = exact_ihgp(prob, W, hp.beta, x, y)
        else:
            raise ValueError(f"unknown dihgp backend {cfg.dihgp!r}")
    lap_x, x_st = laplacian_apply_c(W, x, x_st)
    return lap_x * hp.gamma + prob.grad_x_f(x, y) \
        + hp.beta * prob.cross_xy_g_times(x, y, h), h_st, x_st


def dagm_outer_step(prob: BilevelProblem, W, cfg,
                    x: Array, y: Array,
                    metrics_fn: Callable | None = None,
                    hp: RoundHP | None = None, curvature=None):
    """One full outer iteration of Algorithm 2 (lines 3–13)."""
    if hp is None:
        hp = constant_round_hp(cfg)
    def inner(t, yy):
        return inner_dgd_step(prob, W, hp.beta, x, yy)         # Eq. 16
    with jax.named_scope("inner_dgd"):
        y_tilde = jax.lax.fori_loop(0, cfg.M, inner, y)        # lines 4–9

    # the outer step's scope also holds the round's metrics: they are
    # evaluated at the same (x, ỹ) and share its forward passes
    with jax.named_scope("outer_step"):
        d = hypergrad_estimate(prob, W, cfg, x, y_tilde, hp=hp,
                               curvature=curvature)            # lines 10–12
        x_next = x - hp.alpha * d                              # line 13
        # custom metrics callbacks receive W exactly as configured (a
        # MixingOp under dagm_run, or whatever array the caller
        # passed) — use mixing.as_matrix(W) inside the callback for raw
        # entries.  default_metrics never read W, so the default path
        # no longer threads an n×n matrix through the jitted scan.
        if metrics_fn is None:
            metrics = default_metrics(prob, x, y_tilde)
        else:
            metrics = metrics_fn(prob, W, x, y_tilde)
        metrics["hypergrad_est_norm_sq"] = jnp.sum(d ** 2)
    return x_next, y_tilde, metrics


def dagm_outer_step_c(prob: BilevelProblem, W, cfg,
                      x: Array, y: Array, cs: dict,
                      metrics_fn: Callable | None = None,
                      hp: RoundHP | None = None, curvature=None,
                      mask=None):
    """One outer iteration with every gossip on its comm channel.

    `cs` maps {"inner_y", "dihgp_h", "outer_x"} to ChannelStates; with
    `comm="identity"` each exchange short-circuits to exactly the
    uncompressed op, so this is bit-identical to `dagm_outer_step`
    (regression-tested) while the send counters still tick.

    `mask` is this round's fault mask ((n, k_max) padded-table layout,
    see `repro.faults`): every gossip of the round — the M inner
    exchanges, the U DIHGP exchanges and the outer (I−Ẃ)x exchange —
    runs on the degraded view `W.masked(mask)`, i.e. the round's
    realized W_k.  The DIHGP preconditioner D̃ keeps the *nominal*
    self-weights: realized self-weights only grow under link drops
    (w_ii + folded weight ≥ w_ii), so D̃ ⪰ D_k and the Neumann
    contraction bound still holds (possibly conservatively)."""
    if hp is None:
        hp = constant_round_hp(cfg)
    if mask is not None:
        if not isinstance(W, MixingOp):
            raise ValueError(
                "fault masks require a MixingOp (the masked path lives "
                "in the padded neighbor-table operand space); wrap W "
                "with make_mixing_op first")
        W = W.masked(mask)
    # the DIHGP h vector is re-initialized every round: neighbors'
    # error-feedback replicas restart at zero with it
    cs = dict(cs, dihgp_h=cs["dihgp_h"].reset_hat())

    def inner(t, carry):
        yy, st = carry
        return inner_dgd_step_c(prob, W, hp.beta, x, yy, st)    # Eq. 16
    with jax.named_scope("inner_dgd"):
        y_tilde, y_st = jax.lax.fori_loop(0, cfg.M, inner,
                                          (y, cs["inner_y"]))   # lines 4–9
    with jax.named_scope("outer_step"):
        d, h_st, x_st = hypergrad_estimate_c(
            prob, W, cfg, x, y_tilde, cs["dihgp_h"], cs["outer_x"],
            hp=hp, curvature=curvature)                         # lines 10–12
        x_next = x - hp.alpha * d                               # line 13
        if metrics_fn is None:
            metrics = default_metrics(prob, x, y_tilde)
        else:
            metrics = metrics_fn(prob, W, x, y_tilde)
        metrics["hypergrad_est_norm_sq"] = jnp.sum(d ** 2)
    return x_next, y_tilde, metrics, \
        {"inner_y": y_st, "dihgp_h": h_st, "outer_x": x_st}


def dagm_validate(cfg) -> None:
    """Chunk-machinery validation for any config surface (SolverSpec or
    legacy DAGMConfig/ShardedDAGMConfig) — the serve engine routes
    every job through this before it can mint a bucket
    (`serve.jobs.compile_signature`); `solve()` validates the spec
    directly."""
    from repro.solve.spec import as_solver_spec, validate_spec
    spec = as_solver_spec(cfg)
    # legacy sharded lowering pins tier="sharded"; this validator only
    # guards the reference/serve chunk machinery, so check tier-free
    validate_spec(dataclasses.replace(spec, tier="reference")
                  if spec.tier == "sharded" else spec)


def dagm_init_carry(prob: BilevelProblem, W, cfg,
                    x0: Array | None = None, y0: Array | None = None,
                    seed: int = 0, recorder=None):
    """The round-0 chunk carry ((x0, y0), channel states).

    This is the single init protocol shared by every tier (a serve
    slot admitting job `seed` holds exactly this carry, so batched
    trajectories match solo runs bit-for-bit): x0 = 0 (the paper's
    analysis assumption), y0 = 0.01·N(0, I) from PRNGKey(seed), comm
    channels keyed on a stream disjoint from y0's.

    `recorder` (a `repro.obs.RecorderSpec`) appends a third carry
    element — the flight recorder's preallocated ring buffer (see
    `repro.obs.recorder`); None keeps the historical 2-tuple, so
    existing callers and their compiled programs are untouched."""
    key = jax.random.PRNGKey(seed)
    if x0 is None:   # paper's analysis assumes x_0 = 0
        x0 = jnp.zeros((prob.n, prob.d1), jnp.float32)
    if y0 is None:
        y0 = 0.01 * jax.random.normal(key, (prob.n, prob.d2), jnp.float32)
    from repro.comm import open_channels
    cs0 = open_channels(
        W, {"inner_y": y0, "dihgp_h": y0, "outer_x": x0}, seed)
    if recorder is not None:
        from repro.obs.recorder import recorder_init
        return ((x0, y0), cs0, recorder_init(recorder))
    return ((x0, y0), cs0)


def chunk_hp(cfg, rounds: int, start: int = 0) -> RoundHP:
    """RoundHP of (rounds,) schedule slices [start, start+rounds) for
    any config surface — the operands `dagm_run_chunk` scans over."""
    from repro.solve.spec import as_solver_spec
    spec = as_solver_spec(cfg)
    sched = spec.schedule.materialize(max(spec.K, start + rounds))
    sl = slice(start, start + rounds)
    return RoundHP(alpha=sched.alpha[sl], beta=sched.beta[sl],
                   gamma=sched.gamma[sl])


def dagm_run_chunk(prob: BilevelProblem, W, cfg, carry,
                   rounds: int, metrics_fn: Callable | None = None,
                   hp: RoundHP | None = None, curvature=None,
                   masks=None, recorder=None):
    """`rounds` outer iterations of Algorithm 2, carry in / carry out.

    The round-sliced core shared by `solve`, the legacy `dagm_run`
    shim and the serve engine: carry is ((x, y), channel states) as
    produced by `dagm_init_carry` or a previous chunk.  Pure and
    un-jitted — callers jit it (`solve` with rounds=K) or vmap it over
    a leading job axis (`repro.serve`'s continuous batching, which
    retires converged jobs at chunk boundaries).

    `hp` carries the chunk's hyper-parameter slices as (rounds,)
    arrays — runtime operands, so one compiled chunk serves any
    schedule values; None materializes rounds [0, rounds) of `cfg`'s
    schedules (constants for legacy configs).  `curvature` is the
    matrix-free DIHGP bound (scalar operand; defaults to the config's).

    Chunking is exact: running K rounds as K/T chunks of T (T > 1)
    reproduces the single K-round scan bit-for-bit.  (T = 1 is legal
    but XLA fully unrolls a length-1 scan and may fuse the round body
    differently, drifting ~1 ulp/round from the scanned program — the
    serve engine therefore never slices chunks below T = 2 unless
    K = 1.)

    `masks` scans a fault trace through the chunk: a (rounds, n, k_max)
    float array of per-round padded-table edge masks (see
    `repro.faults.FaultTrace.table_masks`), a traced operand exactly
    like `hp` — one compiled chunk replays any fault schedule, zero
    retraces.  None keeps today's unmasked scan program (structurally
    unchanged, so existing compiled trajectories stay bit-exact).

    `recorder` (a `repro.obs.RecorderSpec`, matching the carry built by
    `dagm_init_carry(..., recorder=...)`) extends the carry to ((x, y),
    channel states, FlightBuffer) and appends one flight row per round
    from inside the scan — pure `dynamic_update_slice` writes, no host
    callbacks, so the zero-retrace contract holds.  The iterate/channel
    algebra is untouched either way: with recorder=None this function
    builds byte-for-byte the same scan program it did before the
    recorder existed, and with it on, the (x, y) trajectory is bitwise
    identical because the recorder only *reads* the round's metrics and
    counters (tests/test_obs.py pins both).

    Returns (carry, metrics) with metrics stacked over the chunk's
    rounds."""
    if hp is None:
        hp = chunk_hp(cfg, rounds)
    hp = RoundHP(*(jnp.asarray(a, jnp.float32) for a in hp))

    if recorder is not None:
        return _dagm_run_chunk_recorded(prob, W, cfg, carry, rounds,
                                        metrics_fn, hp, curvature,
                                        masks)

    if masks is None:
        def body(c, hp_t):
            (x, y), cs = c
            x, y, m, cs = dagm_outer_step_c(prob, W, cfg, x, y, cs,
                                            metrics_fn,
                                            hp=RoundHP(*hp_t),
                                            curvature=curvature)
            return ((x, y), cs), m
        return jax.lax.scan(body, carry, hp, length=rounds)

    masks = jnp.asarray(masks, jnp.float32)

    def body_m(c, operands):
        hp_t, mask_t = operands
        (x, y), cs = c
        x, y, m, cs = dagm_outer_step_c(prob, W, cfg, x, y, cs,
                                        metrics_fn, hp=RoundHP(*hp_t),
                                        curvature=curvature,
                                        mask=mask_t)
        return ((x, y), cs), m
    return jax.lax.scan(body_m, carry, (hp, masks), length=rounds)


def _dagm_run_chunk_recorded(prob, W, cfg, carry, rounds, metrics_fn,
                             hp, curvature, masks):
    """The flight-recorded twin of `dagm_run_chunk`'s scans: same round
    algebra, carry extended with the FlightBuffer, one recorded row per
    round.  Kept separate so the recorder-off paths above stay
    literally the historical program."""
    from repro.obs.recorder import (flight_values, recorder_write,
                                    wire_constants)
    bps, offdiag_valid = wire_constants(W)

    if masks is None:
        def body(c, hp_t):
            (x, y), cs, rec = c
            hp_k = RoundHP(*hp_t)
            x, y, m, cs = dagm_outer_step_c(prob, W, cfg, x, y, cs,
                                            metrics_fn, hp=hp_k,
                                            curvature=curvature)
            rec = recorder_write(rec, flight_values(
                m, cs, hp_k.gamma, bytes_per_send=bps))
            return ((x, y), cs, rec), m
        return jax.lax.scan(body, carry, hp, length=rounds)

    masks = jnp.asarray(masks, jnp.float32)

    def body_m(c, operands):
        hp_t, mask_t = operands
        (x, y), cs, rec = c
        hp_k = RoundHP(*hp_t)
        x, y, m, cs = dagm_outer_step_c(prob, W, cfg, x, y, cs,
                                        metrics_fn, hp=hp_k,
                                        curvature=curvature,
                                        mask=mask_t)
        rec = recorder_write(rec, flight_values(
            m, cs, hp_k.gamma, bytes_per_send=bps, mask=mask_t,
            offdiag_valid=offdiag_valid))
        return ((x, y), cs, rec), m
    return jax.lax.scan(body_m, carry, (hp, masks), length=rounds)


def dagm_run(prob: BilevelProblem, net: Network, cfg,
             x0: Array | None = None, y0: Array | None = None,
             metrics_fn: Callable | None = None, seed: int = 0
             ) -> DAGMResult:
    """Legacy reference-tier entry — lowers onto `repro.solve.solve`.

    Accepts a (deprecated) `DAGMConfig` or a `SolverSpec`; the run is
    identical to ``solve(prob, net, spec, ...)`` — one jitted K-round
    `dagm_run_chunk` with the schedules as traced operands — repackaged
    in the historical `DAGMResult`."""
    from repro.solve import solve
    from repro.solve.spec import as_solver_spec
    res = solve(prob, net, as_solver_spec(cfg), x0=x0, y0=y0,
                metrics_fn=metrics_fn, seed=seed)
    return DAGMResult(x=res.x, y=res.y, metrics=res.metrics,
                      ledger=res.ledger)


def dagm_comm_bytes(cfg, net: Network, d1: int, d2: int,
                    bytes_per: int = 4) -> int:
    """Total bytes moved over K rounds: each agent sends its payload to
    every neighbor each exchange ⇒ 2·|E| directed sends per exchange.

    Computed from the config's CommLedger; `bytes_per` scales the
    uncompressed word size (legacy knob) and is ignored once a real
    compressor sets the wire format."""
    led = cfg.comm_ledger(d1, d2)
    sends = led.network_multiplier(net.num_edges)
    comm = cfg.comm if isinstance(cfg, DAGMConfig) else cfg.comm.spec
    if comm == "identity":
        return led.total_floats * bytes_per * sends
    return led.total_bytes * sends
