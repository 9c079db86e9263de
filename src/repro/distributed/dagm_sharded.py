"""Pod-scale DAGM: the paper's Algorithm 2 as a shard_map program.

Agents = slices of the mesh "data" axis (and "pod" × "data" multi-pod).
Each agent holds a *pytree* copy of the inner variable y (e.g. model
parameters) and the outer variable x (e.g. loss weights / regularizers),
plus its local data shard.  All cross-agent communication is
`lax.ppermute` neighbor exchange over a circulant graph (see
collectives.ring_mix) — vectors only, never matrices, exactly the
paper's communication pattern.

The inner Hessian-vector products use jvp-of-grad (matrix-free), and
DIHGP uses the scalar-preconditioned splitting of repro.core.dihgp
(D̃ = (β·c + 2(1−w_ii))I), so nothing larger than a parameter pytree is
ever materialized or communicated.

`dagm_sharded_step` is written against per-agent local views (it runs
*inside* shard_map); `make_sharded_dagm` wires it into a jitted global
step for a given mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import (RingWeights, ring_laplacian, ring_laplacian_c,
                          ring_mix, ring_mix_c, taxpy, tdot, tnorm,
                          tscale, tsub, tadd)

Pytree = Any


class ShardedRoundCoeffs(NamedTuple):
    """One outer round's scalar coefficients, as jit operands.

    The sharded update algebra only ever *multiplies* by (combinations
    of) α, β and the scalar preconditioner D̃ — every reciprocal is
    taken on the host in float64, exactly as the legacy Python-float
    config did — so feeding these as traced f32 scalars reproduces the
    literal-constant program bit-for-bit while letting one compiled
    step serve any (αₖ, βₖ) schedule (`repro.solve` tier="sharded")."""
    neg_beta: Any       # −β   (inner DGD step)
    beta: Any           # β    (HVP + cross terms)
    d: Any              # D̃ = β·c + 2(1−w_ii)
    neg_inv_d: Any      # −1/D̃ (DIHGP init)
    inv_d: Any          # 1/D̃  (DIHGP rescale)
    neg_alpha: Any      # −α   (outer step)


def sharded_round_coeffs(alpha: float, beta: float, curvature: float,
                         w_self: float) -> ShardedRoundCoeffs:
    """Host-side (float64) coefficient math matching the legacy config
    path, rounded to f32 once at the use sites' precision."""
    d = beta * curvature + 2.0 * (1.0 - w_self)
    return ShardedRoundCoeffs(
        neg_beta=np.float32(-beta), beta=np.float32(beta),
        d=np.float32(d), neg_inv_d=np.float32(-1.0 / d),
        inv_d=np.float32(1.0 / d), neg_alpha=np.float32(-alpha))


@dataclasses.dataclass(frozen=True)
class ShardedDAGMConfig:
    """DEPRECATED — construct a `repro.solve.SolverSpec` with
    tier="sharded" (or the `repro.solve.sharded_spec(...)` kwargs
    mirror) instead.  Survives as a thin shim lowered by
    `repro.solve.spec.as_solver_spec`; every `repro.distributed` entry
    point accepts both.  Constructing one emits a DeprecationWarning
    once per process."""
    alpha: float = 1e-2
    beta: float = 1e-2
    M: int = 5                 # inner DGD steps per outer step
    U: int = 3                 # Neumann order
    curvature: float = 4.0     # c ≥ λmax(∇²_y g_i) bound (scalar precond)
    axis: str | tuple = "data"  # agent mesh axis; a tuple (e.g.
    #                             ("pod", "data")) rings the agents over
    #                             the flattened product of those axes —
    #                             the cross-pod ring of the multi-pod
    #                             DAGM dry-run
    comm_dtype: str = "f32"    # "bf16" = compressed gossip (§Perf
    #                            variant) — same "f32" | "bf16"
    #                            vocabulary as the reference tier's
    #                            DAGMConfig.mixing_dtype, resolved by the
    #                            shared topology.resolve_mixing_dtype
    comm: str = "identity"     # repro.comm gossip spec ("identity" |
    #                            "bf16" | "int8[+ef]" | "int4[+ef]" |
    #                            "top_k:<frac>[+ef]" | ...): the full
    #                            compressed-channel protocol around every
    #                            ppermute exchange.  Generalizes
    #                            comm_dtype — leaving comm="identity"
    #                            with comm_dtype="bf16" aliases to the
    #                            "bf16" policy (same wire), so existing
    #                            configs keep their behavior.  By default
    #                            error-feedback replicas are per-round
    #                            (they reset at each outer round boundary
    #                            so the step stays a pure (x, y, batch)
    #                            function); persist_ef threads them
    #                            across rounds instead.
    persist_ef: bool = False   # thread the EF `hat` replicas (and the
    #                            compressor key/send-counter state)
    #                            across outer rounds as an extra carry:
    #                            the step becomes (x, y, batch, channels)
    #                            -> (x, y, metrics, channels), matching
    #                            the reference tier where inner_y/outer_x
    #                            replicas warm-start every round (the
    #                            per-round dihgp_h variable still resets
    #                            its hat, like dagm_outer_step_c).  Open
    #                            the initial states with
    #                            `open_sharded_channels`.  Closes the
    #                            ROADMAP "EF state across outer rounds"
    #                            item; measured by bench_comm's
    #                            comm/sharded_ef rows.
    mix_every: int = 1         # j > 1: gossip only every j-th inner step
    #                            (local-updates variant, cf. FedNest [77];
    #                            §Perf — cuts inner comm by ~j)
    unroll_loops: bool = False  # Python-unroll the M/U loops so AOT
    #                             cost_analysis counts every iteration
    #                             (fori_loop bodies are counted once);
    #                             used by the dagm_dryrun accounting

    def __post_init__(self):
        from repro.solve._compat import warn_once
        warn_once(
            "ShardedDAGMConfig",
            "ShardedDAGMConfig is deprecated: use repro.solve."
            "SolverSpec with tier='sharded' (sharded_spec(...) mirrors "
            "these kwargs); make_sharded_dagm accepts it directly")

    @property
    def comm_jnp_dtype(self):
        from repro.topology import resolve_mixing_dtype
        return resolve_mixing_dtype(self.comm_dtype)

    @property
    def comm_policy(self):
        """Effective repro.comm policy: `comm` wins; the legacy
        comm_dtype="bf16" knob aliases to the "bf16" compressor."""
        from repro.comm import parse_comm_spec
        from repro.topology import resolve_mixing_dtype
        spec = self.comm
        if spec == "identity" and \
                resolve_mixing_dtype(self.comm_dtype) is not None:
            spec = self.comm_dtype
        return parse_comm_spec(spec)


def _as_sharded_cfg(cfg) -> ShardedDAGMConfig:
    """Normalize a SolverSpec (tier='sharded') or a legacy
    ShardedDAGMConfig to the internal per-round plan.  SolverSpec
    schedules contribute their round-0 constants (the raw step is one
    round per call; `repro.solve.solve` feeds per-round
    `ShardedRoundCoeffs` operands for real schedules)."""
    if isinstance(cfg, ShardedDAGMConfig):
        return cfg
    from repro.solve._compat import silently
    from repro.solve.spec import SolverSpec
    if not isinstance(cfg, SolverSpec):
        raise TypeError(
            f"expected SolverSpec or ShardedDAGMConfig, got "
            f"{type(cfg).__name__}")
    if cfg.curvature is None:
        raise ValueError(
            "the sharded tier's scalar-preconditioned DIHGP needs "
            "SolverSpec.curvature (a λmax bound on the local inner "
            "Hessians)")
    sched = cfg.schedule.materialize(max(cfg.K, 1))
    with silently():
        return ShardedDAGMConfig(
            alpha=float(sched.alpha[0]), beta=float(sched.beta[0]),
            M=cfg.M, U=cfg.U, curvature=cfg.curvature,
            axis=cfg.sharded.axis, comm_dtype=cfg.mixing.dtype,
            comm=cfg.comm.spec, persist_ef=cfg.comm.persist_ef,
            mix_every=cfg.sharded.mix_every,
            unroll_loops=cfg.sharded.unroll_loops)


def _agent_index(axis):
    """Flat agent index inside shard_map, for tuple axes too."""
    if isinstance(axis, tuple):
        idx = jnp.zeros((), jnp.int32)
        for a in axis:
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axis)


def dagm_local_round(g_fn: Callable, f_fn: Callable,
                     cfg, w: RingWeights,
                     x: Pytree, y: Pytree, batch: Pytree,
                     key=None, channels: dict | None = None,
                     hp: ShardedRoundCoeffs | None = None,
                     flight_gamma=None):
    """One DAGM outer round from a single agent's perspective.

    g_fn(x, y, batch) -> scalar local inner loss  (strongly-convex-ish)
    f_fn(x, y, batch) -> scalar local outer loss
    Must be called inside shard_map over cfg.axis.
    Returns (x⁺, y⁺, metrics), plus the advanced channel dict when
    `channels` was given.

    Every ppermute exchange goes through the `cfg.comm_policy` channel
    (`collectives.ring_mix_c`): identity/bf16 policies reproduce the
    historical paths exactly; compressing policies open per-round
    error-feedback channels for y, h and x.  `key` feeds stochastic
    compressors (folded with the agent index so rows decorrelate); it
    is unused otherwise.

    `channels` (persist_ef mode): this agent's {"inner_y", "dihgp_h",
    "outer_x"} ChannelStates carried over from the previous round —
    EF replicas warm-start instead of reopening at zero (dihgp_h still
    resets its hat: the h vector itself re-initializes every round),
    keys advance inside the states, and the send counters accumulate
    across the whole run.  The caller threads the returned dict into
    the next round.

    `hp` (schedule mode): this round's `ShardedRoundCoeffs`, as traced
    scalars — `repro.solve`'s tier="sharded" driver feeds one per round
    so a single compiled step serves a whole (αₖ, βₖ) schedule.  None
    reproduces the config's constants (bit-identical: the coefficients
    are the very same host-float64 expressions either way).

    `flight_gamma` (flight-recorder mode): this round's penalty
    coefficient γₖ as a traced f32 scalar.  When set, two extra
    per-agent metrics are emitted for the flight row — `flight_gap_sq`
    (‖γ·(I−Ẃ)x + β·cross + ∇ₓf‖², this agent's share of the reference
    tier's Eq. 17b stationarity gap; the sharded update folds the
    γ·lap term into the Ẃx mixing, so it is reconstructed here) and
    `flight_consensus_sq` (‖x − x̄‖², whose agent-mean is exactly
    `consensus_error(x)`).  None — the default — leaves the metrics
    dict and the traced program untouched."""
    from repro.comm import channel_init
    cfg = _as_sharded_cfg(cfg)
    axis = cfg.axis
    if hp is None:
        hp = sharded_round_coeffs(cfg.alpha, cfg.beta, cfg.curvature,
                                  w.w_self)
    pol = cfg.comm_policy

    grad_y_g = jax.grad(g_fn, argnums=1)
    grad_x_f = jax.grad(f_fn, argnums=0)
    grad_y_f = jax.grad(f_fn, argnums=1)

    if channels is not None:
        st_y = channels["inner_y"]
        st_h = channels["dihgp_h"].reset_hat()
        st_x = channels["outer_x"]
    else:
        if pol.stochastic:
            if key is None:
                raise ValueError(
                    f"comm policy {pol.spec!r} draws stochastic "
                    f"compression noise: pass a fresh PRNG key per round "
                    f"(reusing one key would correlate the rounding "
                    f"across rounds and bias the gossip) — "
                    f"make_sharded_dagm's step takes it as its fourth "
                    f"argument")
            key = jax.random.fold_in(key, _agent_index(axis))
        elif key is None:
            key = jax.random.PRNGKey(0)     # threaded but never consumed
        ks = jax.random.split(key, 3)
        st_y = channel_init(pol, "inner_y", y, ks[0])
        st_h = channel_init(pol, "dihgp_h", y, ks[1])
        st_x = channel_init(pol, "outer_x", x, ks[2])

    # ---- inner loop: y ← W y − β ∇_y g  (Eq. 15/16), M rounds ----
    def inner(t, carry):
        yy, st = carry
        if cfg.unroll_loops:
            do_mix = (int(t) % cfg.mix_every) == cfg.mix_every - 1
            mixed, st = ring_mix_c(yy, axis, w, pol, st) if do_mix \
                else (yy, st)
        elif cfg.mix_every > 1:
            mixed, st = jax.lax.cond(
                t % cfg.mix_every == cfg.mix_every - 1,
                lambda z, s: ring_mix_c(z, axis, w, pol, s),
                lambda z, s: (z, s), yy, st)
        else:
            mixed, st = ring_mix_c(yy, axis, w, pol, st)
        return taxpy(hp.neg_beta, grad_y_g(x, yy, batch), mixed), st
    with jax.named_scope("inner_dgd"):
        if cfg.unroll_loops:
            for t in range(cfg.M):
                y, st_y = inner(t, (y, st_y))
        else:
            y, st_y = jax.lax.fori_loop(0, cfg.M, inner, (y, st_y))

    # ---- DIHGP (Alg. 1, scalar-preconditioned, matrix-free) ----
    def hvp(v):
        return jax.jvp(lambda yy: grad_y_g(x, yy, batch), (y,), (v,))[1]

    def H_apply(hh, st):
        lap, st = ring_laplacian_c(hh, axis, w, pol, st)
        return taxpy(hp.beta, hvp(hh), lap), st

    def dihgp_iter(_, carry):
        hh, st = carry
        bh_mix, st = H_apply(hh, st)
        bh = tsub(tscale(hp.d, hh), bh_mix)            # B̃ h
        return tscale(hp.inv_d, tsub(bh, p)), st
    with jax.named_scope("dihgp"):
        p = grad_y_f(x, y, batch)
        h = tscale(hp.neg_inv_d, p)
        if cfg.unroll_loops:
            for _ in range(cfg.U):
                h, st_h = dihgp_iter(0, (h, st_h))
        else:
            h, st_h = jax.lax.fori_loop(0, cfg.U, dihgp_iter,
                                        (h, st_h))

    # ---- outer hyper-gradient (Eq. 17b) and step, with the round's
    # metrics (evaluated at the same (x, ỹ)) ----
    def cross(xx):
        return tdot(jax.grad(g_fn, argnums=1)(xx, y, batch), h)

    with jax.named_scope("outer_step"):
        cross_term = jax.grad(cross)(x)
        d_dir = taxpy(hp.beta, cross_term, grad_x_f(x, y, batch))
        mixed_x, st_x = ring_mix_c(x, axis, w, pol, st_x)
        x_new = taxpy(hp.neg_alpha, d_dir, mixed_x)    # Ẃx − α(...)

        metrics = {
            "outer_loss": f_fn(x, y, batch),
            "inner_loss": g_fn(x, y, batch),
            "hypergrad_norm": tnorm(d_dir),
            "consensus_x": tnorm(ring_laplacian(x, cfg.axis, w)),
            # gossip exchanges, from the traced channel counters (feeds
            # sharded_comm_ledger for the byte accounting): this
            # round's when channels reopen per round, cumulative under
            # persist_ef
            "comm_sends": (st_y.sends + st_h.sends + st_x.sends)
            .astype(jnp.float32),
        }  # consensus metric uses full-precision exchange (diagnostic)
        if flight_gamma is not None:
            gamma = jnp.asarray(flight_gamma, jnp.float32)
            gap_t = tadd(tscale(gamma, ring_laplacian(x, cfg.axis, w)),
                         d_dir)
            xbar = jax.tree.map(lambda a: jax.lax.pmean(a, axis), x)
            metrics["flight_gap_sq"] = tdot(gap_t, gap_t).real
            diff = tsub(x, xbar)
            metrics["flight_consensus_sq"] = tdot(diff, diff).real
    if channels is not None:
        return x_new, y, metrics, \
            {"inner_y": st_y, "dihgp_h": st_h, "outer_x": st_x}
    return x_new, y, metrics


def make_sharded_dagm(g_fn: Callable, f_fn: Callable,
                      cfg, mesh: Mesh,
                      x_spec=None, y_spec=None, batch_spec=None,
                      manual_axes=None, jit_step: bool = True,
                      schedule_hp: bool = False, recorder=None):
    """Jitted global DAGM step over `mesh`.

    `cfg` is a `repro.solve.SolverSpec` (tier="sharded") or a legacy
    `ShardedDAGMConfig`.  With ``schedule_hp=True`` the returned step
    takes a trailing `ShardedRoundCoeffs` operand (replicated) so one
    compiled step serves a whole per-round schedule — the
    `repro.solve` tier="sharded" driver's mode.

    Global layout: x and y pytrees carry a leading agent axis of size
    n_agents = mesh size of cfg.axis (sharded 1-per-agent); batch leaves
    carry a leading agent axis likewise.

    `manual_axes` (default: {cfg.axis}) are the mesh axes shard_map
    handles manually; every other mesh axis (e.g. "model") is *auto* —
    GSPMD tensor-parallelizes the per-agent computation over it, so the
    paper's agent-parallel ring composes with model parallelism inside
    each agent (DESIGN.md §2: model-parallel sharding lives inside an
    agent).

    When `cfg.comm_policy` is stochastic (int8/int4/rand_k gossip) the
    returned step takes a fourth argument, a replicated PRNG key:
    ``step(x, y, batch, key)``; deterministic policies keep the
    historical 3-argument signature.

    With ``cfg.persist_ef`` the step instead carries the gossip channel
    states across rounds: ``step(x, y, batch, channels) -> (x, y,
    metrics, channels)`` with `channels` from `open_sharded_channels`
    (keys live inside the states, so stochastic policies need no
    per-round key argument in this mode).

    `recorder` (a `repro.obs.RecorderSpec`, needs ``schedule_hp=True``)
    threads a `FlightBuffer` through the step: the signature grows a
    trailing ``(gamma, rec)`` pair — this round's penalty coefficient
    γₖ (replicated f32 scalar) and the buffer — and the step returns
    the advanced buffer last, having appended one flight row per call
    (reference-tier field semantics: agent-summed Eq. 17b gap, γₖ ×
    consensus_error(x), *cumulative* exact wire bytes = round-count ×
    the one-round `sharded_comm_ledger` charge, alive fraction 1.0 —
    the sharded tier threads no fault masks).  The write is a pure
    `recorder_write` on the replicated metrics outside the shard_map
    body, so it adds no communication; with ``recorder=None`` the
    historical program is built untouched.
    """
    cfg = _as_sharded_cfg(cfg)
    ax = cfg.axis
    ax_names = ax if isinstance(ax, tuple) else (ax,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in ax_names:
        n *= sizes[a]
    w = RingWeights.metropolis_ring(n)
    xs = x_spec if x_spec is not None else P(ax)
    ys = y_spec if y_spec is not None else P(ax)
    bs = batch_spec if batch_spec is not None else P(ax)
    manual = frozenset(manual_axes) if manual_axes is not None         else frozenset(ax_names)
    stochastic = cfg.comm_policy.stochastic

    squeeze = lambda t: jax.tree.map(lambda a: a[0], t)
    expand = lambda t: jax.tree.map(lambda a: a[None], t)

    def local_step(x, y, batch, key=None, hp=None):
        # strip the (size-1) leading agent axis inside the shard
        x1, y1, m = dagm_local_round(g_fn, f_fn, cfg, w,
                                     squeeze(x), squeeze(y),
                                     squeeze(batch), key=key, hp=hp)
        m = jax.tree.map(lambda s: jax.lax.pmean(s, ax), m)
        return expand(x1), expand(y1), m

    def local_step_persist(x, y, batch, cs, hp=None):
        x1, y1, m, cs1 = dagm_local_round(g_fn, f_fn, cfg, w,
                                          squeeze(x), squeeze(y),
                                          squeeze(batch),
                                          channels=squeeze(cs), hp=hp)
        m = jax.tree.map(lambda s: jax.lax.pmean(s, ax), m)
        return expand(x1), expand(y1), m, expand(cs1)

    kw = {}
    if manual != frozenset(mesh.axis_names):
        kw["axis_names"] = manual
    if recorder is not None:
        if not schedule_hp:
            raise ValueError(
                "the sharded flight recorder needs schedule_hp=True: "
                "each row carries that round's penalty coefficient γₖ, "
                "which only exists as a traced operand in schedule "
                "mode (repro.solve's tier='sharded' driver)")
        return _make_recorded_step(g_fn, f_fn, cfg, mesh, w, n,
                                   xs, ys, bs, kw, stochastic,
                                   squeeze, expand, jit_step), w
    if cfg.persist_ef:
        if schedule_hp:
            step = shard_map(local_step_persist, mesh=mesh,
                             in_specs=(xs, ys, bs, P(ax), P()),
                             out_specs=(xs, ys, P(), P(ax)),
                             check_vma=False, **kw)
        else:
            step = shard_map(lambda x, y, b, cs:
                             local_step_persist(x, y, b, cs),
                             mesh=mesh, in_specs=(xs, ys, bs, P(ax)),
                             out_specs=(xs, ys, P(), P(ax)),
                             check_vma=False, **kw)
    elif stochastic:
        if schedule_hp:
            step = shard_map(local_step, mesh=mesh,
                             in_specs=(xs, ys, bs, P(), P()),
                             out_specs=(xs, ys, P()), check_vma=False,
                             **kw)
        else:
            step = shard_map(lambda x, y, b, k: local_step(x, y, b, k),
                             mesh=mesh, in_specs=(xs, ys, bs, P()),
                             out_specs=(xs, ys, P()), check_vma=False,
                             **kw)
    elif schedule_hp:
        step = shard_map(lambda x, y, b, hp:
                         local_step(x, y, b, hp=hp),
                         mesh=mesh, in_specs=(xs, ys, bs, P()),
                         out_specs=(xs, ys, P()), check_vma=False, **kw)
    else:
        step = shard_map(lambda x, y, b: local_step(x, y, b),
                        mesh=mesh, in_specs=(xs, ys, bs),
                        out_specs=(xs, ys, P()), check_vma=False, **kw)
    if not jit_step:
        return step, w
    # jit through the shared obs trace counter: the sharded tier's
    # host-driven round loop calls this step K times, so a retrace
    # (anything but jit_traces_total{name="sharded_dagm_step"} == 1
    # per program) would multiply compile cost K-fold — the same
    # zero-retrace telemetry the serve engine and benches publish
    from repro.obs import TraceCounter
    return TraceCounter("sharded_dagm_step").wrap(step), w


def _make_recorded_step(g_fn, f_fn, cfg, mesh, w, n, xs, ys, bs, kw,
                        stochastic, squeeze, expand, jit_step):
    """The flight-recorder twin of `make_sharded_dagm`'s step builder
    (kept separate so the recorder-off construction stays literally the
    historical code).  See `make_sharded_dagm` for the signature the
    returned step exposes."""
    from repro.obs import TraceCounter
    from repro.obs.recorder import recorder_write
    ax = cfg.axis

    def local_flight(x, y, batch, key=None, hp=None, gamma=None):
        x1, y1, m = dagm_local_round(
            g_fn, f_fn, cfg, w, squeeze(x), squeeze(y), squeeze(batch),
            key=key, hp=hp, flight_gamma=gamma)
        m = jax.tree.map(lambda s: jax.lax.pmean(s, ax), m)
        return expand(x1), expand(y1), m

    def local_flight_persist(x, y, batch, cs, hp=None, gamma=None):
        x1, y1, m, cs1 = dagm_local_round(
            g_fn, f_fn, cfg, w, squeeze(x), squeeze(y), squeeze(batch),
            channels=squeeze(cs), hp=hp, flight_gamma=gamma)
        m = jax.tree.map(lambda s: jax.lax.pmean(s, ax), m)
        return expand(x1), expand(y1), m, expand(cs1)

    def _round_bytes(x, y) -> float:
        # host constant captured at trace time: one round's exact
        # ledger charge, from per-agent leaf *shapes* only
        local = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            (x, y))
        return float(sharded_comm_ledger(
            cfg, local[0], local[1], rounds=1).total_bytes)

    def _write_row(m, gamma, rec, x, y):
        m = dict(m)
        # pmean gave agent means; the reference gap is the agent *sum*,
        # while consensus_error already divides by n — see FIELDS docs
        gap = m.pop("flight_gap_sq") * np.float32(n)
        cons = m.pop("flight_consensus_sq")
        wire = (rec.count + 1).astype(jnp.float32) \
            * jnp.float32(_round_bytes(x, y))
        rec = recorder_write(rec, {
            "outer_gap_sq": gap,
            "penalty": jnp.asarray(gamma, jnp.float32) * cons,
            "wire_bytes": wire,
            "alive_fraction": jnp.ones((), jnp.float32)})
        return m, rec

    if cfg.persist_ef:
        core = shard_map(local_flight_persist, mesh=mesh,
                         in_specs=(xs, ys, bs, P(ax), P(), P()),
                         out_specs=(xs, ys, P(), P(ax)),
                         check_vma=False, **kw)

        def step(x, y, batch, cs, hp, gamma, rec):
            x1, y1, m, cs1 = core(x, y, batch, cs, hp, gamma)
            m, rec = _write_row(m, gamma, rec, x, y)
            return x1, y1, m, cs1, rec
    elif stochastic:
        core = shard_map(local_flight, mesh=mesh,
                         in_specs=(xs, ys, bs, P(), P(), P()),
                         out_specs=(xs, ys, P()), check_vma=False,
                         **kw)

        def step(x, y, batch, key, hp, gamma, rec):
            x1, y1, m = core(x, y, batch, key, hp, gamma)
            m, rec = _write_row(m, gamma, rec, x, y)
            return x1, y1, m, rec
    else:
        core = shard_map(lambda x, y, b, hp, gamma:
                         local_flight(x, y, b, hp=hp, gamma=gamma),
                         mesh=mesh, in_specs=(xs, ys, bs, P(), P()),
                         out_specs=(xs, ys, P()), check_vma=False,
                         **kw)

        def step(x, y, batch, hp, gamma, rec):
            x1, y1, m = core(x, y, batch, hp, gamma)
            m, rec = _write_row(m, gamma, rec, x, y)
            return x1, y1, m, rec

    if not jit_step:
        return step
    return TraceCounter("sharded_dagm_step").wrap(step)


def open_sharded_channels(cfg, x: Pytree, y: Pytree,
                          seed: int = 0) -> dict:
    """Globally-stacked gossip ChannelStates for the persist_ef step.

    `x` / `y` are the *global* pytrees with a leading agent axis n
    (sharded 1-per-agent, the same layout `make_sharded_dagm` expects):
    each agent's slice holds its EF replica (zeros at open), its
    compressor PRNG key (decorrelated by agent index, the same fold-in
    protocol `dagm_local_round` uses when reopening per round) and its
    traced send counter.  Shard with `P(cfg.axis)` — the step's
    in/out_specs already do."""
    from repro.comm import ChannelState
    pol = _as_sharded_cfg(cfg).comm_policy
    n = jax.tree.leaves(y)[0].shape[0]
    keys = jax.vmap(lambda i: jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), i), 3))(
            jnp.arange(n))                                    # (n, 3, 2)

    def mk(name, tpl, k):
        if pol.ef:
            hat = jax.tree.map(jnp.zeros_like, tpl)
        else:
            hat = jnp.zeros((n,), jnp.float32)
        return ChannelState(hat=hat, key=k,
                            sends=jnp.zeros((n,), jnp.int32), name=name)

    return {"inner_y": mk("inner_y", y, keys[:, 0]),
            "dihgp_h": mk("dihgp_h", y, keys[:, 1]),
            "outer_x": mk("outer_x", x, keys[:, 2])}


def sharded_comm_ledger(cfg, x: Pytree, y: Pytree,
                        rounds: int = 1):
    """Byte-accurate CommLedger for the sharded DAGM round.

    `x` / `y` are one agent's *local* pytrees (or the stacked globals —
    only leaf shapes after the agent axis matter is the caller's
    responsibility; pass local views).  Per-leaf wire cost uses the
    configured `comm_policy` compressor, one row per leaf — exactly
    what `ring_mix_c` transmits.  Sends per round mirror the local
    round's loop structure (inner M//mix_every, DIHGP U, outer 1); the
    `comm_sends` metric emitted by `dagm_local_round` cross-checks the
    total at runtime.  The diagnostic full-precision consensus exchange
    is excluded (it is not part of the algorithm's traffic)."""
    from repro.comm import CommLedger
    cfg = _as_sharded_cfg(cfg)
    comp = cfg.comm_policy.compressor
    spec = cfg.comm_policy.spec

    def tree_cost(tree):
        leaves = jax.tree.leaves(tree)
        return (sum(comp.payload_bytes(l.shape) for l in leaves),
                sum(comp.payload_floats(l.shape) for l in leaves))

    inner_sends = sum(1 for t in range(cfg.M)
                      if t % cfg.mix_every == cfg.mix_every - 1)
    led = CommLedger("dagm_sharded")
    for name, tree, per_round in (("inner_y", y, inner_sends),
                                  ("dihgp_h", y, cfg.U),
                                  ("outer_x", x, 1)):
        bytes_per, floats_per = tree_cost(tree)
        led.add_channel(name, (floats_per,), spec=spec,
                        sends=rounds * per_round,
                        floats_per_send=floats_per,
                        bytes_per_send=bytes_per)
    return led
