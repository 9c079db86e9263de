"""Baselines the paper compares against (Table 2, Figs. 4–5).

All baselines are implemented to (a) actually optimize the same stacked
bilevel problems and (b) *faithfully reproduce the communication pattern*
that Table 2 / Appendix S1 charges them for — DGBO gossips d2×d2 Hessian
estimate matrices, DGTBO's JHIP oracle gossips d2×d1 matrices, FedNest
routes everything through a star center.  Each run returns the same
metric traces as DAGM plus exact communication counters so
benchmarks/table2 can compare measured bytes with the closed forms.

These are deterministic full-gradient variants (the paper's Table 1/2
setting is deterministic); stochastic mini-batching is orthogonal.

Entry surface: `repro.solve.solve(prob, net, SolverSpec(method=...))`
with method "dgbo" | "dgtbo" | "ma_dbo" | "fednest" — hyper-parameters
are runtime per-round operands there, so the step-size sequences of
Chen, Huang & Ma (2022) / Dong et al. (2023) are expressible.  The
historical ``dgbo_run(prob, net, alpha=..., beta=...)`` kwargs survive
below as deprecation shims lowering onto SolverSpec; with constant
schedules they reproduce the pre-redesign trajectories bit-for-bit
(multiplications by traced scalars are identical to folded literals,
and MA-DBO's penalty division is the same float32-reciprocal multiply
as DAGM's — regression-tested in tests/test_comm.py).

Every gossip/consensus application routes through `mixing.mix_apply` on
a `MixingOp`, so the baselines run on the same topology-aware sparse
backend as DAGM — their Table 2 cost gap vs DAGM is in *what* they
communicate (matrices), not in how the mixing is executed.

Communication accounting is two-sided: `comm_floats_per_round` keeps
the Appendix-S1 *closed forms* (what the papers charge), while
`BaselineResult.ledger` is the `repro.comm.CommLedger` charged from the
gossips this implementation *actually executes*.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .dagm import RoundHP, default_metrics
from .dihgp import dihgp_dense_c
from .mixing import Network, laplacian_apply_c, make_mixing_op, \
    mix_apply_c
from .penalty import inner_dgd_step_c
from .problems import BilevelProblem

Array = jnp.ndarray


@dataclasses.dataclass
class BaselineResult:
    x: Array
    y: Array
    metrics: dict[str, Array]
    comm_floats_per_round: int      # per-agent scalars per outer round
    #                                 (Appendix-S1 closed form)
    name: str = ""
    ledger: "object | None" = None  # measured traffic (CommLedger)


def _open_channels(W, templates: dict, seed: int):
    """Comm channels on the MixingOp, one per gossiped variable (the
    shared key-derivation protocol lives in repro.comm)."""
    from repro.comm import open_channels
    return open_channels(W, templates, seed)


def _mixing_op(net: Network, spec):
    from repro.solve.spec import mixing_kwargs
    return make_mixing_op(net, **mixing_kwargs(spec))


def _init_xy(prob: BilevelProblem, x0, y0, seed: int):
    n, d1, d2 = prob.n, prob.d1, prob.d2
    if x0 is None:
        x0 = jnp.zeros((n, d1), jnp.float32)
    if y0 is None:
        y0 = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (n, d2))
    return x0, y0


def _run_scan(body, carry0, hp: RoundHP, K: int):
    hp = RoundHP(*(jnp.asarray(a, jnp.float32) for a in hp))

    @jax.jit
    def run(carry0, hp):
        return jax.lax.scan(body, carry0, hp, length=K)
    return run(carry0, hp)


# ---------------------------------------------------------------------------
# DGBO  [Yang, Zhang & Wang, NeurIPS 2022] — gossip-based; communicates the
# full d2×d2 Hessian estimate in its inner Neumann loop (Appendix S1-II).
# ---------------------------------------------------------------------------

def dgbo_solve(prob: BilevelProblem, net: Network, spec, hp: RoundHP,
               x0=None, y0=None, seed: int = 0):
    """Deterministic DGBO: gossip consensus on x, y, grads, Jacobians and
    a gossip+Neumann estimate of the *global mean* Hessian (d2×d2 matrix
    communication — the expensive part the paper improves on).

    Hyper-parameters arrive as (K,) runtime operands in `hp`."""
    W = _mixing_op(net, spec)
    n, d1, d2 = prob.n, prob.d1, prob.d2
    M, b = spec.M, spec.b
    x0, y0 = _init_xy(prob, x0, y0, seed)
    cs0 = _open_channels(
        W, {"inner_y": y0, "hess_nu": jnp.zeros((n, d2, d2)),
            "outer_x": x0}, seed)

    def body(carry, hp_t):
        (x, y), cs = carry
        alpha, beta = hp_t.alpha, hp_t.beta
        # inner: gossip DGD on the *mean* inner objective (Steps 5)
        def inner(t, c):
            yy, st = c
            mixed, st = mix_apply_c(W, yy, st)
            return mixed - beta * prob.grad_y_g(x, yy), st
        y1, y_st = jax.lax.fori_loop(0, M, inner, (y, cs["inner_y"]))

        # Hessian estimate via b gossip rounds on local Hessians (Steps
        # 10–13): nu_i ← Σ_j w_ij nu_j, starting from ∇²_y g_i.  After b
        # rounds nu_i ≈ mean Hessian; matrices are what gets communicated.
        nu = prob.hess_yy_g(x, y1)                       # (n, d2, d2)
        def gossip_h(t, c):
            return mix_apply_c(W, c[0], c[1])
        nu, nu_st = jax.lax.fori_loop(0, b, gossip_h,
                                      (nu, cs["hess_nu"].reset_hat()))

        # per-agent Neumann-style solve with the estimated global Hessian
        p = prob.grad_y_f(x, y1)
        h = -jax.vmap(jnp.linalg.solve)(
            nu + 1e-6 * jnp.eye(d2, dtype=nu.dtype), p)
        # hyper-gradient + gossip consensus step on x (Step 4)
        d = prob.grad_x_f(x, y1) + prob.cross_xy_g_times(x, y1, h)
        mixed_x, x_st = mix_apply_c(W, x, cs["outer_x"])
        x1 = mixed_x - alpha * d
        cs = {"inner_y": y_st, "hess_nu": nu_st, "outer_x": x_st}
        return ((x1, y1), cs), default_metrics(prob, x, y1)

    ((x, y), cs), metrics = _run_scan(body, ((x0, y0), cs0), hp, spec.K)
    W.ledger.charge_states(cs.values())
    # per-agent floats per round: x,y,grad-est vectors + b Hessian matrices
    # + one d1×d2 Jacobian (Appendix S1: K(b d2² + 2(d1+d2) + d1 d2))
    floats = b * d2 * d2 + 2 * (d1 + d2) + d1 * d2 + M * d2
    return x, y, metrics, cs, W.ledger, floats, "DGBO"


# ---------------------------------------------------------------------------
# DGTBO  [Chen, Huang & Ma, 2022] — gradient tracking + JHIP oracle that
# communicates d2×d1 matrices (Appendix S1-III).
# ---------------------------------------------------------------------------

def dgtbo_solve(prob: BilevelProblem, net: Network, spec, hp: RoundHP,
                x0=None, y0=None, seed: int = 0):
    """Deterministic DGTBO: JHIP solves Z ≈ −J H^{-1} (d1×d2) by N
    decentralized Richardson iterations, each gossiping the full Z matrix."""
    W = _mixing_op(net, spec)
    n, d1, d2 = prob.n, prob.d1, prob.d2
    M, N = spec.M, spec.N
    x0, y0 = _init_xy(prob, x0, y0, seed)
    cs0 = _open_channels(
        W, {"inner_y": y0, "jhip_z": jnp.zeros((n, d1, d2)),
            "outer_x": x0}, seed)

    def cross_jac(x, y):
        """(n, d1, d2) full local Jacobians ∇²_xy g_i (what JHIP needs)."""
        def one(xi, yi, di):
            jac = jax.jacobian(
                lambda xx: jax.grad(prob.g, argnums=1)(xx, yi, di))(xi)
            return jac.T                       # (d2, d1) -> (d1, d2)
        return jax.vmap(one)(x, y, prob.data)

    def body(carry, hp_t):
        (x, y), cs = carry
        alpha, beta = hp_t.alpha, hp_t.beta
        def inner(t, c):            # gossip DGD inner loop (Steps 8–9)
            yy, st = c
            mixed, st = mix_apply_c(W, yy, st)
            return mixed - beta * prob.grad_y_g(x, yy), st
        y1, y_st = jax.lax.fori_loop(0, M, inner, (y, cs["inner_y"]))

        Hg = prob.hess_yy_g(x, y1)                      # (n,d2,d2) local
        Jg = cross_jac(x, y1)                           # (n,d1,d2) local
        # JHIP: solve (mean H) Zᵀ = (mean J)ᵀ decentralized: Richardson
        # iterations with gossip averaging of Z (matrix communication).
        lam = 1.0 / (1.0 + jnp.max(jnp.abs(Hg)))
        Z = jnp.zeros((n, d1, d2), Jg.dtype)
        def jhip(t, c):
            Z, st = c
            R = Jg - jnp.einsum("nij,njk->nik", Z, Hg)  # local residual
            Z = Z + lam * R
            return mix_apply_c(W, Z, st)                # gossip Z (d1·d2)
        Z, z_st = jax.lax.fori_loop(0, N, jhip,
                                    (Z, cs["jhip_z"].reset_hat()))

        p = prob.grad_y_f(x, y1)
        d = prob.grad_x_f(x, y1) - jnp.einsum("nij,nj->ni", Z, p)
        mixed_x, x_st = mix_apply_c(W, x, cs["outer_x"])
        x1 = mixed_x - alpha * d
        cs = {"inner_y": y_st, "jhip_z": z_st, "outer_x": x_st}
        return ((x1, y1), cs), default_metrics(prob, x, y1)

    ((x, y), cs), metrics = _run_scan(body, ((x0, y0), cs0), hp, spec.K)
    W.ledger.charge_states(cs.values())
    # Appendix S1: K n (M d2 + d1 + n N d1 d2) / n per agent per round:
    floats = M * d2 + d1 + N * d1 * d2
    return x, y, metrics, cs, W.ledger, floats, "DGTBO"


# ---------------------------------------------------------------------------
# FedNest  [Tarzanagh et al., ICML 2022] — star topology (federated).
# ---------------------------------------------------------------------------

def fednest_solve(prob: BilevelProblem, net: Network | None, spec,
                  hp: RoundHP, x0=None, y0=None, seed: int = 0):
    """Centralized-server bilevel: the server holds global (x, y); each
    round clients send gradients/HVPs (vectors) up and receive the global
    iterate back.  Hyper-gradient via U-term Neumann series on the *mean*
    Hessian using client HVPs (FedIHGP) — vector communication, but all
    through the center (2n vector transfers per exchange)."""
    n, d1, d2 = prob.n, prob.d1, prob.d2
    M, U = spec.M, spec.U
    key = jax.random.PRNGKey(seed)
    xg = jnp.zeros((d1,), jnp.float32) if x0 is None else jnp.mean(x0, 0)
    yg = 0.01 * jax.random.normal(key, (d2,)) if y0 is None else jnp.mean(y0, 0)

    def stacked(z):
        return jnp.broadcast_to(z, (n,) + z.shape)

    def body(carry, hp_t):
        x, y = carry
        alpha, beta = hp_t.alpha, hp_t.beta
        xs = stacked(x)
        def inner(t, yy):
            gy = jnp.mean(prob.grad_y_g(xs, stacked(yy)), 0)
            return yy - beta * gy
        y1 = jax.lax.fori_loop(0, M, inner, y)

        ys = stacked(y1)
        # Neumann IHGP on mean Hessian: h ← h − η(H̄ h) + ... standard
        p = jnp.mean(prob.grad_y_f(xs, ys), 0)
        hvp = lambda v: jnp.mean(prob.hvp_yy_g(xs, ys, stacked(v)), 0)
        lam = 1.0 / (1.0 + jnp.sqrt(jnp.sum(hvp(p / (1e-12 + jnp.linalg.norm(p))) ** 2)))
        h = -lam * p
        def neumann(u, h):
            return h - lam * (hvp(h)) - lam * p
        h = jax.lax.fori_loop(0, U, neumann, h)

        d = jnp.mean(prob.grad_x_f(xs, ys), 0) \
            + jnp.mean(prob.cross_xy_g_times(xs, ys, stacked(h)), 0)
        x1 = x - alpha * d
        return (x1, y1), default_metrics(prob, stacked(x), ys)

    (x, y), metrics = _run_scan(body, (xg, yg), hp, spec.K)
    # per client per round: M+U+2 vector up/downs through the center
    floats = 2 * ((M + 1) * d2 + (U + 1) * d2 + d1)
    # star routing never touches a MixingOp — static ledger describing
    # the up+down transfers the simulation's means stand in for
    from repro.comm import static_ledger
    ledger = static_ledger("identity", [
        ("inner_updown", (d2,), spec.K * 2 * (M + 1)),
        ("ihgp_updown", (d2,), spec.K * 2 * (U + 1)),
        ("outer_updown", (d1,), spec.K * 2),
    ], name="fednest")
    return stacked(x), stacked(y), metrics, None, ledger, floats, \
        "FedNest"


# ---------------------------------------------------------------------------
# MA-DBO  [Chen et al., ICML 2023] — momentum-assisted decentralized
# bilevel (vector communication, momentum on the hyper-gradient).
# ---------------------------------------------------------------------------

def madbo_solve(prob: BilevelProblem, net: Network, spec, hp: RoundHP,
                x0=None, y0=None, seed: int = 0):
    W = _mixing_op(net, spec)
    M, U, momentum = spec.M, spec.U, spec.momentum
    x0, y0 = _init_xy(prob, x0, y0, seed)
    d1, d2 = prob.d1, prob.d2
    v0 = jnp.zeros_like(x0)
    cs0 = _open_channels(
        W, {"inner_y": y0, "dihgp_h": y0, "lap_x": x0, "tracker_v": v0},
        seed)

    def body(carry, hp_t):
        (x, y, v), cs = carry
        alpha, beta, gamma = hp_t.alpha, hp_t.beta, hp_t.gamma
        def inner(t, c):
            yy, st = c
            return inner_dgd_step_c(prob, W, beta, x, yy, st)
        y1, y_st = jax.lax.fori_loop(0, M, inner, (y, cs["inner_y"]))
        h, h_st = dihgp_dense_c(prob, W, beta, x, y1, U,
                                cs["dihgp_h"].reset_hat())
        lap_x, lx_st = laplacian_apply_c(W, x, cs["lap_x"])
        d = lap_x * gamma + prob.grad_x_f(x, y1) \
            + beta * prob.cross_xy_g_times(x, y1, h)
        v1 = momentum * v + (1.0 - momentum) * d
        v1, v_st = mix_apply_c(W, v1, cs["tracker_v"])   # gossip tracker
        x1 = x - alpha * v1
        cs = {"inner_y": y_st, "dihgp_h": h_st, "lap_x": lx_st,
              "tracker_v": v_st}
        return ((x1, y1, v1), cs), default_metrics(prob, x, y1)

    ((x, y, _), cs), metrics = _run_scan(body, ((x0, y0, v0), cs0), hp,
                                         spec.K)
    W.ledger.charge_states(cs.values())
    floats = M * d2 + U * d2 + 2 * d1          # extra d1 for the tracker
    return x, y, metrics, cs, W.ledger, floats, "MA-DBO"


BASELINE_SOLVERS = {
    "dgbo": dgbo_solve,
    "dgtbo": dgtbo_solve,
    "fednest": fednest_solve,
    "ma_dbo": madbo_solve,
}


# ---------------------------------------------------------------------------
# Legacy kwargs shims (deprecated — lower onto SolverSpec + solve)
# ---------------------------------------------------------------------------

def _baseline_shim(method: str, legacy_name: str, prob, net, *,
                   alpha, beta, K, M, x0, y0, seed,
                   mixing="auto", mixing_interpret=None,
                   mixing_dtype="f32", comm="identity", **method_kw):
    from repro.solve import solve
    from repro.solve._compat import warn_once
    from repro.solve.spec import (CommSpec, MixingSpec, ScheduleSpec,
                                  SolverSpec)
    warn_once(
        legacy_name,
        f"{legacy_name}(prob, net, alpha=..., beta=...) is deprecated: "
        f"use repro.solve.solve(prob, net, "
        f"SolverSpec(method={method!r}, ...)) — schedules replace the "
        f"scalar kwargs")
    spec = SolverSpec(
        method=method, tier="reference", K=K, M=M,
        schedule=ScheduleSpec(alpha=alpha, beta=beta),
        mixing=MixingSpec(backend=mixing, interpret=mixing_interpret,
                          dtype=mixing_dtype),
        comm=CommSpec(spec=comm), **method_kw)
    res = solve(prob, net, spec, x0=x0, y0=y0, seed=seed)
    return BaselineResult(
        res.x, res.y, res.metrics,
        res.extras["comm_floats_per_round"],
        name=res.extras["name"], ledger=res.ledger)


def dgbo_run(prob: BilevelProblem, net: Network, *, alpha: float,
             beta: float, K: int, M: int = 10, b: int = 3,
             x0: Array | None = None, y0: Array | None = None,
             seed: int = 0, **mix_kw) -> BaselineResult:
    """Deprecated shim — `solve(prob, net, SolverSpec(method="dgbo"))`."""
    return _baseline_shim("dgbo", "dgbo_run", prob, net, alpha=alpha,
                          beta=beta, K=K, M=M, x0=x0, y0=y0, seed=seed,
                          b=b, **mix_kw)


def dgtbo_run(prob: BilevelProblem, net: Network, *, alpha: float,
              beta: float, K: int, M: int = 10, N: int = 5,
              x0: Array | None = None, y0: Array | None = None,
              seed: int = 0, **mix_kw) -> BaselineResult:
    """Deprecated shim — `solve(prob, net, SolverSpec(method="dgtbo"))`."""
    return _baseline_shim("dgtbo", "dgtbo_run", prob, net, alpha=alpha,
                          beta=beta, K=K, M=M, x0=x0, y0=y0, seed=seed,
                          N=N, **mix_kw)


def fednest_run(prob: BilevelProblem, net: Network | None, *,
                alpha: float, beta: float, K: int, M: int = 10,
                U: int = 3, x0: Array | None = None,
                y0: Array | None = None, seed: int = 0
                ) -> BaselineResult:
    """Deprecated shim — `solve(prob, None, SolverSpec(method="fednest"))`."""
    return _baseline_shim("fednest", "fednest_run", prob, net,
                          alpha=alpha, beta=beta, K=K, M=M, x0=x0,
                          y0=y0, seed=seed, U=U)


def madbo_run(prob: BilevelProblem, net: Network, *, alpha: float,
              beta: float, K: int, M: int = 10, U: int = 3,
              momentum: float = 0.9, x0: Array | None = None,
              y0: Array | None = None, seed: int = 0,
              **mix_kw) -> BaselineResult:
    """Deprecated shim — `solve(prob, net, SolverSpec(method="ma_dbo"))`."""
    return _baseline_shim("ma_dbo", "madbo_run", prob, net, alpha=alpha,
                          beta=beta, K=K, M=M, x0=x0, y0=y0, seed=seed,
                          U=U, momentum=momentum, **mix_kw)
