"""Layered solver specification for the `repro.solve` front-end.

One frozen pytree-of-specs describes a complete decentralized bilevel
run across every tier:

    SolverSpec(method="dagm", tier="reference", K=..., M=..., U=...,
               schedule=ScheduleSpec(alpha=..., beta=..., gamma=...),
               mixing=MixingSpec(...), comm=CommSpec(...),
               sharded=ShardedSpec(...))

* `ScheduleSpec` — the run's hyper-parameter *sequences*.  Each of
  α/β/γ is a constant, a `repro.optim` schedule callable, or an
  explicit length-K tuple; `materialize()` lowers all three to (K,)
  float32 arrays that enter the compiled programs as **traced
  per-round operands**.  One compile therefore serves any sweep, and
  the paper's decaying-αₖ/βₖ, growing-γₖ corollaries become runnable.
* `MixingSpec` — the (I−W)·Y execution backend (repro.topology).
* `CommSpec`   — the gossip wire policy (repro.comm) + EF persistence.
* `ShardedSpec`— mesh wiring knobs of the `distributed` tier.

Bit-exactness contract: with constant schedules the traced-operand
programs reproduce the legacy literal-hyper-parameter trajectories
bit-for-bit.  Multiplications by a traced f32 scalar are identical to
multiplications by the folded literal, and the one division in the hot
loop — the penalty term (I−Ẃ)x/α — is expressed as multiplication by
γ = float32(1)/float32(α), which is exactly what XLA's
division-by-literal folding computes (regression-tested against
inline legacy loops in tests/test_comm.py and tests/test_solve.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from . import _compat

METHODS = ("dagm", "dgbo", "dgtbo", "ma_dbo", "fednest")
TIERS = ("reference", "sharded", "serve")

#: Schedule field: constant, `repro.optim` schedule, or length-K tuple.
ScheduleLike = "float | Callable | tuple[float, ...] | None"


def _freeze_sequence(val):
    """Lists/arrays become tuples so specs stay hashable pytree leaves."""
    if isinstance(val, (list, np.ndarray)):
        return tuple(float(v) for v in np.asarray(val).ravel())
    return val


@dataclasses.dataclass(frozen=True)
class ScheduleSpec:
    """Runtime hyper-parameter sequences (per outer round k < K).

    alpha: outer step size αₖ.
    beta:  inner step size βₖ (also the inner penalty 1/βₖ).
    gamma: outer penalty coefficient γₖ multiplying (I−Ẃ)x in the
           Eq. (17b) hyper-gradient.  None (default) keeps the paper's
           coupling γₖ = 1/αₖ; an explicit schedule decouples a growing
           penalty from a decaying step size.
    """
    alpha: Any = 1e-2
    beta: Any = 1e-2
    gamma: Any = None

    def __post_init__(self):
        object.__setattr__(self, "alpha", _freeze_sequence(self.alpha))
        object.__setattr__(self, "beta", _freeze_sequence(self.beta))
        object.__setattr__(self, "gamma", _freeze_sequence(self.gamma))

    @property
    def is_constant(self) -> bool:
        return all(not callable(v) and not isinstance(v, tuple)
                   for v in (self.alpha, self.beta, self.gamma))

    def materialize(self, K: int) -> "RoundSchedules":
        """(K,) float32 arrays for α/β/γ (γ = f32(1)/f32(α) when None —
        the bit-exact twin of XLA's division-by-literal folding)."""
        alpha = _materialize_one(self.alpha, K, "alpha")
        beta = _materialize_one(self.beta, K, "beta")
        for name, arr in (("alpha", alpha), ("beta", beta)):
            if not np.all(arr > 0):
                raise ValueError(
                    f"ScheduleSpec.{name} must be positive at every "
                    f"round (min over K={K} rounds was {arr.min()!r}); "
                    f"step sizes of 0 or below stall/ diverge the run")
        if self.gamma is None:
            gamma = np.float32(1.0) / alpha
        else:
            gamma = _materialize_one(self.gamma, K, "gamma")
        return RoundSchedules(alpha=alpha, beta=beta, gamma=gamma)


def _materialize_one(val, K: int, name: str) -> np.ndarray:
    if callable(val):                       # repro.optim Schedule
        import jax.numpy as jnp
        arr = np.asarray(val(jnp.arange(K, dtype=jnp.int32)), np.float32)
        return np.broadcast_to(arr, (K,)).astype(np.float32)
    if isinstance(val, tuple):
        if len(val) != K:
            raise ValueError(
                f"ScheduleSpec.{name} has {len(val)} entries but the "
                f"run is K={K} rounds; pass one value per outer round "
                f"(or a float / repro.optim schedule)")
        return np.asarray(val, np.float32)
    return np.full((K,), np.float32(val), np.float32)


@dataclasses.dataclass(frozen=True)
class RoundSchedules:
    """Materialized (K,) float32 α/β/γ rows (host-side numpy)."""
    alpha: np.ndarray
    beta: np.ndarray
    gamma: np.ndarray

    def rows(self) -> np.ndarray:
        """(K, 3) stacked columns in (alpha, beta, gamma) order — the
        layout the serve tier stores per bucket slot."""
        return np.stack([self.alpha, self.beta, self.gamma], axis=1)

    @staticmethod
    def from_rows(rows: np.ndarray) -> "RoundSchedules":
        return RoundSchedules(alpha=rows[..., 0], beta=rows[..., 1],
                              gamma=rows[..., 2])


@dataclasses.dataclass(frozen=True)
class MixingSpec:
    """(I−W)·Y execution backend — see repro.topology.ops.MixingOp."""
    backend: str = "auto"       # "auto" | "dense" | "circulant[_pallas]"
    #                             | "sparse_gather[_pallas]"
    interpret: bool | None = None   # Pallas interpret mode; None: the
    #                                 platform decides (kernels.ops)
    dtype: str = "f32"          # "f32" | "bf16" storage/gossip dtype


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Gossip wire policy — see repro.comm.parse_comm_spec."""
    spec: str = "identity"      # "identity" | "bf16" | "int8[+ef]" | ...
    persist_ef: bool = False    # sharded tier: thread EF channel state
    #                             across outer rounds (ShardedDAGMConfig
    #                             .persist_ef semantics)


@dataclasses.dataclass(frozen=True)
class ShardedSpec:
    """Mesh wiring of the `distributed` tier (ignored elsewhere)."""
    axis: Any = "data"          # agent mesh axis (or tuple of axes)
    mix_every: int = 1          # gossip only every j-th inner step
    unroll_loops: bool = False  # Python-unroll M/U (dryrun accounting)


@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """The single run description `repro.solve.solve` executes."""
    method: str = "dagm"        # METHODS
    tier: str = "reference"     # TIERS
    K: int = 100                # outer rounds
    M: int = 10                 # inner DGD steps per round
    U: int = 3                  # Neumann truncation order
    schedule: ScheduleSpec = ScheduleSpec()
    mixing: MixingSpec = MixingSpec()
    comm: CommSpec = CommSpec()
    sharded: ShardedSpec = ShardedSpec()
    dihgp: str = "dense"        # "dense" | "matrix_free" | "exact"
    curvature: float | None = None   # λmax bound for matrix_free
    momentum: float = 0.9       # ma_dbo tracker momentum
    b: int = 3                  # dgbo Hessian gossip rounds
    N: int = 5                  # dgtbo JHIP iterations
    faults: Any = None          # repro.faults.FaultSpec (or None): lower
    #                             a fault trace and run every gossip on
    #                             the per-round realized W_k

    # -- accounting conveniences (mirror the DAGMConfig API) ---------------

    def comm_channels(self, d1: int, d2: int) -> list[tuple]:
        h_sends = 0 if self.dihgp == "exact" else self.U
        return [("inner_y", (d2,), self.M),
                ("dihgp_h", (d2,), h_sends),
                ("outer_x", (d1,), 1)]

    def comm_ledger(self, d1: int, d2: int, rounds: int | None = None):
        from repro.comm import static_ledger
        K = self.K if rounds is None else rounds
        return static_ledger(
            self.comm.spec,
            [(name, shape, K * sends) for name, shape, sends
             in self.comm_channels(d1, d2)], name="dagm")


def validate_spec(spec: "SolverSpec") -> None:
    """Reject inexpressible/conflicting specs with actionable messages.

    Shared by `solve()` and the serve tier's `compile_signature` (every
    job is validated before it can mint a bucket)."""
    if spec.method not in METHODS:
        raise ValueError(
            f"unknown method {spec.method!r}; expected one of {METHODS}")
    if spec.tier not in TIERS:
        raise ValueError(
            f"unknown tier {spec.tier!r}; expected one of {TIERS}")
    for name, val in (("K", spec.K), ("M", spec.M), ("b", spec.b),
                      ("N", spec.N)):
        if int(val) <= 0:
            raise ValueError(
                f"SolverSpec.{name} must be a positive iteration count "
                f"(got {val}); 0 rounds is not a run — drop the phase "
                f"by choosing a method/dihgp that skips it instead")
    if int(spec.U) < 0:
        raise ValueError(
            f"SolverSpec.U must be a non-negative Neumann truncation "
            f"order (got {spec.U}); U=0 keeps only the D̃⁻¹ "
            f"preconditioner term")
    # materialization validates schedule lengths + positivity
    spec.schedule.materialize(spec.K)
    if spec.tier in ("sharded", "serve") and spec.method != "dagm":
        raise ValueError(
            f"tier={spec.tier!r} only executes method='dagm' (the "
            f"baselines exist for reference-tier comparison); got "
            f"method={spec.method!r} — use tier='reference'")
    if spec.schedule.gamma is not None and \
            spec.method in ("dgbo", "dgtbo", "fednest"):
        raise ValueError(
            f"method={spec.method!r} has no penalty term: the gamma "
            f"schedule multiplies DAGM's (I−Ŵ)x/α "
            f"penalty gradient, which this baseline never forms; drop "
            f"schedule.gamma or use method='dagm'/'ma_dbo'")
    if spec.schedule.gamma is not None and spec.tier == "sharded":
        raise ValueError(
            "the sharded tier folds the penalty coefficient into the "
            "Ŵx − α(·) update (α·γ "
            "= 1 by construction), so an explicit gamma schedule is "
            "inexpressible there; use tier='reference' for decoupled "
            "penalties")
    if spec.comm.persist_ef and spec.tier != "sharded":
        raise ValueError(
            f"CommSpec.persist_ef=True is a sharded-tier knob (the "
            f"reference and serve tiers already thread channel state "
            f"through the whole run); got tier={spec.tier!r}")
    if spec.comm.persist_ef and spec.comm.spec == "identity":
        raise ValueError(
            "CommSpec.persist_ef=True with spec='identity' conflicts: "
            "the identity wire has no error-feedback state to persist; "
            "pick a compressing spec (e.g. 'top_k:0.1+ef') or drop "
            "persist_ef")
    if spec.comm.spec != "identity" and spec.dihgp == "exact":
        raise ValueError(
            "dihgp='exact' solves the penalized system densely and has "
            "no gossip to compress; use 'dense' or 'matrix_free' with "
            f"comm={spec.comm.spec!r}")
    if spec.faults is not None:
        from repro.faults import FaultSpec
        if not isinstance(spec.faults, FaultSpec):
            raise ValueError(
                f"SolverSpec.faults must be a repro.faults.FaultSpec "
                f"(got {type(spec.faults).__name__}); construct one "
                f"with FaultSpec(drop_prob=..., stragglers=..., "
                f"churn=..., seed=...)")
        if spec.method != "dagm":
            raise ValueError(
                f"fault injection degrades the DAGM gossip rounds; the "
                f"baseline methods do not thread per-round edge masks "
                f"(got method={spec.method!r}) — use method='dagm' or "
                f"drop SolverSpec.faults")
        if spec.tier != "reference":
            raise ValueError(
                f"fault-masked mixing is a reference-tier feature (got "
                f"tier={spec.tier!r}): serve buckets share one compiled "
                f"program whose per-slot operands are hyper-parameters "
                f"only, and the sharded tier's lax.ppermute gossip has "
                f"no per-round mask channel yet — use tier='reference'")
    if spec.tier == "sharded" and spec.curvature is None:
        raise ValueError(
            "the sharded tier's scalar-preconditioned DIHGP needs an "
            "explicit curvature bound (SolverSpec.curvature ≥ "
            "λmax(∇²_y g_i)); there is no power-"
            "iteration fallback inside shard_map")


# ---------------------------------------------------------------------------
# Lowering from the legacy config surfaces
# ---------------------------------------------------------------------------

def as_solver_spec(cfg) -> "SolverSpec":
    """Normalize any config surface to a SolverSpec.

    Accepts a SolverSpec (returned as-is), a `DAGMConfig` or a
    `ShardedDAGMConfig` (lowered field-by-field — the deprecation
    warning fired when the caller constructed the legacy object, so
    lowering itself is silent)."""
    if isinstance(cfg, SolverSpec):
        return cfg
    from repro.core.dagm import DAGMConfig
    from repro.distributed.dagm_sharded import ShardedDAGMConfig
    if isinstance(cfg, DAGMConfig):
        return SolverSpec(
            method="dagm", tier="reference", K=cfg.K, M=cfg.M, U=cfg.U,
            schedule=ScheduleSpec(alpha=cfg.alpha, beta=cfg.beta),
            mixing=MixingSpec(backend=cfg.mixing,
                              interpret=cfg.mixing_interpret,
                              dtype=cfg.mixing_dtype),
            comm=CommSpec(spec=cfg.comm),
            dihgp=cfg.dihgp, curvature=cfg.curvature)
    if isinstance(cfg, ShardedDAGMConfig):
        comm = cfg.comm
        if comm == "identity" and cfg.comm_dtype == "bf16":
            comm = "bf16"             # legacy comm_dtype alias
        return SolverSpec(
            method="dagm", tier="sharded", K=1, M=cfg.M, U=cfg.U,
            schedule=ScheduleSpec(alpha=cfg.alpha, beta=cfg.beta),
            mixing=MixingSpec(dtype=cfg.comm_dtype),
            comm=CommSpec(spec=comm, persist_ef=cfg.persist_ef),
            sharded=ShardedSpec(axis=cfg.axis, mix_every=cfg.mix_every,
                                unroll_loops=cfg.unroll_loops),
            dihgp="matrix_free", curvature=cfg.curvature)
    raise TypeError(
        f"expected SolverSpec, DAGMConfig or ShardedDAGMConfig, got "
        f"{type(cfg).__name__}")


def mixing_kwargs(cfg) -> dict:
    """`make_mixing_op` kwargs from any config surface."""
    spec = as_solver_spec(cfg)
    return dict(backend=spec.mixing.backend,
                interpret=spec.mixing.interpret,
                dtype=spec.mixing.dtype, comm=spec.comm.spec)


def dagm_spec(alpha=1e-2, beta=1e-2, gamma=None, K: int = 100,
              M: int = 10, U: int = 3, dihgp: str = "dense",
              curvature: float | None = None, mixing: str = "auto",
              mixing_interpret: bool | None = None,
              mixing_dtype: str = "f32",
              comm: str = "identity", tier: str = "reference",
              faults=None) -> SolverSpec:
    """Convenience constructor mirroring the old DAGMConfig kwargs —
    the one-line migration target for `DAGMConfig(...)` call sites."""
    return SolverSpec(
        method="dagm", tier=tier, K=K, M=M, U=U,
        schedule=ScheduleSpec(alpha=alpha, beta=beta, gamma=gamma),
        mixing=MixingSpec(backend=mixing, interpret=mixing_interpret,
                          dtype=mixing_dtype),
        comm=CommSpec(spec=comm), dihgp=dihgp, curvature=curvature,
        faults=faults)


def sharded_spec(alpha=1e-2, beta=1e-2, M: int = 5, U: int = 3,
                 curvature: float = 4.0, axis="data",
                 comm: str = "identity", comm_dtype: str = "f32",
                 persist_ef: bool = False, mix_every: int = 1,
                 unroll_loops: bool = False, K: int = 1) -> SolverSpec:
    """Convenience constructor mirroring the old ShardedDAGMConfig
    kwargs (K is the round budget when driven through `solve`; the raw
    `make_sharded_dagm` step is still one round per call)."""
    if comm == "identity" and comm_dtype == "bf16":
        comm = "bf16"
    return SolverSpec(
        method="dagm", tier="sharded", K=K, M=M, U=U,
        schedule=ScheduleSpec(alpha=alpha, beta=beta),
        mixing=MixingSpec(dtype=comm_dtype),
        comm=CommSpec(spec=comm, persist_ef=persist_ef),
        sharded=ShardedSpec(axis=axis, mix_every=mix_every,
                            unroll_loops=unroll_loops),
        dihgp="matrix_free", curvature=curvature)


def _register_static(cls):
    import jax
    jax.tree_util.register_static(cls)
    return cls


for _cls in (ScheduleSpec, MixingSpec, CommSpec, ShardedSpec,
             SolverSpec):
    _register_static(_cls)

# re-export for shim modules
silently = _compat.silently
warn_once = _compat.warn_once
