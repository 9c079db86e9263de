"""Job descriptions for the multi-tenant bilevel solver engine.

A `JobSpec` is one independent DAGM instance — a problem-zoo family
(`core.problems.PROBLEM_FAMILIES`) instantiated with its own data/seed,
plus a `repro.solve.SolverSpec` for the run (legacy `DAGMConfig`s are
lowered transparently).  The engine never executes a JobSpec directly:
specs are grouped by `compile_signature` (everything that shapes the
trace), padded into fixed-width buckets, and run as one vmapped
`dagm_run_chunk` per bucket (`repro.serve.engine`).

The signature split:

* **static** (bucket key, baked into the trace): problem family + data
  leaf shapes, (n, d1, d2), topology, mixing backend/dtype, comm
  policy, dihgp backend, K / M / U loop bounds, and whether a curvature
  bound is supplied.  Two jobs with equal signatures share one compiled
  program.
* **per-job** (vary freely inside a bucket): the data *values*, the
  init seed, the curvature bound, and the full α/β/γ **schedules** —
  constant or per-round (decaying step sizes, growing penalties).
  Schedule values enter the chunk program as traced operands in the
  engine's default ``hp_mode="traced"``, so any sweep of them shares
  ONE compile and — since `repro.solve` feeds the solo program the
  same operands — batched trajectories are bit-exact with solo runs.

`JobResult` reports the per-job outcome *including the exact wire
bytes* the job's gossip cost, attributed from the bucket ledger's
per-slot send counters (`repro.comm.CommLedger.per_job_bytes`).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from repro.core.problems import BilevelProblem, problem_family
from repro.solve.spec import SolverSpec, as_solver_spec
from repro.topology import Network, make_network

Signature = tuple


@dataclasses.dataclass(frozen=True)
class JobSpec:
    """One bilevel solve request.

    family:   `core.problems.PROBLEM_FAMILIES` key, or a callable
              constructor (called with the `problem` kwargs) for
              problems outside the zoo — `repro.solve`'s serve tier
              wraps ad-hoc problem instances this way.
    problem:  constructor kwargs for the family (n, d, m_per, seed, ...).
              Everything that changes a data *shape* changes the
              compile signature; the data values ride per-job.
    config:   `SolverSpec` (or legacy `DAGMConfig`) for the run.  The
              schedules and curvature are per-job; the remaining
              fields are bucket-static.
    graph:    topology kind for `make_network` (+ graph_kwargs), or a
              prebuilt `Network`; shared across a bucket — a job
              sweeping topologies lands in one bucket per topology.
    seed:     init seed (y0 draw + comm channel keys), per-job.
    tol:      optional convergence threshold on the Eq. (17b) estimate
              ‖∇̂F‖²; a job whose last chunked round reaches it retires
              early and its slot is backfilled from the queue.
    job_id:   caller's handle (auto-assigned when None).
    tenant:   billing identity for `repro.serve.admission` quota
              ledgers; never part of the compile signature.
    klass:    priority-class name (`admission.classes`) consumed by the
              async admission loop; the wave-mode engine ignores it.
              Never part of the compile signature.
    """
    family: Any
    problem: dict
    config: Any
    graph: Any = "ring"
    graph_kwargs: dict = dataclasses.field(default_factory=dict)
    seed: int = 0
    tol: float | None = None
    job_id: str | None = None
    tenant: str = "default"
    klass: str = "standard"


@dataclasses.dataclass
class JobResult:
    """Outcome of one job: final iterates, convergence, cost."""
    job_id: str
    x: Any                    # final stacked outer iterates (n, d1)
    y: Any                    # final stacked inner iterates (n, d2)
    rounds: int               # outer rounds actually run (≤ config.K)
    converged: bool           # tol reached before the K-round budget
    final_gap: float          # last ‖∇̂F‖² (Eq. 17b estimate)
    wire_bytes: int           # exact gossip bytes this job moved
    wire_floats: int          # uncompressed f32 words (comparison base)
    sends: dict               # per-channel send counts
    wall_clock_s: float       # engine wall time attributed to this job
    signature: Signature      # bucket the job ran in
    metrics: dict | None = None   # per-round trajectory (rounds, ...)
    #                               when the engine records metrics
    quarantined: bool = False     # chunk poisoned this job (non-finite
    #                               iterates); x/y hold the last finite
    #                               pre-chunk state, rounds the rounds
    #                               completed before the poisoned chunk
    flight: Any = None            # (rows, len(obs.FIELDS)) flight-
    #                               recorder rows when the engine was
    #                               built with flight_recorder=...


def solver_spec(spec: JobSpec) -> SolverSpec:
    """The job's normalized SolverSpec (tier pinned to "reference":
    the chunk machinery is tier-agnostic and the job already *is* the
    serve tier)."""
    s = as_solver_spec(spec.config)
    return dataclasses.replace(s, tier="reference") \
        if s.tier != "reference" else s


def build_problem(spec: JobSpec) -> BilevelProblem:
    """Instantiate the spec's problem-zoo family (or ad-hoc callable)."""
    maker = spec.family if callable(spec.family) \
        else problem_family(spec.family)
    return maker(**spec.problem)


def build_network(spec: JobSpec) -> Network:
    """Topology shared by the spec's bucket (n defaults to the
    problem's agent count); prebuilt Networks pass through."""
    if isinstance(spec.graph, Network):
        return spec.graph
    kw = dict(spec.graph_kwargs)
    n = int(kw.pop("n", _graph_n(spec)))
    return make_network(spec.graph, n, **kw)


def _graph_n(spec: JobSpec) -> int:
    n = spec.problem.get("n")
    if n is None:
        raise ValueError(
            f"JobSpec.problem must carry the agent count 'n' "
            f"(got keys {sorted(spec.problem)})")
    return int(n)


def schedule_rows(cfg) -> np.ndarray:
    """(K, 3) float32 materialized (α, β, γ) schedule columns in the
    order the engine's chunk runner consumes them.  Single source of
    truth for job rows and the padding slots' template rows alike."""
    spec = as_solver_spec(cfg)
    return spec.schedule.materialize(spec.K).rows()


def job_hp(spec: JobSpec) -> np.ndarray:
    """The per-job hyper-parameter schedule rows (see `schedule_rows`)."""
    return schedule_rows(spec.config)


def compile_signature(spec: JobSpec, prob: BilevelProblem) -> Signature:
    """Everything that shapes the compiled bucket program.

    Jobs with equal signatures run under ONE trace: same problem family
    at the same data shapes, same topology, same mixing/comm execution
    path, same loop bounds.  Per-job data values, seeds, curvature
    bounds and schedule *values* deliberately stay out (they are the
    sweep axes)."""
    return _signature(spec, prob, k_entry=None)


def pack_signature(spec: JobSpec, prob: BilevelProblem) -> Signature:
    """`compile_signature` with the round budget K replaced by a
    sentinel: the near-miss bucket key for `repro.serve.admission`'s
    K-packing.  Jobs that differ ONLY in K share a pack signature —
    the chunk program scans T rounds regardless of K, so packing them
    into one bucket (budget K padded to the pack max, each slot
    retiring at its own budget) reuses a single trace across
    heterogeneous round budgets.  Everything else that shapes the
    trace still keys the bucket."""
    return _signature(spec, prob, k_entry="K:packed")


def _signature(spec: JobSpec, prob: BilevelProblem,
               k_entry) -> Signature:
    from repro.core.dagm import dagm_validate
    s = solver_spec(spec)
    dagm_validate(s)
    if s.faults is not None:
        raise ValueError(
            "serve jobs do not thread fault masks yet: a bucket's "
            "compiled program carries per-slot hyper-parameter operands "
            "only, so a per-job FaultSpec would be silently ignored — "
            "run faulted solves through repro.solve with "
            "tier='reference', or drop SolverSpec.faults")
    import jax
    leaf_shapes = tuple(sorted(
        (jax.tree_util.keystr(path), tuple(leaf.shape))
        for path, leaf in jax.tree_util.tree_leaves_with_path(prob.data)))
    if isinstance(spec.graph, Network):
        # content-addressed: two prebuilt Networks with equal (name, n)
        # but different W must NOT share a bucket — the bucket runs on
        # the first job's graph, which would silently solve the others
        # on the wrong topology
        graph = spec.graph.content_key()
    else:
        graph = (spec.graph,) + tuple(sorted(spec.graph_kwargs.items()))
    return (spec.family, prob.n, prob.d1, prob.d2, leaf_shapes, graph,
            s.mixing.backend, s.mixing.dtype, s.mixing.interpret,
            s.comm.spec, s.dihgp, s.K if k_entry is None else k_entry,
            s.M, s.U, s.curvature is not None)
