"""ServeEngine — continuous-batched execution of bilevel job fleets.

The scheduling loop per bucket signature:

    admit jobs into slots ─► one vmapped+jitted T-round chunk
         ▲                          │ (compile cache: one trace per
         │                          │  bucket program, ever)
         └── backfill ◄── retire converged / budget-exhausted slots

Every chunk call advances *all* slots T outer rounds through one fused
`lax.scan`; converged jobs retire mid-flight at chunk boundaries and
queued jobs backfill their slots, so the accelerator never idles on a
straggler-free queue.  Per-job results carry the exact wire bytes from
the bucket ledger's per-slot send counters, the rounds actually run,
and the wall-clock share.

Hyper-parameter modes (`hp_mode`)
---------------------------------
Hyper-parameters are full (K,) α/β/γ *schedule rows* per slot (see
`repro.solve.ScheduleSpec`); each chunk scans its per-slot (T,) slice.

* ``"traced"`` (default): the slices enter the chunk program as
  runtime arguments.  ONE compile serves every sweep of the same
  signature — backfill, new waves, new hyper-parameter grids, decaying
  schedules, no retrace — and because `repro.solve` feeds the solo
  program the same traced operands, batched trajectories are
  **bit-exact with solo runs** (measured in `benchmarks/bench_serve`).
* ``"static"``: the slices are baked into the trace as constants.
  Identical trajectories (constants and operands multiply identically);
  the compile cache keys on the hp snapshot, so changing a slot's
  schedule (e.g. backfilling a different sweep point) re-traces.
  Kept for cache-behavior studies and as the historical mode.

Both modes share the width-invariance guarantee (widths ≥ 2) because
the bucket program treats every slot identically; padding slots are
frozen by the active mask (see `batching`).

Crash safety (`repro.faults` arc)
---------------------------------
An engine built with ``checkpoint_dir=...`` persists every chunk
boundary through `repro.checkpoint`: the device state (bucket carry —
iterates, channel error-feedback replicas, send counters — plus the
per-slot data stack) goes into an atomic ``step_<chunks>.npz``, and the
host state (run order, finished results, remaining buckets, slot
bookkeeping, stats) into a ``state_<chunks>.pkl`` sidecar.  A NEW
engine pointed at the same directory resumes the interrupted `run()`
**bit-exactly**: chunking is bitwise-exact and the restored carry is
the exact chunk-boundary state, so the resumed trajectory equals the
uninterrupted one (regression-tested; `EngineStats.restarts` counts
resumptions).  Checkpoints are swept on successful completion, pruned
to ``keep_last`` while running, and stale ``*.tmp.npz`` debris from a
crash mid-save is removed on the next touch.

Poisoned chunks degrade instead of killing the run: device/runtime
errors are retried with exponential backoff (`EngineStats.retries`),
and a chunk that turns a slot's iterates non-finite rolls that slot
back to its pre-chunk state, retires it as ``quarantined``
(`JobResult.quarantined`, `EngineStats.quarantined`) and backfills the
slot — the other tenants' trajectories are untouched (the rollback is
per-slot, and slot trajectories are independent by the width-invariance
guarantee).
"""
from __future__ import annotations

import dataclasses
import os
import pickle
import re
import time
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.dagm import RoundHP, dagm_run_chunk, dagm_validate
from repro.topology import make_mixing_op

from .batching import (BucketState, bucketize, chunk_rounds_for,
                       pad_width)
from .jobs import (JobResult, JobSpec, Signature, build_problem,
                   compile_signature, solver_spec)

HP_MODES = ("traced", "static")


class SimulatedCrash(RuntimeError):
    """Raised by the `crash_after_chunks` test hook right after a
    checkpoint lands — the restart smoke's stand-in for kill -9."""


def _no_metrics(prob, W, x, y):
    # dagm_outer_step_c appends hypergrad_est_norm_sq — the engine's
    # convergence signal — on top of whatever the metrics_fn returns;
    # the default serve run records nothing else per round.
    return {}


@dataclasses.dataclass
class EngineStats:
    """Aggregate counters across the engine's lifetime."""
    traces: int = 0            # chunk programs actually traced by jax
    cache_misses: int = 0      # chunk-fn builds (≡ distinct cache keys)
    cache_hits: int = 0        # chunk-fn lookups served from cache
    chunks: int = 0            # vmapped chunk invocations
    buckets: int = 0           # bucket flights completed
    jobs_completed: int = 0
    wall_s: float = 0.0        # engine wall time inside run()
    retries: int = 0           # chunk invocations retried after errors
    quarantined: int = 0       # job slots retired by the poison detector
    restarts: int = 0          # run() resumptions from a checkpoint
    checkpoints: int = 0       # chunk-boundary checkpoints written


class ServeEngine:
    """Multi-tenant batched DAGM solver (see module docstring).

    chunk_rounds: requested retirement granularity T (rounded down to
                  a divisor of each bucket's K, floor 2).
    max_width:    bucket width cap (pad_width pads to powers of two).
    hp_mode:      "traced" | "static" — see module docstring.
    metrics_fn:   optional per-round metrics callback threaded to
                  `dagm_outer_step_c` (default records nothing beyond
                  the convergence signal).
    record_metrics: keep each job's per-round metric trajectory and
                  attach it to `JobResult.metrics` (the serve tier of
                  `repro.solve.solve` uses this to return the same
                  trajectory a reference-tier run would).
    checkpoint_dir: directory for chunk-boundary crash checkpoints
                  (None disables; see module docstring).  A resuming
                  engine must be constructed with the same
                  chunk_rounds / hp_mode / metrics_fn as the one that
                  wrote the checkpoint — the first two are verified.
    checkpoint_every: write a checkpoint every N-th chunk.
    keep_last:    checkpoints retained while running (older pruned).
    max_chunk_retries: device/runtime chunk errors retried (with
                  exponential backoff) before the run gives up.
    retry_backoff_s: base backoff; attempt i sleeps base·2^i.
    crash_after_chunks: test hook — raise `SimulatedCrash` once
                  `stats.chunks` reaches this count, right after the
                  checkpoint lands (the restart smoke's kill switch).
    """

    def __init__(self, chunk_rounds: int = 10, max_width: int = 64,
                 hp_mode: str = "traced", metrics_fn=None,
                 cache_capacity: int = 64,
                 record_metrics: bool = False,
                 checkpoint_dir: str | None = None,
                 checkpoint_every: int = 1, keep_last: int = 3,
                 max_chunk_retries: int = 2,
                 retry_backoff_s: float = 0.05,
                 crash_after_chunks: int | None = None,
                 flight_recorder=None):
        if hp_mode not in HP_MODES:
            raise ValueError(f"unknown hp_mode {hp_mode!r}; expected "
                             f"one of {HP_MODES}")
        if max_width < 2:
            raise ValueError(
                f"max_width must be >= 2 (got {max_width}): width-1 "
                f"buckets compile to an XLA-specialized program that "
                f"breaks the width-invariance guarantee")
        if flight_recorder is not None \
                and not isinstance(flight_recorder, obs.RecorderSpec):
            raise TypeError(
                f"flight_recorder must be a repro.obs.RecorderSpec or "
                f"None, got {type(flight_recorder).__name__}")
        self.chunk_rounds = int(chunk_rounds)
        self.max_width = int(max_width)
        self.hp_mode = hp_mode
        self.metrics_fn = metrics_fn if metrics_fn is not None \
            else _no_metrics
        self.record_metrics = bool(record_metrics)
        # obs.RecorderSpec | None: every bucket carry grows a per-slot
        # in-jit FlightBuffer and each JobResult carries its rows
        self.flight_recorder = flight_recorder
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = max(int(checkpoint_every), 1)
        self.keep_last = int(keep_last)
        self.max_chunk_retries = int(max_chunk_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.crash_after_chunks = crash_after_chunks
        self.stats = EngineStats()
        self.ledgers: dict[Signature, object] = {}
        self._queue: list[JobSpec] = []
        self._auto_id = 0
        # compile cache: key -> jitted chunk fn; lives for the engine's
        # lifetime, so a later wave of the same bucket program re-traces
        # nothing (EngineStats.traces is the ground truth — it counts
        # actual jax traces via a side effect in the traced body).
        # LRU-bounded: static hp_mode mints a key per hp snapshot, so a
        # long-running sweep service would otherwise grow one compiled
        # program (plus its closed-over MixingOp) per snapshot forever.
        self._cache: dict[tuple, object] = {}
        self._cache_capacity = int(cache_capacity)
        # shared repro.obs trace counter: ticks from inside the traced
        # chunk body, so it counts actual jax traces (cache hits are
        # silent); `stats.traces` mirrors it for the historical surface
        self._trace_counter = obs.TraceCounter(name="serve_chunk")

    # -- queue -------------------------------------------------------------

    def submit(self, specs) -> list[str]:
        """Enqueue job specs (auto-assigning missing job_ids); returns
        the job ids in submission order.  Caller-supplied ids must be
        unique within the queued batch — results are keyed by id, so a
        duplicate would silently shadow the first job's outcome.

        Specs are validated here, at the API edge: config conflicts
        (`dagm_validate`), K/chunk_rounds incompatibilities and
        unpicklable checkpointed jobs all fail with an actionable
        ValueError instead of a shape error deep inside the vmapped
        chunk (or a torn checkpoint) mid-run."""
        ids = []
        queued = {spec.job_id for spec in self._queue}
        for spec in ([specs] if isinstance(specs, JobSpec) else
                     list(specs)):
            self._validate_submit(spec)
            if spec.job_id is None:
                spec = dataclasses.replace(
                    spec, job_id=f"job{self._auto_id}")
                self._auto_id += 1
            if spec.job_id in queued:
                raise ValueError(
                    f"duplicate job_id {spec.job_id!r} in queue")
            queued.add(spec.job_id)
            self._queue.append(spec)
            obs.instant("submit", cat="serve.lifecycle",
                        track="engine", job_id=spec.job_id)
            ids.append(spec.job_id)
        self._set_queue_gauge()
        return ids

    def _set_queue_gauge(self) -> None:
        obs.registry().gauge(
            "serve_queue_depth",
            "jobs waiting in the ServeEngine queue").set(
                float(len(self._queue)))

    def _validate_submit(self, spec: JobSpec) -> None:
        sspec = solver_spec(spec)     # TypeError for non-config objects
        dagm_validate(sspec)          # schedule lengths, tier conflicts
        if sspec.faults is not None:
            raise ValueError(
                "serve jobs do not thread fault masks yet: a bucket's "
                "compiled program carries per-slot hyper-parameter "
                "operands only, so a per-job FaultSpec would be "
                "silently ignored — run faulted solves through "
                "repro.solve with tier='reference', or drop "
                "SolverSpec.faults")
        T = chunk_rounds_for(sspec.K, self.chunk_rounds)
        if spec.tol is not None and T >= sspec.K \
                and sspec.K > self.chunk_rounds:
            raise ValueError(
                f"JobSpec.tol needs a chunk boundary to retire at, but "
                f"K={sspec.K} and chunk_rounds={self.chunk_rounds} "
                f"share no divisor ≥ 2 — the whole run would be one "
                f"chunk and the tolerance could only fire at the full "
                f"budget; pick K with a small factor (e.g. "
                f"{sspec.K + 1}) or raise chunk_rounds")
        if self.checkpoint_dir is not None and callable(spec.family):
            raise ValueError(
                "a checkpointing engine (checkpoint_dir=...) must be "
                "able to pickle every queued JobSpec, and callable "
                "problem families (repro.solve's inline serve-tier "
                "wrapper) do not survive a restart — use a problem-zoo "
                "family name, or drop checkpoint_dir")

    # -- chunk program cache ----------------------------------------------

    def _chunk_fn(self, bucket: BucketState, T: int):
        # metrics_fn is part of the compiled program (the chunk closes
        # over it), so swapping it must miss the cache, not serve a
        # program that still records the old metrics
        # the flight recorder keys too: it changes the chunk program
        # (extra carry leaf + the per-round recorder writes)
        key = (bucket.signature, bucket.width, T, self.hp_mode,
               self.metrics_fn, self.flight_recorder)
        if self.hp_mode == "static":
            key += (bucket.hp_key(T),)
        fn = self._cache.get(key)
        if fn is not None:
            self.stats.cache_hits += 1
            self._cache[key] = self._cache.pop(key)   # LRU touch
            return fn
        self.stats.cache_misses += 1
        with obs.span("build_chunk_fn", cat="serve.compile",
                      track="engine", width=bucket.width, rounds=T,
                      hp_mode=self.hp_mode):
            fn = self._build_chunk_fn(bucket, T)
        while len(self._cache) >= self._cache_capacity:
            self._cache.pop(next(iter(self._cache)))  # evict oldest
        self._cache[key] = fn
        return fn

    def _build_chunk_fn(self, bucket: BucketState, T: int):
        # close over a data-free template: the job data always arrives
        # through the `data` argument, so the closure must not pin the
        # creating wave's data arrays for the cache entry's lifetime
        template = bucket.template.with_data(None)
        op, spec = bucket.op, bucket.spec
        has_curv = bucket.has_curvature
        metrics_fn = self.metrics_fn
        recorder = self.flight_recorder
        tc = self._trace_counter
        stats = self.stats

        def one_job(data_j, hp_j, carry, active):
            prob_j = template.with_data(data_j)
            curv = hp_j["curvature"] if has_curv else None
            hp = RoundHP(alpha=hp_j["alpha"], beta=hp_j["beta"],
                         gamma=hp_j["gamma"])
            c2, m = dagm_run_chunk(prob_j, op, spec, carry, T,
                                   metrics_fn, hp=hp, curvature=curv,
                                   recorder=recorder)
            # inert padding/retired slots: freeze the whole carry
            # (state, EF replicas, send counters — and the flight
            # buffer, an ordinary pytree leaf) behind the mask
            c2 = jax.tree.map(lambda new, old: jnp.where(active, new, old),
                              c2, carry)
            return c2, m

        if self.hp_mode == "static":
            # hp slices enter as concrete closure constants: jit bakes
            # them into the program (same trajectories as traced mode —
            # multiplications by constants and operands are identical)
            hp_const = {k: jnp.asarray(v)
                        for k, v in bucket.hp_chunk(T).items()}

            def chunk(data, carry, active):
                stats.traces = tc.bump()
                return jax.vmap(one_job)(data, hp_const, carry, active)
        else:
            def chunk(data, hp, carry, active):
                stats.traces = tc.bump()
                return jax.vmap(one_job)(data, hp, carry, active)

        return jax.jit(chunk)

    # -- scheduling loop ---------------------------------------------------

    def run(self) -> list[JobResult]:
        """Drain the queue; returns JobResults in submission order.

        With `checkpoint_dir` set and a checkpoint present, resumes the
        interrupted run first (bit-exactly) — any newly queued jobs run
        after the restored ones."""
        t0 = time.perf_counter()
        with obs.span("engine_run", cat="serve", track="engine") as sp:
            ctx = self._restore_run_state()
            if ctx is None:
                queue, self._queue = self._queue, []
                self._set_queue_gauge()
                ctx = {"order": [spec.job_id for spec in queue],
                       "buckets": list(bucketize(queue).values()),
                       "bucket_index": 0, "results": {}, "resume": None}
            while ctx["bucket_index"] < len(ctx["buckets"]):
                items = ctx["buckets"][ctx["bucket_index"]]
                self._run_bucket(items, ctx)
                ctx["bucket_index"] += 1
                ctx["resume"] = None
            self._clear_checkpoints()
            sp.annotate(jobs=len(ctx["order"]),
                        chunks=self.stats.chunks,
                        traces=self._trace_counter.count)
        self.stats.wall_s += time.perf_counter() - t0
        return [ctx["results"][jid] for jid in ctx["order"]]

    def _run_bucket(self, items: list, ctx: dict) -> None:
        from .jobs import build_network
        results = ctx["results"]
        spec0, prob0 = items[0]
        sig = compile_signature(spec0, prob0)
        sspec = solver_spec(spec0)
        net = build_network(spec0)
        op = make_mixing_op(net, backend=sspec.mixing.backend,
                            interpret=sspec.mixing.interpret,
                            dtype=sspec.mixing.dtype,
                            comm=sspec.comm.spec)
        width = pad_width(len(items), self.max_width)
        T = chunk_rounds_for(sspec.K, self.chunk_rounds)
        bucket = BucketState(sig, width, prob0, net, op, sspec,
                             recorder=self.flight_recorder)
        tr = obs.tracer()
        resume = ctx["resume"]
        if resume is None:
            pending = deque(items)
            for slot in range(width):
                if pending:
                    spec_a, prob_a = pending.popleft()
                    bucket.admit(slot, spec_a, prob_a)
                    tr.instant("admit", cat="serve.lifecycle",
                               track="engine", job_id=spec_a.job_id,
                               slot=int(slot))
        else:
            # chunk-boundary restore: host bookkeeping from the pickle
            # sidecar, device state through repro.checkpoint — together
            # the exact state the interrupted run held at the boundary
            from repro import checkpoint as ckpt
            bucket.restore_host(resume["bucket_host"])
            template = {"carry": bucket.carry, "data": bucket.data}
            dev = ckpt.restore_into(
                ckpt.load_arrays(self.checkpoint_dir, resume["step"]),
                template)
            bucket.carry, bucket.data = dev["carry"], dev["data"]
            ids = set(resume["pending_ids"])
            pending = deque(it for it in items if it[0].job_id in ids)

        def backfill(bkt, slot):
            if not pending:
                return False
            spec_b, prob_b = pending.popleft()
            bkt.admit(slot, spec_b, prob_b)
            tr.instant("admit", cat="serve.lifecycle", track="engine",
                       job_id=spec_b.job_id, slot=int(slot),
                       backfill=True)
            return True

        inflight = obs.registry().gauge(
            "serve_inflight_jobs",
            "active slots in the currently running bucket")
        while bucket.any_active():
            inflight.set(float(bucket.active.sum()))
            self._advance_bucket(bucket, T, results, backfill)
            self._maybe_checkpoint(bucket, ctx, pending)

        inflight.set(0.0)
        self._finalize_ledger(bucket)
        self.stats.buckets += 1

    def _advance_bucket(self, bucket: BucketState, T: int,
                        results: dict, backfill) -> None:
        """One T-round chunk + the boundary processing that follows:
        poison quarantine, rounds/wall/metrics accounting, retirement
        of converged/budget-exhausted slots, and backfill.

        This is the shared scheduling primitive: `run()`'s wave loop
        and `repro.serve.admission`'s always-on loop both advance
        buckets through it.  `backfill(bucket, slot) -> bool` fills a
        freed slot from whatever queue the caller owns (the wave
        pending deque, the admission queue); each retirement also hits
        the `_on_retired` hook (a no-op here — the admission loop uses
        it for completion events and tenant quota charging)."""
        tr = obs.tracer()
        fn = self._chunk_fn(bucket, T)
        prev_carry = bucket.carry
        t0 = time.perf_counter()
        with tr.span("chunk", cat="serve.chunk", track="engine",
                     rounds=T, width=bucket.width,
                     active=int(bucket.active.sum())) as chunk_sp:
            if self.hp_mode == "static":
                carry, metrics = self._invoke_chunk(
                    fn, (bucket.data, bucket.carry,
                         bucket.active_mask()))
            else:
                hp = {k: jnp.asarray(v)
                      for k, v in bucket.hp_chunk(T).items()}
                carry, metrics = self._invoke_chunk(
                    fn, (bucket.data, hp, bucket.carry,
                         bucket.active_mask()))
            chunk_sp.annotate(traces=self._trace_counter.count)
        dt = time.perf_counter() - t0
        self.stats.chunks += 1
        bucket.carry = carry

        with tr.span("boundary", cat="serve.chunk", track="engine"):
            ran = bucket.active.copy()   # slots that ran this chunk
            bad = self._poisoned_slots(bucket)
            if bad.any():
                self._quarantine(bucket, prev_carry, bad, results,
                                 backfill)
            # freshly backfilled slots (quarantine replacements) start
            # at the NEXT chunk; only surviving runners earn this one
            active = np.nonzero(ran & ~bad)[0]
            bucket.rounds[active] += T
            bucket.wall[active] += dt / max(len(active), 1)
            if self.record_metrics:
                host = jax.tree.map(np.asarray, metrics)
                for slot in active:
                    bucket.metric_log[slot].append(
                        {k: v[slot] for k, v in host.items()})
            gaps = np.asarray(metrics["hypergrad_est_norm_sq"])[:, -1]
            for slot in active:
                spec = bucket.slots[slot]
                converged = spec.tol is not None \
                    and float(gaps[slot]) <= spec.tol
                if converged \
                        or bucket.rounds[slot] >= bucket.budget[slot]:
                    rec = bucket.retire(slot, float(gaps[slot]),
                                        converged)
                    tr.instant("retire", cat="serve.lifecycle",
                               track="engine",
                               job_id=rec.spec.job_id, slot=int(slot),
                               rounds=rec.rounds,
                               converged=rec.converged)
                    result = self._make_result(bucket, rec)
                    results[rec.spec.job_id] = result
                    self.stats.jobs_completed += 1
                    self._on_retired(rec, result)
                    backfill(bucket, slot)

    def _on_retired(self, rec, result: JobResult) -> None:
        """Retirement hook (wave mode: nothing beyond the results dict
        the caller already owns).  `repro.serve.admission` overrides it
        to resolve completion events and charge tenant quotas."""

    # -- fault tolerance ---------------------------------------------------

    def _invoke_chunk(self, fn, args):
        """Run one compiled chunk, retrying device/runtime errors with
        exponential backoff (transient-failure classes only — a
        ValueError/TypeError is a bug, not weather, and raises
        immediately)."""
        attempt = 0
        while True:
            try:
                out = fn(*args)
                with obs.span("chunk_wait", cat="serve.chunk",
                              track="engine"):
                    jax.block_until_ready(out)
                return out
            except (RuntimeError, OSError) as e:
                if attempt >= self.max_chunk_retries:
                    raise
                self.stats.retries += 1
                obs.instant("retry", cat="serve.lifecycle",
                            track="engine", attempt=attempt,
                            error=type(e).__name__)
                time.sleep(self.retry_backoff_s * (2.0 ** attempt))
                attempt += 1

    def _poisoned_slots(self, bucket: BucketState) -> np.ndarray:
        """(width,) bool: active slots whose post-chunk iterates went
        non-finite (divergent hyper-parameters, poisoned data)."""
        (x, y) = bucket.carry[0]
        finite = np.asarray(
            jnp.isfinite(x).all(axis=tuple(range(1, x.ndim)))
            & jnp.isfinite(y).all(axis=tuple(range(1, y.ndim))))
        return bucket.active & ~finite

    def _quarantine(self, bucket: BucketState, prev_carry, bad,
                    results: dict, backfill) -> None:
        """Roll the poisoned slots back to their pre-chunk state (the
        other tenants keep the chunk's results), retire them as
        quarantined and backfill.  Rounds/sends roll back with the
        carry — the poisoned chunk never happened for these slots."""
        mask = jnp.asarray(bad)
        bucket.carry = jax.tree.map(
            lambda new, old: jnp.where(
                mask.reshape((-1,) + (1,) * (new.ndim - 1)), old, new),
            bucket.carry, prev_carry)
        for slot in np.nonzero(bad)[0]:
            rec = bucket.retire(slot, float("nan"), False,
                                quarantined=True)
            obs.instant("quarantine", cat="serve.lifecycle",
                        track="engine", job_id=rec.spec.job_id,
                        slot=int(slot), rounds=rec.rounds)
            result = self._make_result(bucket, rec)
            results[rec.spec.job_id] = result
            self.stats.quarantined += 1
            self._on_retired(rec, result)
            backfill(bucket, slot)

    # -- crash checkpoints (repro.checkpoint) ------------------------------

    def _state_path(self, step: int) -> str:
        return os.path.join(self.checkpoint_dir,
                            f"state_{step:08d}.pkl")

    def _maybe_checkpoint(self, bucket: BucketState, ctx: dict,
                          pending: deque) -> None:
        if self.checkpoint_dir is None:
            return
        if self.stats.chunks % self.checkpoint_every == 0:
            self._save_run_state(bucket, ctx, pending)
        if self.crash_after_chunks is not None \
                and self.stats.chunks >= self.crash_after_chunks:
            raise SimulatedCrash(
                f"crash_after_chunks hook fired at chunk "
                f"{self.stats.chunks}")

    def _save_run_state(self, bucket: BucketState, ctx: dict,
                        pending: deque) -> None:
        with obs.span("checkpoint", cat="serve.checkpoint",
                      track="engine", step=self.stats.chunks):
            self._save_run_state_inner(bucket, ctx, pending)

    def _save_run_state_inner(self, bucket: BucketState, ctx: dict,
                              pending: deque) -> None:
        from repro import checkpoint as ckpt
        step = self.stats.chunks
        ckpt.save_checkpoint(self.checkpoint_dir, step,
                             {"carry": bucket.carry,
                              "data": bucket.data},
                             keep_last=self.keep_last)
        host = {
            "format": 1,
            "engine": {"chunk_rounds": self.chunk_rounds,
                       "hp_mode": self.hp_mode},
            "order": ctx["order"],
            "results": ctx["results"],
            "bucket_index": ctx["bucket_index"],
            "bucket_specs": [[spec for spec, _ in items]
                             for items in ctx["buckets"]],
            "pending_ids": [spec.job_id for spec, _ in pending],
            "bucket_host": bucket.snapshot_host(),
            "stats": {"chunks": self.stats.chunks,
                      "jobs_completed": self.stats.jobs_completed,
                      "retries": self.stats.retries,
                      "quarantined": self.stats.quarantined,
                      "restarts": self.stats.restarts,
                      "checkpoints": self.stats.checkpoints + 1},
            "auto_id": self._auto_id,
        }
        tmp = self._state_path(step) + ".tmp"
        with open(tmp, "wb") as f:
            pickle.dump(host, f)
        os.replace(tmp, self._state_path(step))
        self.stats.checkpoints += 1
        # prune sidecars alongside the npz files keep_last keeps
        kept = {f"state_{s:08d}.pkl" for s in
                ckpt.checkpoint_steps(self.checkpoint_dir)}
        for f in os.listdir(self.checkpoint_dir):
            if re.fullmatch(r"state_\d+\.pkl", f) and f not in kept:
                os.remove(os.path.join(self.checkpoint_dir, f))

    def _restore_run_state(self) -> dict | None:
        if self.checkpoint_dir is None:
            return None
        from repro import checkpoint as ckpt
        ckpt.sweep_stale(self.checkpoint_dir)
        host, step = None, None
        for s in reversed(ckpt.checkpoint_steps(self.checkpoint_dir)):
            # a crash between the npz and its sidecar leaves a torn
            # step — fall back to the newest complete pair
            if os.path.exists(self._state_path(s)):
                with open(self._state_path(s), "rb") as f:
                    host = pickle.load(f)
                step = s
                break
        if host is None:
            return None
        eng = host["engine"]
        if eng["chunk_rounds"] != self.chunk_rounds \
                or eng["hp_mode"] != self.hp_mode:
            raise ValueError(
                f"checkpoint at {self.checkpoint_dir!r} was written by "
                f"an engine with chunk_rounds={eng['chunk_rounds']}, "
                f"hp_mode={eng['hp_mode']!r}; this engine has "
                f"chunk_rounds={self.chunk_rounds}, "
                f"hp_mode={self.hp_mode!r} — bit-exact resumption "
                f"needs identical chunking, construct the resuming "
                f"engine to match")
        for k, v in host["stats"].items():
            setattr(self.stats, k, v)
        self.stats.restarts += 1
        self._auto_id = max(self._auto_id, host["auto_id"])
        ctx = {
            "order": list(host["order"]),
            "buckets": [[(s, build_problem(s)) for s in specs]
                        for specs in host["bucket_specs"]],
            "bucket_index": host["bucket_index"],
            "results": dict(host["results"]),
            "resume": {"step": step,
                       "bucket_host": host["bucket_host"],
                       "pending_ids": host["pending_ids"]},
        }
        if self._queue:                      # jobs queued before resume
            queue, self._queue = self._queue, []
            ctx["order"] += [spec.job_id for spec in queue]
            ctx["buckets"] += list(bucketize(queue).values())
        return ctx

    def _clear_checkpoints(self) -> None:
        """A completed run owes the disk nothing: drop every step and
        sidecar so the next run() starts fresh instead of resuming."""
        if self.checkpoint_dir is None \
                or not os.path.isdir(self.checkpoint_dir):
            return
        from repro import checkpoint as ckpt
        ckpt.sweep_stale(self.checkpoint_dir)
        for s in ckpt.checkpoint_steps(self.checkpoint_dir):
            os.remove(os.path.join(self.checkpoint_dir,
                                   f"step_{s:08d}.npz"))
        for f in os.listdir(self.checkpoint_dir):
            if re.fullmatch(r"state_\d+\.pkl", f):
                os.remove(os.path.join(self.checkpoint_dir, f))

    # -- accounting --------------------------------------------------------

    def _make_result(self, bucket: BucketState, rec) -> JobResult:
        chans = bucket.op.ledger.channels
        wire_bytes = sum(sends * chans[name].bytes_per_send
                         for name, sends in rec.sends.items())
        wire_floats = sum(sends * chans[name].floats_per_send
                          for name, sends in rec.sends.items())
        return JobResult(
            job_id=rec.spec.job_id, x=rec.x, y=rec.y, rounds=rec.rounds,
            converged=rec.converged, final_gap=rec.final_gap,
            wire_bytes=int(wire_bytes), wire_floats=int(wire_floats),
            sends=dict(rec.sends), wall_clock_s=rec.wall_s,
            signature=bucket.signature, metrics=rec.metrics,
            quarantined=rec.quarantined, flight=rec.flight)

    def _finalize_ledger(self, bucket: BucketState) -> None:
        """Charge the bucket ledger with per-job send arrays (ordered
        by retirement) so `CommLedger.per_job_bytes` attributes exact
        traffic and the total is their sum (additivity, tested)."""
        for name in bucket.op.ledger.channels:
            bucket.op.ledger.charge(name, np.asarray(
                [rec.sends[name] for rec in bucket.retired], np.int64))
        self.ledgers[bucket.signature] = bucket.op.ledger
