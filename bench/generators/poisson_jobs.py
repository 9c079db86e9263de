"""Open-loop job stream into an always-on `AdmissionLoop`: many small
tenant jobs.

Traffic parameters:

    rate_hz        offered jobs per second (fixed; see PERF.md for the
                   sweep it came from)
    budgets        round budgets K; the jobs of a window take each in
                   equal shares
    pattern_seed   fixes the order of the gaps and budgets; the run's
                   seed rotates it (`harness.load.arrival_pattern`)
    alpha          {"base", "step", "period"}: job j runs α = base +
                   step·(j mod period)
    bucket_width, chunk_rounds, hp_mode, klass   the loop's settings
    warm_jobs      jobs run to their end at set-up (every budget)
    grace_s        how long after the window's close to wait for jobs
    sample         jobs, drawn from the seed, compared with the
                   reference after the window

Job j is due at the j-th arrival time (`harness.load`);
its latency runs from that due time to the moment the client, reading
`as_completed`, holds its result.  A job still out `grace_s` after the
window is missing: its latency counts as infinite.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from harness.base import GeneratorBase
from harness.load import arrival_pattern, quantile, rotated_arrivals

SEED_MASK = 0x7FFFFFFF


class Generator(GeneratorBase):
    def __init__(self, cell, seed: int, devices, log):
        from repro.serve.admission import AdmissionLoop
        from repro.solve import dagm_spec

        self.cell, self.seed, self.log = cell, seed, log
        self.p = dict(cell.config["problem"])
        self.s = dict(cell.config["solver"])
        self.t = dict(cell.traffic)
        self.ref = cell.reference()
        self.objectives = self.ref.objectives(self.p)
        self.dagm = cell.module("references", "dagm")
        self.base_spec = dagm_spec(
            alpha=self.t["alpha"]["base"], beta=self.s["beta"],
            K=max(self.t["budgets"]), M=self.s["M"], U=self.s["U"],
            dihgp=self.s["dihgp"], curvature=self.s["curvature"],
            mixing=self.s["mixing"], comm=self.s["comm"])
        width = int(self.t["bucket_width"])
        self.loop = AdmissionLoop(
            chunk_rounds=int(self.t["chunk_rounds"]), max_width=width,
            bucket_width=width, hp_mode=self.t["hp_mode"],
            telemetry=False)
        self.specs: dict = {}
        self.results: dict = {}
        budgets = self.t["budgets"]
        warm = [self.job(f"warm{j}", (seed + 1_000_003 + j) & SEED_MASK,
                         budgets[j % len(budgets)], j)
                for j in range(int(self.t["warm_jobs"]))]
        self.loop.start()
        self.loop.submit(warm)
        for spec in warm:
            self.loop.result(spec.job_id, timeout=600)

    def job(self, job_id: str, job_seed: int, K: int, j: int):
        """Job j of the stream: its own data seed, budget and α."""
        from repro.serve import JobSpec
        from repro.solve import ScheduleSpec
        a = self.t["alpha"]
        alpha = a["base"] + a["step"] * (j % a["period"])
        spec = JobSpec(
            self.p["family"],
            {"n": self.p["n"], "d": self.p["d"], "m_per": self.p["m_per"],
             "seed": job_seed},
            dataclasses.replace(self.base_spec, K=K, schedule=ScheduleSpec(
                alpha=alpha, beta=self.s["beta"])),
            graph=self.s["graph"], seed=job_seed, job_id=job_id,
            klass=self.t["klass"])
        self.specs[job_id] = (spec, job_seed, K, alpha)
        return spec

    def window(self, seconds: float) -> dict:
        gaps, sizes = arrival_pattern(
            float(self.t["rate_hz"]), seconds, int(self.t["pattern_seed"]),
            self.t["budgets"])
        due, budgets = rotated_arrivals(gaps, sizes, seconds, self.seed)
        specs = [self.job(f"j{j}", (self.seed + 1 + j) & SEED_MASK,
                          int(budgets[j]), j) for j in range(len(due))]
        ids = [s.job_id for s in specs]
        done_at: dict[str, float] = {}
        grace = float(self.t["grace_s"])

        def client():
            try:
                for r in self.loop.as_completed(ids, timeout=seconds + grace):
                    done_at[r.job_id] = time.perf_counter()
                    self.results[r.job_id] = r
            except TimeoutError:
                pass

        reader = threading.Thread(target=client, name="bench-client")
        late = np.zeros(len(due))
        t0 = time.perf_counter()
        reader.start()
        for j, spec in enumerate(specs):
            wait = t0 + due[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[j] = time.perf_counter() - t0 - due[j]
            self.loop.submit(spec)
        t_close = t0 + seconds
        reader.join(timeout=max(t_close + grace - time.perf_counter(), 0)
                    + 5)
        lat = [done_at[i] - (t0 + d) if i in done_at else float("inf")
               for i, d in zip(ids, due)]
        retired = sum(1 for i in ids if i in done_at and
                      done_at[i] <= t_close)
        self.window_ids = ids
        self.log(f"generator lateness: p50 {1e3 * quantile(late, .5):.3f} "
                 f"ms, max {1e3 * float(late.max()):.3f} ms over "
                 f"{len(due)} jobs")
        return {"window_s": seconds, "jobs": len(ids), "retired": retired,
                "job_ids": ids, "latencies_s": lat,
                "metrics": {
                    "job_latency_p50_ms": 1e3 * quantile(lat, 0.5),
                    "jobs_per_s": retired / seconds}}

    def release(self) -> None:
        self.loop.stop(drain=False)
        ok = [i for i in self.window_ids if i in self.results
              and not self.results[i].quarantined]
        self.attempted = len(self.window_ids)
        self.failed = self.attempted - len(ok)
        rng = np.random.default_rng(self.seed)
        pick = list(rng.permutation(ok)[: int(self.t["sample"])])
        longest = [i for i in ok if self.specs[i][2] == max(
            self.t["budgets"])]
        if longest and not any(i in longest for i in pick):
            pick[-1] = longest[int(rng.integers(len(longest)))]
        self.picked = [(i, np.asarray(self.results[i].x),
                        np.asarray(self.results[i].y)) for i in pick]
        self.results.clear()
        self.loop = None

    def start(self, job_id: str):
        """The service's init protocol: x0 = 0, y0 = 0.01·N(0, I) from
        the job's seed."""
        _, job_seed, _, _ = self.specs[job_id]
        d1, d2 = self.ref.sizes(self.p)
        n = self.p["n"]
        return jnp.zeros((n, d1), jnp.float32), 0.01 * jax.random.normal(
            jax.random.PRNGKey(job_seed), (n, d2), jnp.float32)

    def reference(self, job_id: str, dtype=jnp.float32,
                  precision="highest"):
        f, g = self.objectives
        _, job_seed, K, alpha = self.specs[job_id]
        data = jax.device_put(self.ref.make_data(job_seed, self.p))
        x0, y0 = self.start(job_id)
        return self.dagm.run(
            f, g, data, x0, y0, alpha=alpha, beta=self.s["beta"], K=K,
            M=self.s["M"], U=self.s["U"], curvature=self.s["curvature"],
            dtype=dtype, precision=precision)
