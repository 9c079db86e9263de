"""Back-to-back `repro.solve.solve` calls: a decentralized trainer's
workload.

Traffic parameters:

    tier             "reference" (one jitted K-round scan on one chip)
                     or "sharded" (one agent per chip, ring ppermute)
    rounds_per_call  K of every call

Set-up makes the configuration's data on the device from the seed, one
start (x0) and the program's problem, network and spec, and runs one
call to warm every program.  The window then calls `solve` until
`seconds` have passed; call i starts from y0 drawn from seed + 1 + i.
Each call waits for its result, so the window holds every call's trace,
dispatch and device time.  After the window one call, drawn from the
seed, is run again by the plain reference and compared.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from harness.base import GeneratorBase


class Generator(GeneratorBase):
    def __init__(self, cell, seed: int, devices, log):
        from repro.core import make_network
        from repro.core.problems import problem_family
        from repro.solve import dagm_spec, sharded_spec

        self.cell, self.seed, self.log = cell, seed, log
        self.p = dict(cell.config["problem"])
        self.s = dict(cell.config["solver"])
        self.K = int(cell.traffic["rounds_per_call"])
        self.tier = cell.traffic["tier"]
        self.ref = cell.reference()
        self.objectives = self.ref.objectives(self.p)
        self.dagm = cell.module("references", "dagm")
        k_data, k_x = jax.random.split(jax.random.PRNGKey(seed), 2)
        self.data = self.ref.make_data(k_data, self.p)
        self.x0 = self.ref.init_x(k_x, self.p)
        _, self.d2 = self.ref.sizes(self.p)
        # the program's problem: its own objectives, over the data above
        small = {k: v for k, v in self.p.items()
                 if k not in ("family", "margin", "m_per")}
        self.prob = problem_family(self.p["family"])(
            m_per=2, seed=0, **small).with_data(self.data)
        if self.tier == "sharded":
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self.mesh = Mesh(np.array(devices[: self.p["n"]]), ("data",))
            self.put = NamedSharding(self.mesh, PartitionSpec("data"))
            self.prob = self.prob.with_data(
                jax.device_put(self.data, self.put))
            self.x0_in = jax.device_put(self.x0, self.put)
            self.spec = sharded_spec(
                alpha=self.s["alpha"], beta=self.s["beta"], M=self.s["M"],
                U=self.s["U"], curvature=self.s["curvature"],
                comm=self.s["comm"], K=self.K)
            self.net = None
        else:
            self.mesh = None
            self.spec = dagm_spec(
                alpha=self.s["alpha"], beta=self.s["beta"], K=self.K,
                M=self.s["M"], U=self.s["U"], dihgp=self.s["dihgp"],
                curvature=self.s["curvature"], mixing=self.s["mixing"],
                comm=self.s["comm"])
            self.net = make_network(self.s["graph"], self.p["n"])
            self.x0_in = self.x0
        self.outs: list = []
        self.call(seed)          # warm-up: compiles, or loads the cache
        self.outs.clear()

    def y0(self, seed: int):
        return 0.01 * jax.random.normal(jax.random.PRNGKey(seed),
                                        (self.p["n"], self.d2), jnp.float32)

    def call(self, seed: int):
        """The timed path: one solve() from (x0, y0(seed)), to its end."""
        from repro.solve import solve
        y0 = self.y0(seed)
        if self.mesh is not None:
            res = solve(self.prob, None, self.spec, mesh=self.mesh,
                        x0=self.x0_in, y0=jax.device_put(y0, self.put),
                        seed=seed)
        else:
            res = solve(self.prob, self.net, self.spec, x0=self.x0_in,
                        y0=y0, seed=seed)
        out = jax.block_until_ready((res.x, res.y))
        self.outs.append((seed, out))
        return out

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            self.call(self.seed + 1 + i)
            i += 1
        elapsed = time.perf_counter() - t0
        rounds = i * self.K
        return {"window_s": elapsed, "rounds": rounds, "calls": i,
                "metrics": {"round_ms": 1e3 * elapsed / rounds}}

    def release(self) -> None:
        """Keep only the compared call's result; drop the program."""
        finite = [bool(jnp.isfinite(x).all() & jnp.isfinite(y).all())
                  for _, (x, y) in self.outs]
        self.failed = finite.count(False)
        self.attempted = len(finite)
        pick = int(np.random.default_rng(self.seed).integers(len(self.outs)))
        seed, (x, y) = self.outs[pick]
        self.picked = [(seed, *jax.device_get((x, y)))]
        self.outs.clear()
        self.prob = None

    def start(self, seed: int):
        return self.x0, self.y0(seed)

    def reference(self, seed: int, dtype=jnp.float32, precision="highest"):
        f, g = self.objectives
        x0, y0 = self.start(seed)
        return self.dagm.run(
            f, g, self.data, x0, y0, alpha=self.s["alpha"],
            beta=self.s["beta"], K=self.K, M=self.s["M"], U=self.s["U"],
            curvature=self.s["curvature"], dtype=dtype, precision=precision)
