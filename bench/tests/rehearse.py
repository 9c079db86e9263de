"""Drive a whole run of a cell at CPU-test size, the chip check skipped.

    python bench/tests/rehearse.py <cell> <seconds> [fault]

prints the result line as JSON.  With a fault name, the timed path is
broken underneath first (`FAULTS`), the way a faulty program would be.
The sharded cell runs here on 4 virtual CPU devices, so the tests start
this file in a process of its own.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
for path in (HERE.parent, HERE, HERE.parent.parent / "src"):
    sys.path.insert(0, str(path))

from tiny import make_tiny_bench  # noqa: E402


def _solve_returning(edit):
    """Patch `repro.solve.solve` so its result passes through `edit`."""
    import repro.solve
    real = repro.solve.solve

    def solve(prob, net, spec, **kw):
        res = real(prob, net, spec, **kw)
        res.x, res.y = edit(res.x, res.y, kw)
        return res
    repro.solve.solve = solve


def _jobs_returning(edit):
    """Patch the serve engine so each job's result passes through
    `edit(x, y, x0, y0)`, with the job's start by the service's init
    protocol (x0 = 0, y0 = 0.01·N(0, I) from the job's seed)."""
    import jax
    import numpy as np
    from repro.serve.engine import ServeEngine
    real = ServeEngine._make_result

    def make_result(self, bucket, rec):
        res = real(self, bucket, rec)
        x, y = np.asarray(res.x), np.asarray(res.y)
        y0 = 0.01 * np.asarray(jax.random.normal(
            jax.random.PRNGKey(rec.spec.seed), y.shape))
        res.x, res.y = edit(x, y, np.zeros_like(x), y0)
        return res
    ServeEngine._make_result = make_result


def state_unchanged():
    """solve() and the service hand back their start."""
    _solve_returning(lambda x, y, kw: (kw["x0"], kw["y0"]))
    _jobs_returning(lambda x, y, x0, y0: (x0, y0))


def answer_altered():
    """One agent's outer iterate comes back as its start."""
    _solve_returning(lambda x, y, kw: (x.at[0].set(kw["x0"][0]), y))

    def first_agent_reset(x, y, x0, y0):
        x = x.copy()
        x[0] = x0[0]
        return x, y
    _jobs_returning(first_agent_reset)


def half_batch():
    """The program sees only the first half of every agent's rows."""
    from repro.core.problems import BilevelProblem
    real = BilevelProblem.with_data

    def with_data(self, data):
        import jax
        return real(self, jax.tree.map(
            lambda a: a[:, : max(a.shape[1] // 2, 1)]
            if a.ndim >= 2 else a, data))
    BilevelProblem.with_data = with_data


def no_exchange():
    """The ring ppermute between chips is left out: W = I."""
    import jax
    from repro.distributed import dagm_sharded
    dagm_sharded.ring_mix_c = lambda tree, axis, w, pol, st: (tree, st)
    dagm_sharded.ring_laplacian_c = lambda tree, axis, w, pol, st: (
        jax.tree.map(lambda a: a * 0, tree), st)


def control():
    """The reference computed in bfloat16 takes the program's place."""
    from harness.base import GeneratorBase
    real = GeneratorBase.check

    def check(self, limits):
        self.use_control()
        return real(self, limits)
    GeneratorBase.check = check


FAULTS = {f.__name__: f for f in (state_unchanged, answer_altered,
                                  half_batch, no_exchange, control)}


def rehearse(cell: str, seconds: float, fault: str | None = None) -> dict:
    from harness import runner
    runner.enable_cache = lambda: "off (test)"
    if fault:
        FAULTS[fault]()
    with tempfile.TemporaryDirectory() as tmp:
        bench = make_tiny_bench(Path(tmp))
        result, _ = runner.run_cell(
            cell, seed=2**33 + 12345, seconds=seconds, trace=False,
            t_start=T_START, trace_dir=Path(tmp) / "trace", bench_dir=bench,
            allow_cpu=True)
    return result


if __name__ == "__main__":
    print(json.dumps(rehearse(sys.argv[1], float(sys.argv[2]),
                              sys.argv[3] if len(sys.argv) > 3 else None)))
