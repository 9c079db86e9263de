"""A benchmark directory at CPU-test sizes, built from the real one.

`make_tiny_bench(dest)` copies every file the harness discovers by name
into `dest/bench/` and writes `dest/BENCHMARK.json`, with each
configuration and mix cut to a size a test run holds.  Widths, depths
and rates are the only changes; every code path is the real one.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_PROBLEMS = {
    "hyper_representation": {"n": 4, "d": 16, "hidden": 8, "n_classes": 3,
                             "m_per": 20},
    "ho_regression": {"n": 4, "d": 16, "m_per": 8},
}
TINY_TRAFFIC = {
    "solve_loop": {"rounds_per_call": 60},
    "poisson_jobs": {"rate_hz": 40.0, "budgets": [4, 8], "chunk_rounds": 4,
                     "bucket_width": 2, "warm_jobs": 2, "grace_s": 30,
                     "sample": 4},
}


def make_tiny_bench(dest: Path) -> Path:
    """Write a tiny copy of the benchmark under `dest`; returns its
    bench directory."""
    bench = dest / "bench"
    for kind in ("generators", "references", "metrics"):
        shutil.copytree(BENCH / kind, bench / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for path in (BENCH / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["problem"].update(TINY_PROBLEMS[cfg["problem"]["family"]])
        (bench / "configs" / path.name).write_text(json.dumps(cfg))
    for path in (BENCH / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(TINY_TRAFFIC[mix["generator"]])
        (bench / "traffic" / path.name).write_text(json.dumps(mix))
    shutil.copy(BENCH.parent / "BENCHMARK.json", dest / "BENCHMARK.json")
    return bench
