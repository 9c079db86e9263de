"""Find a cell's pieces by name, from files alone.

Under the benchmark directory:

    configs/<config>.json      sizes, solver settings, limits
    traffic/<traffic>.json     the mix: which generator, its parameters
    generators/<generator>.py  the general generator of one kind of
                               traffic: a class `Generator`
    references/<family>.py     the plain reference of a problem family
    metrics/<metric>.py        a per-layer metric's reader: `read(run)`

and `BENCHMARK.json` one directory up.  Adding a configuration, a mix or
a metric is adding its file and its entry; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def load_benchmark(bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((bench_dir.parent / "BENCHMARK.json").read_text())


def load_module(path: Path, name: str):
    """Import the file at `path` as a fresh module named `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic,
    generator, reference and per-layer metric readers."""

    def __init__(self, name: str, bench_dir: Path = BENCH_DIR,
                 benchmark: dict | None = None):
        self.bench_dir = Path(bench_dir)
        bench = benchmark if benchmark is not None \
            else load_benchmark(self.bench_dir)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        self.config = json.loads(
            (self.bench_dir / "configs" /
             f"{self.workload['config']}.json").read_text())
        self.traffic = json.loads(
            (self.bench_dir / "traffic" /
             f"{self.workload['traffic']}.json").read_text())
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def module(self, kind: str, name: str):
        return load_module(self.bench_dir / kind / f"{name}.py",
                           f"bench_{kind}_{name}".replace(".", "_"))

    def generator_class(self):
        return self.module("generators", self.traffic["generator"]).Generator

    def reference(self):
        return self.module("references", self.config["problem"]["family"])

    def readers(self) -> dict:
        return {m["name"]: self.module("metrics", m["name"]).read
                for m in self.per_layer}
