"""The harness finds a configuration, a traffic mix and a per-layer
metric from their files alone, and refuses to measure without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from harness import runner
from harness.spec import Cell
from tiny import make_tiny_bench

ROOT = Path(__file__).resolve().parents[2]


def add_files(bench: Path) -> None:
    """A new configuration, mix and metric, and their entries."""
    cfg = json.loads((bench / "configs" / "horeg_hpo16.json").read_text())
    cfg["name"] = "horeg_wide"
    cfg["problem"]["d"] = 24
    (bench / "configs" / "horeg_wide.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "serve_poisson.json").read_text())
    mix["rate_hz"] = 25.0
    (bench / "traffic" / "serve_slow.json").write_text(json.dumps(mix))
    (bench / "metrics" / "jobs_in_window.py").write_text(
        "def read(run):\n    return float(run.window['jobs'])\n")
    path = bench.parent / "BENCHMARK.json"
    b = json.loads(path.read_text())
    b["configs"].append({"name": "horeg_wide", "source": "test",
                         "file": "bench/configs/horeg_wide.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "horeg_wide.serve_slow",
                           "config": "horeg_wide", "traffic": "serve_slow",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if "horeg16.serve_poisson" in m.get("workloads", []):
            m["workloads"].append("horeg_wide.serve_slow")
    b["per_layer"].append({"name": "jobs_in_window", "unit": "jobs",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler",
                           "moves": "jobs_per_s",
                           "workloads": ["horeg_wide.serve_slow"]})
    path.write_text(json.dumps(b))


def test_new_config_mix_and_metric_from_files_alone(tmp_path, monkeypatch):
    bench = make_tiny_bench(tmp_path)
    add_files(bench)
    cell = Cell("horeg_wide.serve_slow", bench)
    assert cell.config["problem"]["d"] == 24
    assert cell.traffic["rate_hz"] == 25.0
    assert [m["name"] for m in cell.per_layer] == ["jobs_in_window"]
    monkeypatch.setattr(runner, "enable_cache", lambda: "off (test)")
    result, _ = runner.run_cell(
        "horeg_wide.serve_slow", seed=7, seconds=0.4, trace=False,
        t_start=time.perf_counter(), trace_dir=tmp_path / "trace",
        bench_dir=bench, allow_cpu=True)
    assert result["correct"] is True
    assert result["attempted"] == 10          # 25 jobs/s over 0.4 s
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    rec = runner.RunRecord(cell, {"jobs": result["attempted"]}, {}, [],
                           result["device"], 1)
    assert cell.readers()["jobs_in_window"](rec) == 10.0


def run_bench(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hyperrep16.ring",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_no_tpu_means_no_result():
    out = run_bench(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_bench(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
