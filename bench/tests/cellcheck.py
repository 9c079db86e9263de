"""Checks shared by the per-cell rehearsal tests."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from harness.spec import Cell

HERE = Path(__file__).resolve().parent
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def rehearse(cell: str, fault: str = "", devices: int = 1) -> dict:
    """One run in a process of its own (faults patch the program)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run(
        [sys.executable, str(HERE / "rehearse.py"), cell, "0.5"]
        + ([fault] if fault else []),
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


FAULTS = ("control", "state_unchanged", "answer_altered", "half_batch")


def check_result_line(cell: str, devices: int) -> None:
    """A sound run's result line: the contract's keys and nothing else."""
    res = rehearse(cell, devices=devices)
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    names = {m["name"] for m in Cell(cell).end_to_end}
    assert set(res["metrics"]) == names
    for m in res["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["count"] == devices
    limits = Cell(cell).config["limits"]
    assert {k: v["limit"] for k, v in res["checks"].items()} == limits


def check_fault(cell: str, devices: int, fault: str) -> None:
    """A run with the timed path broken, or the control in its place,
    is not correct."""
    res = rehearse(cell, fault, devices)
    assert res["correct"] is False, res["checks"]
