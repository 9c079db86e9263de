"""Each per-layer metric's reader on a hand-made run record, and none
reads 0 or anything where it finds nothing to read."""
from __future__ import annotations

import pytest

from harness.runner import RunRecord
from harness.spec import Cell
from repro.obs import SpanEvent


def record(cell: str, window: dict, trace: dict, spans=()) -> RunRecord:
    c = Cell(cell)
    return RunRecord(c, window, trace, list(spans),
                     {"kind": "TPU v5 lite"}, c.chips)


TRACE = {"window_s": 10.0, "busy_s": 4.0, "collective_s": 0.5,
         "collective_exposed_s": 0.2}


def test_solver_readers():
    readers = Cell("hyperrep4.sharded").readers()
    rec = record("hyperrep4.sharded", {"rounds": 1000}, TRACE)
    flops = rec.cell.config["flops_per_round"]
    assert readers["round_mfu"](rec) == pytest.approx(
        100 * flops * 1000 / (10.0 * 4 * 197e12))
    assert readers["idle_share.solver"](rec) == pytest.approx(60.0)
    assert readers["collective_exposed_share"](rec) == pytest.approx(2.0)
    spans = [SpanEvent("trace_compile", "solver.compile", 0.0, 1500.0),
             SpanEvent("trace_compile", "solver.compile", 9e3, 2500.0)]
    ring = record("hyperrep16.ring", {"rounds": 10}, TRACE, spans)
    assert Cell("hyperrep16.ring").readers()["front_ms.solve"](ring) == \
        pytest.approx(2.0)


def test_serve_readers():
    readers = Cell("horeg16.serve_poisson").readers()
    spans = [SpanEvent("submit", "s", 1e3 * i, None, args={"job_id": f"j{i}"})
             for i in range(20)]
    spans += [SpanEvent("admit", "s", 1e3 * i + 100.0 * (i + 1), None,
                        args={"job_id": f"j{i}"}) for i in range(20)]
    window = {"job_ids": [f"j{i}" for i in range(20)],
              "latencies_s": [0.1 * (i + 1) for i in range(20)]}
    rec = record("horeg16.serve_poisson", window, TRACE, spans)
    assert readers["admit_wait_ms.p95"](rec) == pytest.approx(1.905)
    assert readers["job_latency_p95_ms.traced"](rec) == pytest.approx(1905.0)
    assert readers["idle_share.serve"](rec) == pytest.approx(60.0)


def test_readers_find_nothing_to_read():
    for cell in ("hyperrep16.ring", "horeg16.serve_poisson",
                 "hyperrep4.sharded"):
        rec = record(cell, {}, {}, [])
        for name, read in Cell(cell).readers().items():
            assert read(rec) is None, name
