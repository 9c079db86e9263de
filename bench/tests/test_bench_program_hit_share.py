"""`program_hit_share.solve`: the share of solve() calls whose
`trace_compile` span says the program came from solve()'s cache."""
from __future__ import annotations

import pytest

from harness.runner import RunRecord
from harness.spec import Cell
from repro.obs import SpanEvent


def record(cell: str, spans) -> RunRecord:
    c = Cell(cell)
    return RunRecord(c, {}, {}, list(spans), {"kind": "TPU v5 lite"},
                     c.chips)


def compile_span(t: float, **args) -> SpanEvent:
    return SpanEvent("trace_compile", "solver.compile", t, 10.0,
                     args=dict(rounds=500, **args))


@pytest.mark.parametrize("cell", ["hyperrep16.ring", "hyperrep4.sharded"])
def test_share_of_hits(cell):
    read = Cell(cell).readers()["program_hit_share.solve"]
    spans = [compile_span(0.0, cache="miss")]
    spans += [compile_span(1e3 * i, cache="hit") for i in range(1, 4)]
    spans.append(SpanEvent("chunk", "solver.chunk", 9e3, 5.0,
                           args={"cache": "miss"}))
    assert read(record(cell, spans)) == pytest.approx(75.0)
    assert read(record(cell, spans[1:])) == pytest.approx(100.0)
    assert read(record(cell, spans[:1])) == pytest.approx(0.0)


@pytest.mark.parametrize("cell", ["hyperrep16.ring", "hyperrep4.sharded"])
def test_nothing_to_read(cell):
    """No `trace_compile` span, or only spans of a program that keeps
    no program cache (no `cache` arg), read None."""
    read = Cell(cell).readers()["program_hit_share.solve"]
    assert read(record(cell, [])) is None
    solve = SpanEvent("solve", "solver", 0.0, 20.0, args={"tier": "x"})
    assert read(record(cell, [solve])) is None
    assert read(record(cell, [solve, compile_span(1.0)])) is None
