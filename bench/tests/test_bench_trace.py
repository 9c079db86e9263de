"""The trace reduction: on hand-made events, on a trace recorded here on
the CPU (host side only), and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import glob
import json
from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).resolve().parent / "data"


def test_busy_exposed_collective_and_labelled_gaps():
    norm = {"window": [0.0, 50.0], "devices": [[
        ["fusion.1", 0.0, 10.0], ["fusion.2", 5.0, 15.0],
        ["collective-permute-done.3", 25.0, 5.0], ["fusion.4", 28.0, 7.0],
    ]], "host": [
        ["python", trace.WINDOW, 0.0, 50.0],
        ["python", "solve_call", 0.0, 22.0],
        ["python", "trace_jaxpr", 36.0, 14.0],
    ]}
    s = trace.summarize(norm)
    assert s["window_s"] == pytest.approx(50e-9)
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["collective_s"] == pytest.approx(5e-9)
    assert s["collective_exposed_s"] == pytest.approx(3e-9)
    assert s["device_ops"][0] == ["fusion.2", pytest.approx(15e-9)]
    assert dict(s["idle_gaps"]) == {"trace_jaxpr": pytest.approx(15e-9),
                                    trace.WINDOW: pytest.approx(5e-9)}


def test_nested_ops_count_self_time_and_leaves():
    """A while op spanning its body: self time, and a collective under
    it is still exposed when no other leaf runs."""
    norm = {"window": [0.0, 100.0], "devices": [[
        ["%while.1 = (f32[4]) while(...)", 0.0, 100.0],
        ["%fusion.2 = f32[4] fusion(...)", 10.0, 30.0],
        ["%collective-permute-start.3 = f32[4] collective-permute-start()",
         50.0, 20.0],
    ]], "host": [["python", trace.WINDOW, 0.0, 100.0]]}
    s = trace.summarize(norm)
    assert dict(s["device_ops"]) == {
        "while.1": pytest.approx(50e-9), "fusion.2": pytest.approx(30e-9),
        "collective-permute-start.3": pytest.approx(20e-9)}
    assert s["busy_s"] == pytest.approx(100e-9)
    assert s["collective_exposed_s"] == pytest.approx(20e-9)


def test_two_devices_are_averaged_and_clipped_to_the_window():
    norm = {"window": [10.0, 20.0], "devices": [
        [["a", 0.0, 15.0]], [["b", 12.0, 2.0], ["c", 30.0, 5.0]]],
        "host": [["python", trace.WINDOW, 10.0, 10.0]]}
    s = trace.summarize(norm)
    assert s["busy_s"] == pytest.approx((5 + 2) / 2 * 1e-9)
    assert s["devices"] == 2


def test_interval_arithmetic():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_recorded_v5e_trace():
    """3 ms of a `hyperrep16.ring` trace on a TPU v5e: the scan's while op
    spans the cut, so the device is busy throughout; self time puts the
    two large fusions of the round first and sums to the busy time."""
    norm = json.loads((DATA / "trace_ring_v5e.json").read_text())
    s = trace.summarize(norm)
    assert s["busy_s"] == pytest.approx(s["window_s"]) == pytest.approx(3e-3)
    names = [name for name, _ in s["device_ops"]]
    assert names[:2] == ["fusion.266", "fusion.258"]
    assert all(" = " not in name for name in names)
    t0, t1 = norm["window"]
    ops = sorted(((trace.op_name(n), max(st, t0), min(st + d, t1))
                  for n, st, d in norm["devices"][0]),
                 key=lambda op: (op[1], op[1] - op[2]))
    own, leaf = trace.self_times(ops)
    assert sum(own) * 1e-9 == pytest.approx(s["busy_s"], rel=1e-6)
    assert not leaf[0] and ops[0][0].startswith("while")
    assert s["collective_s"] == 0 and s["idle_gaps"] == []


def test_normalize_reads_host_events_and_the_window(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((64, 64))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        f(a).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    norm = trace.normalize(path)
    t0, t1 = norm["window"]
    assert t1 > t0
    assert any(h[1] == trace.WINDOW for h in norm["host"])
    assert norm["devices"] == []          # the CPU has no device plane
