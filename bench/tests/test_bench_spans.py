"""The program's spans on the profiler's clock, device time by named
phase, and the readers of the spans, counters and phases."""
from __future__ import annotations

import glob
import json
import threading
from pathlib import Path

import pytest

from harness import scopes, trace
from harness.runner import RunRecord
from harness.spec import Cell
from repro import obs
from repro.obs import SpanEvent
from tiny import make_tiny_bench

DATA = Path(__file__).resolve().parent / "data"


def test_enabled_spans_are_host_events_of_the_profiler_trace(tmp_path):
    """An enabled span is a `repro:<name>` host event on the thread that
    ran it, inside the window; a disabled one leaves nothing."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((32, 32))
    f(a).block_until_ready()

    def other():
        with obs.span("worker", cat="t"):
            f(a).block_until_ready()

    obs.enable_tracing(False)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with obs.span("quiet", cat="t"):
            f(a).block_until_ready()
        with obs.tracing():
            with obs.span("work", cat="t", k=1):
                f(a).block_until_ready()
            t = threading.Thread(target=other, name="worker-thread")
            t.start()
            t.join()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    norm = trace.normalize(path)
    host = {h[1]: h for h in norm["host"] if h[1].startswith("repro:")}
    assert set(host) == {"repro:work", "repro:worker"}
    t0, t1 = norm["window"]
    for _, _, start, dur in host.values():
        assert t0 <= start and start + dur <= t1
    # each on the line of the thread that ran it
    from jax.profiler import ProfileData
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    line_of = {e.name: i for i, line in enumerate(plane.lines)
               for e in line.events if e.name in host}
    assert line_of["repro:work"] != line_of["repro:worker"]
    obs.tracer().clear()


@pytest.mark.parametrize("path, phase", [
    ("jit(run)/jit(main)/while/body/inner_dgd/gossip/add", "gossip"),
    # as a v5e profile writes it
    ("jit(f)/outer_step/while/body/closed_call/gossip/add:", "gossip"),
    ("jit(run)/while/body/vmap(outer_step):", "outer_step"),
    ("jit(run)/while/body/outer_step/dihgp/dot_general", "dihgp"),
    ("jit(run)/transpose(jvp(outer_step))/mul", "outer_step"),
    ("jit(step)/vmap(inner_dgd)/while/body/dot_general", "inner_dgd"),
    ("jit(run)/while/body/mul", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
    ("jit(run)/inner_dgd_extra/add", scopes.UNSCOPED),
])
def test_phase_of_takes_the_innermost_phase(path, phase):
    assert scopes.phase_of(path) == phase


def test_scope_times_split_self_time_by_phase():
    """A while op spanning its body keeps its own time in its own phase;
    ops are clipped to the window; two devices are averaged."""
    norm = {"window": [0.0, 100.0], "devices": [
        [["%while.1 = (f32[4]) while(...)", 0.0, 100.0, "jit(run)/while"],
         ["%fusion.2 = f32[4] fusion(...)", 10.0, 30.0,
          "jit(run)/while/body/inner_dgd/dot_general"],
         ["%fusion.3 = f32[4] fusion(...)", 40.0, 20.0,
          "jit(run)/while/body/inner_dgd/gossip/add"],
         ["%fusion.4 = f32[4] fusion(...)", 90.0, 30.0,
          "jit(run)/while/body/outer_step/dihgp/mul"]],
        [["fusion.5", 0.0, 40.0, "jit(run)/outer_step/sub"]],
    ]}
    s = scopes.scope_times(norm)
    assert s == pytest.approx({
        scopes.UNSCOPED: (100 - 30 - 20 - 10) / 2 * 1e-9,
        "inner_dgd": 30 / 2 * 1e-9, "gossip": 20 / 2 * 1e-9,
        "dihgp": 10 / 2 * 1e-9, "outer_step": 40 / 2 * 1e-9})


def test_recorded_v5e_trace_summary_unchanged_and_unscoped():
    """The recorded trace's summary reads as it did, and a trace with no
    scope paths puts all of its busy time under `UNSCOPED`."""
    norm = json.loads((DATA / "trace_ring_v5e.json").read_text())
    s = trace.summarize(norm)
    assert list(s) == ["window_s", "busy_s", "collective_s",
                       "collective_exposed_s", "device_ops", "idle_gaps",
                       "devices"]
    assert s["window_s"] == pytest.approx(3e-3)
    assert s["busy_s"] == pytest.approx(3e-3)
    assert s["devices"] == 1 and s["idle_gaps"] == []
    assert s["collective_s"] == s["collective_exposed_s"] == 0
    assert [n for n, _ in s["device_ops"][:2]] == ["fusion.266",
                                                   "fusion.258"]
    bare = {"window": norm["window"],
            "devices": [[op + [""] for op in ops]
                        for ops in norm["devices"]]}
    assert scopes.scope_times(bare) == pytest.approx(
        {scopes.UNSCOPED: s["busy_s"]})


def write_xplane(root: Path, scoped: bool = True) -> None:
    """A small TPU-shaped profile: one device, an "XLA Ops" line whose
    events' metadata carry their scope path (with no phase named in it
    unless `scoped`), and the harness's window mark."""
    from jax.profiler import ProfileData
    ops = [("%while.1 = while()", 0, 10_000_000, "jit(run)/while"),
           ("%fusion.2 = fusion()", 1_000_000, 4_000_000,
            "jit(run)/while/body/inner_dgd/dot_general"),
           ("%fusion.3 = fusion()", 5_000_000, 2_000_000,
            "jit(run)/while/body/outer_step/dihgp/gossip/add"),
           ("%fusion.4 = fusion()", 7_000_000, 1_000_000,
            "jit(run)/while/body/outer_step/dihgp/mul"),
           ("%fusion.5 = fusion()", 8_000_000, 1_500_000,
            "jit(run)/while/body/outer_step/sub")]
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" '
        f'stats {{ metadata_id: 1 str_value: '
        f'"{p if scoped else "jit(run)/while/body/add"}" }} }} }}'
        for i, (name, _, _, p) in enumerate(ops, 1))
    events = " ".join(
        f"events {{ metadata_id: {i} offset_ps: {s} duration_ps: {d} }}"
        for i, (_, s, d, _) in enumerate(ops, 1))
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {events} }}
  {meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "{scopes.STAT}" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{trace.WINDOW}" }} }} }}
"""
    dest = root / scopes.TRACE_DIR / "plugins/profile/run"
    dest.mkdir(parents=True)
    (dest / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_device_ms_readers_read_the_runs_profile(tmp_path):
    bench = make_tiny_bench(tmp_path)
    write_xplane(tmp_path)
    path, = glob.glob(str(tmp_path / scopes.TRACE_DIR / "*/*/*/*.pb"))
    norm = scopes.normalize(path)
    assert norm["window"] == [1000.0, 21000.0]
    assert [op[3] for op in norm["devices"][0]][:2] == [
        "jit(run)/while", "jit(run)/while/body/inner_dgd/dot_general"]
    cell = Cell("hyperrep16.ring", bench)
    rec = RunRecord(cell, {"rounds": 2}, {"window_s": 2e-5}, [],
                    {"kind": "TPU v5 lite"}, 1)
    readers = cell.readers()
    got = {ph: readers[f"device_ms.{ph}"](rec) for ph in scopes.PHASES}
    # ns of self time / 2 rounds, in ms
    assert got == pytest.approx({"inner_dgd": 4e3 / 2 * 1e-6,
                                 "gossip": 2e3 / 2 * 1e-6,
                                 "dihgp": 1e3 / 2 * 1e-6,
                                 "outer_step": 1.5e3 / 2 * 1e-6})
    assert scopes.scope_seconds(rec)[scopes.UNSCOPED] == pytest.approx(
        1.5e-6)
    # no rounds, or no traced summary: nothing to read
    assert readers["device_ms.dihgp"](RunRecord(
        cell, {}, {"window_s": 2e-5}, [], {}, 1)) is None
    assert readers["device_ms.dihgp"](RunRecord(
        cell, {"rounds": 2}, {}, [], {}, 1)) is None


def test_device_ms_readers_without_scoped_ops(tmp_path):
    """A program without named scopes (every op unscoped, as the parent
    of this benchmark's scopes runs) gives no device_ms.* at all."""
    bench = make_tiny_bench(tmp_path)
    write_xplane(tmp_path, scoped=False)
    cell = Cell("hyperrep16.ring", bench)
    rec = RunRecord(cell, {"rounds": 2}, {"window_s": 2e-5}, [], {}, 1)
    assert all(cell.readers()[f"device_ms.{ph}"](rec) is None
               for ph in scopes.PHASES)


def test_metadata_stats_resolves_string_and_ref_values():
    from jax.profiler import ProfileData
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1"
    stats {{ metadata_id: 7 str_value: "jit(f)/dihgp/add" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2"
    stats {{ metadata_id: 8 str_value: "other" }}
    stats {{ metadata_id: 7 ref_value: 9 }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "copy.3" }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "{scopes.STAT}" }} }}
  stat_metadata {{ key: 8 value {{ id: 8 name: "hlo_category" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "jit(f)/gossip/mul" }} }} }}
planes {{ id: 2 name: "/host:CPU" }}
"""
    buf = ProfileData.text_proto_to_serialized_xspace(text)
    assert scopes.metadata_stats(buf) == {"/device:TPU:0": {
        "fusion.1": "jit(f)/dihgp/add", "fusion.2": "jit(f)/gossip/mul"}}


def record(cell: str, window: dict, spans) -> RunRecord:
    c = Cell(cell)
    return RunRecord(c, window, {}, list(spans), {"kind": "TPU v5 lite"},
                     c.chips)


def test_front_end_readers():
    solves = [SpanEvent("solve", "solver", 0.0, 9e5, args={
        "trace_s": 0.3, "lower_s": 0.1, "backend_s": 0.5}),
        SpanEvent("solve", "solver", 1e6, 9e5, args={
            "trace_s": 0.5, "lower_s": 0.1, "backend_s": 0.3})]
    for cell in ("hyperrep16.ring", "hyperrep4.sharded"):
        readers = Cell(cell).readers()
        rec = record(cell, {"rounds": 1000}, solves)
        assert readers["front_trace_ms.solve"](rec) == pytest.approx(500.0)
        assert readers["front_load_ms.solve"](rec) == pytest.approx(400.0)
        # solve spans of a program that counts no compile stages
        bare = record(cell, {}, [SpanEvent("solve", "solver", 0.0, 1.0)])
        assert readers["front_trace_ms.solve"](bare) is None
        assert readers["front_load_ms.solve"](bare) is None


def test_round_dispatch_reader():
    """Mean dispatch over the rounds after each call's first, whose
    dispatch holds the call's tracing and loading."""
    spans = []
    for call, t in enumerate((0.0, 1e6)):
        spans += [SpanEvent("outer_round", "r", t + 1e4 * k, 9e3,
                            args={"round": k}) for k in range(3)]
        spans.append(SpanEvent("round_dispatch", "r", t + 10.0, 7e5))
        spans += [SpanEvent("round_dispatch", "r", t + 1e4 * k + 10.0,
                            1e3 * (k + call)) for k in (1, 2)]
    spans += [SpanEvent("round_sync", "r", 0.0, 9e3)]
    read = Cell("hyperrep4.sharded").readers()["round_dispatch_ms.sharded"]
    assert read(record("hyperrep4.sharded", {}, spans)) == \
        pytest.approx(2.0)
    only_first = [s for s in spans if s.name == "outer_round"
                  or s.dur_us == 7e5]
    assert read(record("hyperrep4.sharded", {}, only_first)) is None


def test_scheduler_readers():
    readers = Cell("horeg16.serve_poisson").readers()
    spans = [
        # a tick that advanced a bucket: 100 ms, 60 of them on the device
        SpanEvent("tick", "s", 0.0, 1e5, args={"active": 6, "width": 8}),
        SpanEvent("chunk_wait", "s", 2e4, 4e4),
        SpanEvent("chunk_wait", "s", 7e4, 2e4),
        # an idle tick advances nothing and is left out
        SpanEvent("tick", "s", 2e5, 3e3),
        SpanEvent("tick", "s", 3e5, 5e4, args={"active": 8, "width": 8}),
        SpanEvent("chunk_wait", "s", 3.1e5, 3e4),
        SpanEvent("submit_locked", "s", 1e5, 3e3, args={"jobs": 1}),
        SpanEvent("submit_locked", "s", 1.5e5, 9e3, args={"jobs": 3}),
        SpanEvent("submit", "s", 1e5, None, args={"job_id": "j0"}),
    ]
    rec = record("horeg16.serve_poisson", {}, spans)
    assert readers["tick_host_ms.serve"](rec) == pytest.approx(30.0)
    assert readers["submit_lock_ms.serve"](rec) == pytest.approx(3.0)
    assert readers["slot_occupancy.serve"](rec) == pytest.approx(87.5)
    idle = record("horeg16.serve_poisson", {},
                  [SpanEvent("tick", "s", 0.0, 10.0)])
    assert readers["tick_host_ms.serve"](idle) is None
    assert readers["slot_occupancy.serve"](idle) is None
    assert readers["submit_lock_ms.serve"](idle) is None
