"""repro.obs — the observability substrate's contracts.

The load-bearing guarantees, in order of importance:

  * **inert when off / bitwise identical when on** — enabling span
    tracing + the in-`jit` flight recorder changes NOTHING about a
    solve's trajectory, on the reference tier and through the serve
    engine (the recorder rides the carry as a pure extra leaf; the
    disabled paths are literally the historical code);
  * **zero additional retraces** — the recorder is part of the compile
    key, not a per-call respecialization: one program serves the run;
  * **exported traces are valid Perfetto** — required ph/ts/pid/tid,
    well-formed per-track nesting (and `validate_trace` REJECTS
    malformed documents, so the validator itself is load-bearing);
  * the metrics registry's Prometheus text round-trips, and
    `TraceCounter` counts traces (not calls).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import make_mixing_op, make_network, quadratic_bilevel
from repro.solve import dagm_spec, solve
from repro.solve.spec import mixing_kwargs


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test starts with tracing off and an empty registry."""
    obs.reset_metrics()
    obs.tracer().clear()
    obs.enable_tracing(False)
    yield
    obs.reset_metrics()
    obs.tracer().clear()
    obs.enable_tracing(False)


def _spec(K=6, **kw):
    kw.setdefault("mixing", "sparse_gather")
    return dagm_spec(alpha=0.05, beta=0.1, K=K, M=3, U=2,
                     dihgp="matrix_free", curvature=6.0, **kw)


def _problem():
    return quadratic_bilevel(6, 4, 8, seed=0), make_network("ring", 6)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing():
    with obs.span("work", cat="t") as sp:
        sp.annotate(k=1)
        obs.instant("tick")
    assert len(obs.tracer()) == 0


def test_span_nesting_and_instants():
    with obs.tracing() as tr:
        with obs.span("outer", cat="t", track="tests"):
            with obs.span("inner", cat="t", track="tests"):
                obs.instant("tick", track="tests")
    # spans record on close, instants immediately → completion order
    names = [e.name for e in tr.events()]
    assert names == ["tick", "inner", "outer"]
    tick, inner, outer = tr.events()
    assert outer.ts_us <= inner.ts_us
    assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us \
        + 1e-6
    assert tick.dur_us is None


def test_span_records_exception_and_reraises():
    with obs.tracing() as tr:
        with pytest.raises(RuntimeError):
            with obs.span("boom", cat="t"):
                raise RuntimeError("no")
    (ev,) = tr.events()
    assert "RuntimeError" in ev.args["error"]


# ---------------------------------------------------------------------------
# metrics / TraceCounter
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_roundtrip():
    reg = obs.MetricsRegistry()
    reg.counter("c_total", "help").labels(tier="ref").inc(2)
    reg.gauge("g", "help").labels().set(1.5)
    h = reg.histogram("h_seconds", "help", buckets=(0.1, 1.0,
                                                    float("inf")))
    h.labels(op="mix").observe(0.05)
    h.labels(op="mix").observe(0.5)
    parsed = obs.parse_prometheus(obs.prometheus_text(reg))
    assert parsed['c_total{tier="ref"}'] == 2.0
    assert parsed["g"] == 1.5
    assert parsed['h_seconds_bucket{op="mix",le="0.1"}'] == 1.0
    assert parsed['h_seconds_bucket{op="mix",le="+Inf"}'] == 2.0
    assert parsed['h_seconds_count{op="mix"}'] == 2.0
    assert parsed['h_seconds_sum{op="mix"}'] == pytest.approx(0.55)


def test_trace_counter_counts_traces_not_calls():
    tc = obs.TraceCounter("test_fn")
    f = tc.wrap(lambda x: x * 2)
    f(jnp.ones(3))
    f(jnp.zeros(3))          # same shape: cache hit, no tick
    assert (tc.traces, tc.retraces) == (1, 0)
    f(jnp.zeros((3, 2)))     # new shape: genuine retrace
    assert (tc.traces, tc.retraces) == (2, 1)
    assert obs.counter_value("jit_traces_total", name="test_fn") == 2.0


def test_fused_fallback_warning_is_counted():
    """The warn-once RuntimeWarning dedupes, but the labeled counter
    ticks on EVERY fallback dispatch — long-running serve processes
    keep the degradation visible after the warning is gone."""
    import warnings
    from repro.topology.ops import _warn_pallas_fallback
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _warn_pallas_fallback("obs_test_op", "fused_comm", "detail")
        _warn_pallas_fallback("obs_test_op", "fused_comm", "detail")
    assert len(caught) == 1      # warn-once
    assert obs.counter_value("mixing_fused_fallbacks_total",
                             op="obs_test_op",
                             kind="fused_comm") == 2.0


def test_ledger_and_fault_observe_adapters():
    prob, net = _problem()
    spec = _spec(K=4, faults=None)
    res = solve(prob, net, spec)
    res.ledger.observe(run="t")
    parsed = obs.parse_prometheus(obs.prometheus_text(obs.registry()))
    total = sum(v for k, v in parsed.items()
                if k.startswith("comm_wire_bytes_total"))
    assert total == float(res.ledger.total_bytes)


# ---------------------------------------------------------------------------
# flight recorder (unit)
# ---------------------------------------------------------------------------

def test_recorder_spec_validates():
    with pytest.raises(ValueError):
        obs.RecorderSpec(capacity=0)


def test_recorder_ring_buffer_wraps_oldest_first():
    rec = obs.recorder_init(obs.RecorderSpec(capacity=3))
    for k in range(5):
        rec = obs.recorder_write(rec, {
            "outer_gap_sq": float(k), "penalty": 0.0,
            "wire_bytes": 0.0, "alive_fraction": 1.0})
    rows = obs.recorder_rows(rec)
    assert rows.shape == (3, len(obs.FIELDS))
    # rounds 2,3,4 survive, oldest first
    assert rows[:, 0].tolist() == [2.0, 3.0, 4.0]
    assert obs.rows_to_dicts(rows)[0]["outer_gap_sq"] == 2.0


def test_recorder_ring_buffer_wrap_property():
    hypothesis = pytest.importorskip("hypothesis")
    given, settings = hypothesis.given, hypothesis.settings
    st = hypothesis.strategies

    @settings(max_examples=20, deadline=None)
    @given(cap=st.integers(1, 8), writes=st.integers(0, 20))
    def prop(cap, writes):
        rec = obs.recorder_init(obs.RecorderSpec(capacity=cap))
        for k in range(writes):
            rec = obs.recorder_write(rec, {
                "outer_gap_sq": 0.0, "penalty": 0.0,
                "wire_bytes": float(k), "alive_fraction": 1.0})
        rows = obs.recorder_rows(rec)
        assert rows.shape[0] == min(writes, cap)
        # round column is the contiguous tail of the write sequence
        expect = list(range(max(writes - cap, 0), writes))
        assert rows[:, 0].tolist() == [float(e) for e in expect]

    prop()


def test_wire_constants_marks_padding_invalid():
    net = make_network("ring", 6)
    W = make_mixing_op(net, **mixing_kwargs(_spec()))
    bps, valid = obs.wire_constants(W)
    assert all(isinstance(v, int) and v > 0 for v in bps.values())
    assert isinstance(valid, np.ndarray)      # host array, not traced
    sp = W.sparse
    real = (np.asarray(sp.neighbors) != np.arange(sp.n)[:, None])
    assert np.array_equal(valid.astype(bool), real)


# ---------------------------------------------------------------------------
# Perfetto export schema
# ---------------------------------------------------------------------------

def _export_doc(tr):
    return obs.export.trace_event_json(tr)


def test_exported_trace_validates(tmp_path):
    with obs.tracing() as tr:
        with obs.span("a", cat="t"):
            with obs.span("b", cat="t"):
                obs.instant("i")
    path = tmp_path / "trace.json"
    n = obs.write_trace(tr, path)
    events = obs.read_trace(path)
    assert len(events) == n
    doc = json.loads(path.read_text())
    for ev in doc["traceEvents"]:
        assert {"ph", "pid", "tid"} <= set(ev)
        assert ev["ph"] == "M" or "ts" in ev
        assert ev["pid"] == obs.TRACE_PID
        if ev["ph"] == "X":
            assert ev["dur"] >= 0


@pytest.mark.parametrize("mutate, err", [
    (lambda e: e.pop("ph"), "ph"),
    (lambda e: e.pop("tid"), "tid"),
    (lambda e: e.pop("ts"), "ts"),
    (lambda e: e.pop("dur"), "dur"),
    (lambda e: e.__setitem__("ts", float("nan")), "finite"),
])
def test_validate_trace_rejects_malformed_events(mutate, err):
    with obs.tracing() as tr:
        with obs.span("a", cat="t"):
            pass
    events = obs.trace_events(tr)
    ev = next(e for e in events if e["ph"] == "X")
    mutate(ev)
    with pytest.raises(ValueError, match=err):
        obs.validate_trace(events)


def test_validate_trace_rejects_malformed_nesting():
    # two "X" events on one track that partially overlap — impossible
    # output of a sane tracer, and exactly what nesting checks exist
    # to catch
    bad = [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0,
         "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 5.0,
         "dur": 10.0},
    ]
    with pytest.raises(ValueError, match="nest"):
        obs.validate_trace(bad)


# ---------------------------------------------------------------------------
# the bit-exactness + zero-retrace contract (reference tier)
# ---------------------------------------------------------------------------

def test_reference_solve_bitwise_identical_with_obs_on():
    prob, net = _problem()
    spec = _spec(K=6)
    base = solve(prob, net, spec)
    with obs.tracing() as tr:
        res = solve(prob, net, spec,
                    recorder=obs.RecorderSpec(capacity=16))
    assert np.array_equal(np.asarray(base.x), np.asarray(res.x))
    assert np.array_equal(np.asarray(base.y), np.asarray(res.y))
    for k in base.metrics:
        assert np.array_equal(np.asarray(base.metrics[k]),
                              np.asarray(res.metrics[k]))

    flight = res.extras["flight"]
    assert flight.shape == (spec.K, len(obs.FIELDS))
    assert flight[:, 0].tolist() == [float(k) for k in range(spec.K)]
    # in-jit cumulative wire bytes agree with the post-run ledger
    assert flight[-1, obs.FIELDS.index("wire_bytes")] \
        == float(res.ledger.total_bytes)
    assert np.all(flight[:, obs.FIELDS.index("alive_fraction")] == 1.0)

    names = {e.name for e in tr.events()}
    assert {"solve", "init_carry", "trace_compile", "chunk"} <= names
    obs.validate_trace(obs.trace_events(tr))
    # the K rounds run inside one program: no host span per round
    assert "outer_round" not in names


def test_reference_faulted_alive_fraction_matches_host_trace():
    from repro.faults import FaultSpec, lower_faults
    prob, net = _problem()
    spec = _spec(K=6, faults=FaultSpec(drop_prob=0.3, seed=1))
    res = solve(prob, net, spec, recorder=obs.RecorderSpec(capacity=8))
    flight = res.extras["flight"]
    trace = lower_faults(spec.faults, net, spec.K)
    col = flight[:, obs.FIELDS.index("alive_fraction")]
    assert float(col.mean()) == pytest.approx(trace.alive_fraction(),
                                              abs=1e-6)


def test_recorder_rejects_baseline_methods():
    # the recorder rides the dagm round carry on all three tiers now —
    # only the baseline methods (no flight instrumentation) reject it
    import dataclasses
    prob, net = _problem()
    spec = dataclasses.replace(_spec(K=4), method="ma_dbo")
    with pytest.raises(ValueError, match="method"):
        solve(prob, net, spec, recorder=obs.RecorderSpec())


# ---------------------------------------------------------------------------
# bounded resident spans (Tracer eviction)
# ---------------------------------------------------------------------------

def test_tracer_evicts_oldest_beyond_max_resident():
    tr = obs.Tracer(enabled=True, max_resident_spans=5)
    for k in range(12):
        tr.instant(f"i{k}")
    events = tr.events()
    assert len(events) == 5
    assert [e.name for e in events] == [f"i{k}" for k in range(7, 12)]
    assert tr.dropped == 7
    assert obs.counter_value("obs_dropped_spans_total") == 7.0
    tr.clear()
    assert tr.dropped == 0 and len(tr) == 0


def test_tracer_unbounded_and_validation():
    tr = obs.Tracer(enabled=True, max_resident_spans=None)
    for k in range(10):
        tr.instant(f"i{k}")
    assert len(tr) == 10 and tr.dropped == 0
    with pytest.raises(ValueError, match="max_resident_spans"):
        obs.Tracer(max_resident_spans=0)


def test_tracer_sinks_see_events_before_eviction():
    tr = obs.Tracer(enabled=True, max_resident_spans=2)
    seen = []
    tr.add_sink(seen.append)
    for k in range(6):
        tr.instant(f"i{k}")
    # the sink observed every event even though only 2 stayed resident
    assert [e.name for e in seen] == [f"i{k}" for k in range(6)]
    assert len(tr) == 2
    tr.remove_sink(seen.append)
    tr.instant("after")
    assert len(seen) == 6


# ---------------------------------------------------------------------------
# streaming exporters
# ---------------------------------------------------------------------------

def test_streaming_writer_rotates_and_segments_validate(tmp_path):
    tr = obs.Tracer(enabled=True)
    with obs.StreamingTraceWriter(tmp_path, flush_every=3,
                                  rotate_events=6, tracer=tr) as w:
        for k in range(21):
            tr.instant(f"i{k}", track=f"t{k % 2}")
            assert w.resident < 3
    assert len(w.segments) >= 3
    assert w.total_events == 21
    names = []
    for seg in w.segments:
        events = obs.read_trace(seg)   # parses AND validates
        names.extend(e["name"] for e in events if e["ph"] != "M")
    assert names == [f"i{k}" for k in range(21)]


def test_streaming_writer_valid_mid_flush(tmp_path):
    """Every flush leaves the current segment a complete, valid JSON
    document — a concurrent reader (or a crash) never sees a torn
    file."""
    tr = obs.Tracer(enabled=True)
    w = obs.StreamingTraceWriter(tmp_path, flush_every=2,
                                 rotate_events=None, tracer=tr)
    tr.instant("a")
    tr.instant("b")            # first flush
    events = obs.read_trace(w.current_segment)
    assert [e["name"] for e in events if e["ph"] != "M"] == ["a", "b"]
    tr.instant("c")
    tr.instant("d")            # second flush appends in place
    events = obs.read_trace(w.current_segment)
    assert [e["name"] for e in events if e["ph"] != "M"] \
        == ["a", "b", "c", "d"]
    w.close()
    assert len(w.segments) == 1


def test_streaming_writer_rotate_bytes_and_spans(tmp_path):
    tr = obs.Tracer(enabled=True)
    with obs.StreamingTraceWriter(tmp_path, flush_every=1,
                                  rotate_events=None, rotate_bytes=600,
                                  tracer=tr) as w:
        for k in range(8):
            with tr.span(f"s{k}", cat="t"):
                pass
    assert len(w.segments) >= 2
    for seg in w.segments:
        for ev in obs.read_trace(seg):
            if ev["ph"] == "X":
                assert ev["dur"] >= 0.0


def test_metrics_jsonl_writer_rotates_and_parses(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("c_total", "h").inc()
    reg.gauge("g", "h").set(2.0)
    with obs.MetricsJsonlWriter(tmp_path, rotate_bytes=200) as mw:
        for snap in range(5):
            n = mw.write_snapshot(reg, snapshot=snap)
            assert n == 2
    assert len(mw.segments) >= 2
    assert mw.total_records == 10
    recs = []
    for seg in mw.segments:
        recs.extend(json.loads(ln) for ln in open(seg))
    assert len(recs) == 10
    assert {r["metric"] for r in recs} == {"c_total", "g"}
    assert {r["snapshot"] for r in recs} == set(range(5))
    assert all({"kind", "labels", "value"} <= set(r) for r in recs)


# ---------------------------------------------------------------------------
# the bit-exactness + zero-retrace contract (serve tier)
# ---------------------------------------------------------------------------

def test_serve_solve_bitwise_identical_with_obs_on():
    prob, net = _problem()
    spec = _spec(K=8, tier="serve")
    base = solve(prob, net, spec)
    obs.reset_metrics()
    with obs.tracing() as tr:
        res = solve(prob, net, spec,
                    recorder=obs.RecorderSpec(capacity=8))
    assert np.array_equal(np.asarray(base.x), np.asarray(res.x))
    assert np.array_equal(np.asarray(base.y), np.asarray(res.y))
    # one fresh engine, one job, one bucket program: exactly one trace
    assert obs.counter_value("jit_traces_total",
                             name="serve_chunk") == 1.0
    flight = res.extras["flight"]
    assert flight.shape[0] == spec.K
    names = {e.name for e in tr.events()}
    assert {"engine_run", "build_chunk_fn", "chunk", "retire",
            "submit", "admit"} <= names
    obs.validate_trace(obs.trace_events(tr))


def test_serve_engine_checkpoint_span_and_flight(tmp_path):
    from repro.serve import JobSpec, ServeEngine
    cfg = _spec(K=8)
    specs = [JobSpec("quadratic", {"n": 6, "d1": 4, "d2": 8, "seed": s},
                     cfg, seed=s, job_id=f"j{s}") for s in range(2)]
    with obs.tracing() as tr:
        eng = ServeEngine(chunk_rounds=4, max_width=2,
                          checkpoint_dir=str(tmp_path),
                          flight_recorder=obs.RecorderSpec(capacity=8))
        eng.submit(specs)
        results = eng.run()
    assert eng.stats.traces == 1
    for r in results:
        assert r.flight is not None and r.flight.shape[0] == cfg.K
        # per-slot recorders: each job's rounds count independently
        assert r.flight[:, 0].tolist() == [float(k)
                                           for k in range(cfg.K)]
    names = {e.name for e in tr.events()}
    assert "checkpoint" in names
    obs.validate_trace(obs.trace_events(tr))


def test_serve_engine_rejects_non_spec_recorder():
    from repro.serve import ServeEngine
    with pytest.raises(TypeError, match="RecorderSpec"):
        ServeEngine(flight_recorder=16)


def test_serve_prebuilt_engine_recorder_mismatch():
    from repro.serve import ServeEngine
    prob, net = _problem()
    eng = ServeEngine(record_metrics=True)   # no recorder
    with pytest.raises(ValueError, match="flight_recorder"):
        solve(prob, net, _spec(K=4, tier="serve"), serve_engine=eng,
              recorder=obs.RecorderSpec())


# ---------------------------------------------------------------------------
# one clock: spans as profiler annotations, compile stages per solve
# ---------------------------------------------------------------------------

class _Annotations:
    """Stand-in for `jax.profiler.TraceAnnotation` that logs use."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kwargs):
        log = self.log
        log.append(("new", name, kwargs))

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
        return _Ann()


def test_disabled_span_creates_no_annotation(monkeypatch):
    from repro.obs import spans
    spans._install_live_hooks()
    fake = _Annotations()
    monkeypatch.setattr(spans, "_TraceAnnotation", fake)
    with obs.span("quiet", cat="t", k=1):
        pass
    assert fake.log == []
    assert obs.span("quiet") is spans._NULL_SPAN
    with obs.tracing():
        with obs.span("work", cat="t", k=1):
            with obs.span("inner", cat="t"):
                pass
    assert fake.log == [("new", "repro:work", {}), ("enter", "repro:work"),
                        ("new", "repro:inner", {}),
                        ("enter", "repro:inner"), ("exit", "repro:inner"),
                        ("exit", "repro:work")]
    # the span's args stay in obs; the annotation has none
    (_, work) = obs.tracer().events()
    assert work.name == "work" and work.args == {"k": 1}


def _count_traces():
    """A jax.monitoring listener counting jaxpr traces; returns the
    counter list (one element, mutated)."""
    import jax
    n = [0]

    def on(event, secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on)
    return n


@pytest.mark.parametrize("tier", ["reference", "serve"])
def test_solve_span_counts_compile_stages_bitwise_no_retrace(tier):
    prob, net = _problem()
    spec = _spec(K=8, tier=tier)
    base = solve(prob, net, spec)          # warms every helper program
    n = _count_traces()
    solve(prob, net, spec)
    off = n[0]
    n[0] = 0
    with obs.tracing() as tr:
        res = solve(prob, net, spec)
    assert n[0] == off                      # tracing adds no retrace
    assert np.array_equal(np.asarray(base.x), np.asarray(res.x))
    assert np.array_equal(np.asarray(base.y), np.asarray(res.y))
    (sp,) = [e for e in tr.events() if e.name == "solve"]
    assert sp.args["tier"] == tier
    if tier == "reference":
        # the bare solve() builds a fresh program per call: every call
        # traces, lowers and compiles (or loads) it
        assert sp.args["trace_s"] > 0 and sp.args["lower_s"] > 0 \
            and sp.args["backend_s"] > 0
        assert obs.counter_value("jax_compile_seconds_total",
                                 stage="trace") >= sp.args["trace_s"]
    names = {e.name for e in tr.events()}
    if tier == "serve":
        assert {"chunk", "chunk_wait", "boundary"} <= names
    obs.validate_trace(obs.trace_events(tr))


def test_serve_solve_span_first_call_compiles():
    """A fresh engine's first call traces, lowers and compiles its
    bucket program inside the solve span."""
    prob, net = _problem()
    with obs.tracing() as tr:
        solve(prob, net, _spec(K=8, tier="serve"))
    (sp,) = [e for e in tr.events() if e.name == "solve"]
    assert sp.args["trace_s"] > 0 and sp.args["lower_s"] > 0 \
        and sp.args["backend_s"] > 0


def test_reference_spans_nest_on_the_solver_track():
    prob, net = _problem()
    with obs.tracing() as tr:
        solve(prob, net, _spec(K=4))
    ev = {e.name: e for e in tr.events()}
    solve_ev = ev["solve"]
    for name in ("init_carry", "trace_compile", "chunk"):
        e = ev[name]
        assert e.track == "solver"
        assert solve_ev.ts_us <= e.ts_us
        assert e.ts_us + e.dur_us <= solve_ev.ts_us + solve_ev.dur_us + 1e-6
    # trace_compile ends where the device wait (chunk) begins
    assert ev["trace_compile"].ts_us + ev["trace_compile"].dur_us \
        <= ev["chunk"].ts_us + 1e-6


def test_sharded_solve_round_spans_bitwise():
    """The sharded tier scans its rounds on the device: one
    trace_compile then one chunk span inside solve, each with
    rounds=K, and no per-round host span (one-device mesh)."""
    import jax
    from jax.sharding import Mesh
    from repro.core import quadratic_bilevel
    from repro.solve import sharded_spec
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    prob = quadratic_bilevel(1, 3, 4, seed=0)
    spec = sharded_spec(alpha=0.05, beta=0.1, M=2, U=2, curvature=5.0,
                        K=3)
    base = solve(prob, None, spec, mesh=mesh)
    with obs.tracing() as tr:
        res = solve(prob, None, spec, mesh=mesh)
    assert np.array_equal(np.asarray(base.x), np.asarray(res.x))
    assert np.array_equal(np.asarray(base.y), np.asarray(res.y))
    events = tr.events()
    names = [e.name for e in events]
    assert not {"outer_round", "round_dispatch", "round_sync"} & set(names)
    (sp,) = [e for e in events if e.name == "solve"]
    (tc,) = [e for e in events if e.name == "trace_compile"]
    (ch,) = [e for e in events if e.name == "chunk"]
    assert tc.args["rounds"] == ch.args["rounds"] == 3
    assert tc.ts_us + tc.dur_us <= ch.ts_us + 1e-6
    for e in (tc, ch):
        assert sp.ts_us <= e.ts_us
        assert e.ts_us + e.dur_us <= sp.ts_us + sp.dur_us + 1e-6
    # make_sharded_dagm builds a fresh step per call
    assert sp.args["trace_s"] > 0 and sp.args["lower_s"] > 0 \
        and sp.args["backend_s"] > 0
    obs.validate_trace(obs.trace_events(tr))


def test_admission_loop_host_spans_and_lifecycle_instants():
    import dataclasses
    from repro.serve import JobSpec
    from repro.serve.admission import AdmissionLoop
    cfg = dataclasses.replace(_spec(K=8), tier="reference")
    specs = [JobSpec("quadratic", {"n": 6, "d1": 4, "d2": 8, "seed": s},
                     cfg, seed=s, job_id=f"j{s}", tenant="t0")
             for s in range(3)]
    loop = AdmissionLoop(chunk_rounds=4, bucket_width=2, telemetry=False)
    with obs.tracing() as tr:
        loop.submit(specs)
        loop.pump()
    events = tr.events()
    names = {e.name for e in events}
    assert {"tick", "admit_phase", "chunk", "chunk_wait", "boundary",
            "submit_locked", "build_problem"} <= names
    submits = [e for e in events if e.name == "submit"]
    assert [e.args for e in submits] == [
        {"job_id": f"j{s}", "klass": "standard", "tenant": "t0"}
        for s in range(3)]
    admits = [e for e in events if e.name == "admit"]
    assert {e.args["job_id"] for e in admits} == {"j0", "j1", "j2"}
    assert all(set(e.args) == {"job_id", "slot", "rounds", "klass"}
               for e in admits)
    assert all(e.dur_us is None for e in submits + admits)
    # no new span carries a job id or takes an instant's name
    spans = [e for e in events if e.dur_us is not None]
    assert not any("job_id" in e.args for e in spans)
    assert not {"submit", "admit"} & {e.name for e in spans}
    (locked,) = [e for e in spans if e.name == "submit_locked"]
    assert locked.args == {"jobs": 3}
    built = [e for e in spans if e.name == "build_problem"]
    assert len(built) == 3 and all(
        locked.ts_us <= e.ts_us and e.ts_us + e.dur_us
        <= locked.ts_us + locked.dur_us + 1e-6 for e in built)
    advanced = [e for e in spans if e.name == "tick" and "width" in e.args]
    # 3 jobs of 2 chunks through 2 slots: j0, j1, then j2 alone
    assert [(e.args["active"], e.args["width"]) for e in advanced] == [
        (2, 2), (2, 2), (1, 2), (1, 2)]
    waits = [e for e in spans if e.name == "chunk_wait"]
    assert len(waits) == 4 and all(
        any(t.ts_us <= w.ts_us and w.ts_us + w.dur_us
            <= t.ts_us + t.dur_us + 1e-6 for t in advanced)
        for w in waits)
    obs.validate_trace(obs.trace_events(tr))


def test_admission_loop_thread_spans_its_idle_wait():
    """The scheduler thread's wait for work is one span per idle
    stretch, however many polls it takes, so a device idle gap while
    the loop has nothing to do is named; an idle tick records
    nothing."""
    import dataclasses
    import time
    from repro.serve import JobSpec
    from repro.serve.admission import AdmissionLoop
    cfg = dataclasses.replace(_spec(K=4), tier="reference")
    spec = JobSpec("quadratic", {"n": 6, "d1": 4, "d2": 8, "seed": 0},
                   cfg, seed=0, job_id="j0")
    with obs.tracing() as tr:
        loop = AdmissionLoop(chunk_rounds=4, bucket_width=2,
                             telemetry=False, idle_wait_s=0.005)
        with loop:
            time.sleep(0.05)        # about 10 polls with nothing queued
            loop.submit(spec)
            loop.result("j0")
            time.sleep(0.05)
    events = tr.events()
    waits = [e for e in events if e.name == "idle_wait"]
    # before the submit, and after the job until stop()
    assert len(waits) == 2 and all(e.track == "admission" for e in waits)
    assert waits[0].dur_us >= 2e4           # many 5 ms polls, one span
    assert {"tick", "chunk_wait", "boundary"} <= {e.name for e in events}
    # every tick admitted or advanced: the idle polls opened none
    ticks = [e for e in events if e.name == "tick"]
    assert len(ticks) == 1 and ticks[0].args == {"active": 1, "width": 2}
    obs.validate_trace(obs.trace_events(tr))


def test_round_programs_name_the_four_phases():
    """The lowered DAGM round (reference and sharded) carries the
    inner_dgd / dihgp / outer_step / gossip scopes."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.dagm import dagm_init_carry, dagm_outer_step_c
    from repro.distributed.collectives import RingWeights
    from repro.distributed.dagm_sharded import dagm_local_round
    from repro.solve import sharded_spec
    prob, net = _problem()
    spec = _spec(K=2)
    W = make_mixing_op(net, **mixing_kwargs(spec))
    (x, y), cs = dagm_init_carry(prob, W, spec)
    text = jax.jit(lambda x, y, cs: dagm_outer_step_c(
        prob, W, spec, x, y, cs)).lower(x, y, cs).as_text(debug_info=True)
    for scope in ("inner_dgd", "dihgp", "outer_step", "gossip"):
        assert f"/{scope}/" in text, scope

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    cfg = sharded_spec(alpha=0.05, beta=0.1, M=2, U=2, curvature=5.0)
    w = RingWeights.metropolis_ring(1)
    g = lambda x, y, b: jnp.sum((y - x) ** 2)
    f = lambda x, y, b: jnp.sum(y ** 2)

    def local(x, y):
        x1, y1, _ = dagm_local_round(g, f, cfg, w, x[0], y[0], None)
        return x1[None], y1[None]
    step = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("data"),) * 2,
                             out_specs=(P("data"),) * 2, check_vma=False))
    text = step.lower(jnp.zeros((1, 3)), jnp.ones((1, 3))).as_text(
        debug_info=True)
    for scope in ("inner_dgd", "dihgp", "outer_step", "gossip"):
        assert f"/{scope}/" in text, scope
