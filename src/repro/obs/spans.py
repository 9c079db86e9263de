"""Structured span tracer — host-side phase timing for every tier.

One `Tracer` records *spans* (named, nested intervals on a logical
track) and *instants* (zero-duration markers) with microsecond
timestamps relative to the tracer's epoch.  The schema is the Chrome /
Perfetto `trace_event` model — each finished span is one complete
("ph": "X") event with `name/cat/ts/dur/pid/tid/args` — so a recorded
run exports losslessly to a JSON that `ui.perfetto.dev` opens directly
(`repro.obs.export.write_trace`).

Tracks ("tid") are *named*: `span("chunk", track="engine")` puts the
span on the "engine" track; the exporter emits the thread-name metadata
events Perfetto uses to label them.  Host threads are not the unit —
the solver is single-threaded host-side and the interesting concurrency
axis is logical (engine vs solver vs checkpoint I/O), so tracks are
chosen by the instrumentation, not by `threading.get_ident()`.

Off by default, and disabled tracing is *free* in the sense the
bitwise-identical contract needs: `span()` returns a shared no-op
context manager after one attribute check, no event is allocated, and
nothing about the instrumented computation changes either way (spans
only ever *observe* wall clock — regression-tested in
tests/test_obs.py, where a traced `solve()` must equal the untraced one
bit-for-bit with zero extra retraces).

While a tracer is enabled, every live span also opens a
`jax.profiler.TraceAnnotation` named ``repro:<span name>`` on the
thread that runs it (no args, so the label is stable; the args stay
here).  A profiler trace therefore shows the program's spans on the
device trace's clock, and an idle gap of the device can be named by
the span the host was in.  Instants write no annotation: the profiler
drops zero-length events.

Phases that execute *inside* one `jax.jit` program (the inner DGD
steps, DIHGP, the outer step, gossip) are not host-observable; they
carry `jax.named_scope` names in the compiled program instead
(`inner_dgd`, `dihgp`, `outer_step`, `gossip`), which a device trace
attributes op by op.

Enabling tracing also registers one `jax.monitoring` listener that
adds JAX's compile-stage durations to the registry counter
`jax_compile_seconds_total{stage}` (`trace`, `lower`, `backend`; the
last includes fetches from the persistent compile cache).  A span
opened with ``compile_stages=True`` annotates itself at exit with each
stage's increase over the span (`trace_s`, `lower_s`, `backend_s`).

Usage:

    from repro import obs
    with obs.tracing():                       # or obs.enable_tracing()
        with obs.span("solve", method="dagm"):
            ...
    obs.export.write_trace(obs.tracer(), "trace.json")
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable

#: Default logical track for spans that do not name one.
DEFAULT_TRACK = "main"

#: Default cap on resident (un-exported) events per tracer.  A span is
#: ~200 bytes host-side, so the default bounds a forgotten-`write_trace`
#: long-lived engine at ~13 MB before oldest-first eviction kicks in.
DEFAULT_MAX_RESIDENT_SPANS = 65536


@dataclasses.dataclass
class SpanEvent:
    """One finished span (or instant, when `dur_us` is None)."""
    name: str
    cat: str
    ts_us: float                  # offset from the tracer epoch, µs
    dur_us: float | None          # None → instant event ("ph": "i")
    track: str = DEFAULT_TRACK
    args: dict = dataclasses.field(default_factory=dict)


class _NullSpan:
    """Shared no-op context manager returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()

#: Prefix of the profiler annotation each live span opens.
ANNOTATION_PREFIX = "repro:"

#: JAX compile events -> `jax_compile_seconds_total` stage label.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

_TraceAnnotation = None


def _on_compile_event(event: str, secs: float, **_) -> None:
    stage = COMPILE_EVENTS.get(event)
    if stage is not None:
        from .metrics import compile_seconds_counter
        compile_seconds_counter().labels(stage=stage).inc(secs)


def _install_live_hooks() -> None:
    """Once per process, on the first enabled use: bind the profiler
    annotation class and register the compile-duration listener."""
    global _TraceAnnotation
    if _TraceAnnotation is not None:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_event)
    _TraceAnnotation = jax.profiler.TraceAnnotation


def compile_seconds() -> dict[str, float]:
    """Seconds JAX has spent in each compile stage since tracing was
    first enabled (the `jax_compile_seconds_total` series)."""
    from .metrics import counter_value
    return {stage: counter_value("jax_compile_seconds_total", stage=stage)
            for stage in COMPILE_EVENTS.values()}


class _LiveSpan:
    """Context manager that records one SpanEvent on exit and holds a
    `repro:<name>` profiler annotation open while it runs."""

    __slots__ = ("tracer", "name", "cat", "track", "args",
                 "compile_stages", "_t0", "_annotation", "_compile0")

    def __init__(self, tracer, name, cat, track, args,
                 compile_stages=False):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.track = track
        self.args = args
        self.compile_stages = compile_stages
        self._t0 = 0.0
        self._annotation = None
        self._compile0 = None

    def __enter__(self):
        self._annotation = _TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        if self.compile_stages:
            self._compile0 = compile_seconds()
        self._t0 = time.perf_counter()
        return self

    def annotate(self, **args) -> None:
        """Attach args discovered mid-span (e.g. a retry count)."""
        self.args.update(args)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        tr = self.tracer
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        if self.compile_stages:
            for stage, secs in compile_seconds().items():
                self.args[f"{stage}_s"] = secs - self._compile0[stage]
        tr._record(SpanEvent(
            name=self.name, cat=self.cat,
            ts_us=(self._t0 - tr.epoch) * 1e6,
            dur_us=(t1 - self._t0) * 1e6,
            track=self.track, args=self.args))
        return False


class Tracer:
    """Span/instant recorder (see module docstring).

    Construction is cheap and tracers are independent — tests build
    their own; library instrumentation goes through the module-level
    default (`tracer()`) guarded by `enabled`.

    Resident memory is *bounded*: at most `max_resident_spans` events
    stay buffered, and recording past the cap evicts the oldest event
    (counted on `self.dropped` and published as the
    `obs_dropped_spans_total` registry counter).  A long-lived engine
    that never calls `write_trace` therefore plateaus instead of
    growing without bound; attach a `StreamingTraceWriter` (it
    registers itself via `add_sink`) to persist every event before it
    can be evicted.  Pass `max_resident_spans=None` to opt out."""

    def __init__(self, enabled: bool = False,
                 max_resident_spans: "int | None" =
                 DEFAULT_MAX_RESIDENT_SPANS):
        self.enabled = bool(enabled)
        self.epoch = time.perf_counter()
        self._events: collections.deque[SpanEvent] = collections.deque()
        if max_resident_spans is not None:
            max_resident_spans = int(max_resident_spans)
            if max_resident_spans < 1:
                raise ValueError(
                    f"max_resident_spans must be a positive event count "
                    f"or None for unbounded (got {max_resident_spans})")
        self.max_resident_spans = max_resident_spans
        self.dropped = 0
        self._sinks: list[Callable[[SpanEvent], None]] = []

    # -- recording ---------------------------------------------------------

    def _record(self, ev: SpanEvent) -> None:
        """Single funnel for every finished event: feed sinks first
        (streaming writers see each event exactly once, before any
        eviction can touch it), then buffer under the resident cap."""
        for sink in self._sinks:
            sink(ev)
        self._events.append(ev)
        cap = self.max_resident_spans
        if cap is not None:
            dropped = 0
            while len(self._events) > cap:
                self._events.popleft()
                dropped += 1
            if dropped:
                self.dropped += dropped
                from .metrics import dropped_spans_counter
                dropped_spans_counter().inc(dropped)

    def add_sink(self, sink: Callable[[SpanEvent], None]) -> None:
        """Subscribe `sink(event)` to every subsequently recorded
        event (used by `StreamingTraceWriter.attach`)."""
        if sink not in self._sinks:
            self._sinks.append(sink)

    def remove_sink(self, sink: Callable[[SpanEvent], None]) -> None:
        try:
            self._sinks.remove(sink)
        except ValueError:
            pass

    def span(self, name: str, cat: str = "solver",
             track: str = DEFAULT_TRACK, *, compile_stages: bool = False,
             **args):
        """Context manager timing one phase; no-op when disabled.
        `compile_stages` adds the JAX compile seconds spent during the
        span to its args at exit."""
        if not self.enabled:
            return _NULL_SPAN
        _install_live_hooks()
        return _LiveSpan(self, name, cat, track, dict(args),
                         compile_stages)

    def instant(self, name: str, cat: str = "solver",
                track: str = DEFAULT_TRACK, **args) -> None:
        """Zero-duration marker (retire, retry, quarantine, ...)."""
        if not self.enabled:
            return
        self._record(SpanEvent(
            name=name, cat=cat,
            ts_us=(time.perf_counter() - self.epoch) * 1e6,
            dur_us=None, track=track, args=dict(args)))

    def now_us(self) -> float:
        """Current timestamp on the tracer clock (µs since epoch)."""
        return (time.perf_counter() - self.epoch) * 1e6

    # -- views -------------------------------------------------------------

    def events(self) -> list[SpanEvent]:
        return list(self._events)

    def __len__(self) -> int:
        return len(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0
        self.epoch = time.perf_counter()


# ---------------------------------------------------------------------------
# Module-level default tracer (what the library instrumentation uses)
# ---------------------------------------------------------------------------

_TRACER = Tracer(enabled=False)


def tracer() -> Tracer:
    """The process-default tracer every built-in span goes through."""
    return _TRACER


def enable_tracing(enabled: bool = True) -> Tracer:
    if enabled:
        _install_live_hooks()
    _TRACER.enabled = bool(enabled)
    return _TRACER


@contextlib.contextmanager
def tracing(enabled: bool = True):
    """Scoped enable/disable of the default tracer."""
    prev = _TRACER.enabled
    if enabled:
        _install_live_hooks()
    _TRACER.enabled = bool(enabled)
    try:
        yield _TRACER
    finally:
        _TRACER.enabled = prev


def span(name: str, cat: str = "solver", track: str = DEFAULT_TRACK,
         **args):
    return _TRACER.span(name, cat, track, **args)


def instant(name: str, cat: str = "solver", track: str = DEFAULT_TRACK,
            **args) -> None:
    _TRACER.instant(name, cat, track, **args)
