"""One run of one cell: set-up, window, trace, check, result line."""
from __future__ import annotations

import dataclasses
import glob
import sys
import time
from pathlib import Path

import numpy as np

from .spec import BENCH_DIR, Cell
from .trace import WINDOW, normalize, summarize

SEED_MASK = 0x7FFFFFFF


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def derive_seed(seed: int) -> int:
    """A 31-bit seed from any whole number (JAX keys keep 32 bits, so
    seeds 2**32 apart would otherwise make the same inputs)."""
    state = np.random.SeedSequence(int(seed) % 2**128).generate_state(1)
    return int(state[0]) & SEED_MASK


def cell_devices(chips: int, allow_cpu: bool = False) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoAccelerator(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


class CompileCounter:
    """Compile requests that the persistent cache did not serve."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.hits


def enable_cache() -> str:
    import jax
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    # every program, however quick to compile, is served from the cache
    # in later runs, so that no run compiles in its window
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric's reader may read."""
    cell: Cell
    window: dict          # the generator's window result (rounds, jobs, ...)
    trace: dict           # harness.trace.summarize of the window
    spans: list           # the program's obs events in the window
    device: dict
    chips: int


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, trace_dir: Path, bench_dir: Path = BENCH_DIR,
             benchmark: dict | None = None, allow_cpu: bool = False):
    """Run the cell once; returns (result dict, lines for stderr)."""
    import jax
    cell = Cell(name, bench_dir, benchmark)
    devs = cell_devices(cell.chips, allow_cpu)
    log(f"compile cache: {enable_cache()}")
    counter = CompileCounter()
    generator = cell.generator_class()(cell, derive_seed(seed), devs, log)

    if trace:
        from repro import obs
        tracer = obs.enable_tracing(True)
        tracer.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles0, requests0 = counter.compiles, counter.requests
    setup_s = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation(WINDOW):
        win = generator.window(seconds)
    compiles = counter.compiles - compiles0
    requests = counter.requests - requests0
    summary, spans = {}, []
    if trace:
        jax.profiler.stop_trace()
        spans = tracer.events()
        tracer.enabled = False
        t0 = time.perf_counter()
        (path,) = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
        summary = summarize(normalize(path))
        log(f"trace reduced in {time.perf_counter() - t0:.3f} s")
    device = device_record(devs)
    generator.release()
    cmp = generator.check(cell.config["limits"])
    log(f"set-up {setup_s:.3f} s, window {win['window_s']:.3f} s, "
        f"programs requested in the window {requests}, of which "
        f"compiled {compiles}")

    if trace:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        rec = RunRecord(cell, win, summary, spans, device, cell.chips)
        readers = cell.readers()
        metrics = {}
        for m in cell.per_layer:
            value = readers[m["name"]](rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": cmp.correct and generator.failed == 0,
              "attempted": generator.attempted, "failed": generator.failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = cmp.record()
    lines = cmp.lines() + [f"check failed_calls = {generator.failed} "
                           f"(limit 0)"]
    return result, lines
