"""Device time by the round's named phases.

The program names the phases of a DAGM round with `jax.named_scope`:
`inner_dgd`, `dihgp`, `outer_step`, and `gossip` wherever a mixing op
or the ring exchange is applied (`gossip` nests inside the others).
XLA keeps the scope path in each op's `op_name` metadata.  A TPU
profile holds it as the stat `STAT` of the event metadata behind each
op of a device's "XLA Ops" line (read by hand from a v5e trace), and
`ProfileData` does not show metadata stats: `metadata_stats` reads them
from the file itself.

* `normalize` adds each device op's scope path to `trace.normalize`.
* `scope_times` gives each op's self time (as `trace.summarize` counts
  it) to the innermost phase on its path, or to `UNSCOPED`: seconds,
  mean over the devices.
* `scope_seconds(run)` does both for a run's trace, which `bench/run.py`
  keeps under ``<checkout>/.bench_trace`` until the run has printed its
  line; None where there is no trace or no op carries a path.

This module is a stopgap beside `trace`: the scope path belongs in
`trace.normalize` as one more column, and `scope_s` in `summarize`.
"""
from __future__ import annotations

import collections
import glob
from pathlib import Path

from . import trace

PHASES = ("inner_dgd", "dihgp", "outer_step", "gossip")
UNSCOPED = "(unscoped)"
STAT = "tf_op"
TRACE_DIR = ".bench_trace"
SCOPE_KEY = "scope_s"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, value) of one protobuf message in buf[start:end];
    a length-delimited value is its (start, end), left unread."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def metadata_stats(buf: bytes, stat: str = STAT) -> dict[str, dict]:
    """Per device plane of a serialized XSpace, the string value of
    `stat` on each event metadata, by the metadata's (the event's) name.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and
    stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata: name
    = 2, stats = 5; XStat: metadata_id = 1, str_value = 5, ref_value = 7
    (the name of another stat metadata); XStatMetadata: name = 2."""
    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def entry_value(span):
        return next((v for f, v in _fields(buf, *span) if f == 2), None)

    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((text(v) for f, v in fields if f == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for f, v in fields:
            if f == 5 and (meta := entry_value(v)) is not None:
                m = dict(_fields(buf, *meta))
                stat_names[m.get(1, 0)] = text(m[2]) if 2 in m else ""
        want = {k for k, n in stat_names.items() if n == stat}
        paths = {}
        for f, v in fields:
            if f != 4 or not want or (meta := entry_value(v)) is None:
                continue
            ev_name, value = "", None
            for g, w in _fields(buf, *meta):
                if g == 2:
                    ev_name = text(w)
                elif g == 5:
                    st = dict(_fields(buf, *w))
                    if st.get(1) in want:
                        value = text(st[5]) if 5 in st \
                            else stat_names.get(st.get(7), "")
            if value is not None:
                paths[ev_name] = value
        out[name] = paths
    return out


def normalize(path: str) -> dict:
    """`trace.normalize` of the file, with each device op as
    ``[name, start_ns, dur_ns, scope path]`` (the path is "" where the
    op has none)."""
    norm = trace.normalize(path)
    meta = metadata_stats(Path(path).read_bytes())
    return {"window": norm["window"], "devices": [
        [op + [meta.get(name, {}).get(op[0], "")] for op in ops]
        for name, ops in zip(norm["device_names"], norm["devices"])]}


def phase_of(path: str) -> str:
    """The innermost phase named on a scope path; a transformed scope
    reads like ``transpose(jvp(outer_step))``, and a TPU profile ends
    the path with a colon."""
    for part in reversed(path.split("/")):
        name = part.rstrip(":)").rsplit("(", 1)[-1]
        if name in PHASES:
            return name
    return UNSCOPED


def scope_times(norm: dict) -> dict[str, float]:
    """Self time of the window's device ops by phase, mean over the
    devices, in seconds."""
    t0, t1 = norm["window"]
    ndev = len(norm["devices"])
    if ndev == 0:
        raise ValueError("trace has no device with an 'XLA Ops' line")
    out = collections.Counter()
    for ops in norm["devices"]:
        ops = sorted(((trace.op_name(name), max(s, t0), min(s + d, t1),
                       phase_of(path))
                      for name, s, d, path in ops if s + d > t0 and s < t1),
                     key=lambda op: (op[1], op[1] - op[2]))
        own, _ = trace.self_times([op[:3] for op in ops])
        for op, t in zip(ops, own):
            out[op[3]] += t * 1e-9
    return {name: t / ndev for name, t in out.items()}


def scope_seconds(run) -> dict[str, float] | None:
    """`scope_times` of the run's profiler trace, kept in the run's
    trace summary under `SCOPE_KEY` so the four readers parse it once.
    `RunRecord` does not carry the profile's path: it is the directory
    `bench/run.py` hands `runner.run_cell`."""
    if not run.trace:
        return None
    if SCOPE_KEY not in run.trace:
        root = Path(run.cell.bench_dir).parent / TRACE_DIR
        paths = glob.glob(str(root / "plugins/profile/*/*.xplane.pb"))
        times = {}
        if len(paths) == 1:
            try:
                times = scope_times(normalize(paths[0]))
            except (ValueError, KeyError, IndexError):
                pass        # a profile this reader cannot take: no metric
        run.trace[SCOPE_KEY] = times if set(times) - {UNSCOPED} else None
    return run.trace[SCOPE_KEY]


def device_ms(run, phase: str) -> float | None:
    """Device ms per round in `phase` over the traced window."""
    rounds = run.window.get("rounds")
    times = scope_seconds(run) if rounds else None
    if not times or phase not in times:
        return None
    return 1e3 * times[phase] / rounds
