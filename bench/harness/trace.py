"""From a profiler trace to device metrics.

Two steps, kept apart so that the second can be tested on a small
recorded trace without a chip:

* `normalize` reads a JAX profiler ``.xplane.pb`` into plain lists:
  per device, the ops of its "XLA Ops" line as ``[name, start_ns,
  dur_ns]``; from the host, every event with a duration as ``[thread,
  name, start_ns, dur_ns]``; and the traced window, which the harness
  marks with a host annotation named `WINDOW`.
* `summarize` reduces those lists: device busy time as the union of op
  intervals, the ops that took most time, the part of the window in
  which a collective ran on a device while no other op did, and the
  device's idle gaps labelled by the innermost host event open at each
  gap's midpoint.  Every figure is a mean over the devices.

On a TPU the "XLA Ops" line nests: a `while` op spans every op of its
body.  So op time is self time (an op's duration less its children's),
and the exposed-collective test looks at leaf ops only.  An op's name
there is its whole HLO instruction; `op_name` keeps the part before
" = ".
"""
from __future__ import annotations

import bisect
import collections
import re

WINDOW = "bench:window"
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|"
    r"all-to-all)")
TOP = 10


def normalize(path: str) -> dict:
    """Read an ``.xplane.pb`` file into the plain form `summarize` takes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append((plane.name, [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [[line.name, e.name, float(e.start_ns),
                          float(e.duration_ns)]
                         for e in line.events if e.duration_ns > 0]
    devices.sort(key=lambda d: d[0])
    marks = [h for h in host if h[1] == WINDOW]
    if not marks:
        raise ValueError(f"trace {path} has no {WINDOW!r} annotation")
    t0, dur = marks[0][2], marks[0][3]
    return {"devices": [ops for _, ops in devices],
            "device_names": [name for name, _ in devices],
            "host": host, "window": [t0, t0 + dur]}


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Disjoint sorted intervals `a` minus disjoint sorted `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops) -> tuple[list[float], list[bool]]:
    """Per op (name, start, end), sorted by start then longest first:
    its duration less its nested children's, and whether it is a leaf."""
    own = [e - s for _, s, e in ops]
    leaf = [True] * len(ops)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent = stack[-1]
            own[parent] -= e - s
            leaf[parent] = False
        stack.append(i)
    return own, leaf


def _label_gaps(gaps, host) -> list[str]:
    """Name of the shortest host event covering each gap's midpoint."""
    events = sorted(((s, s + d, name) for _, name, s, d in host),
                    key=lambda ev: ev[0])
    starts = [ev[0] for ev in events]
    mids = sorted(range(len(gaps)),
                  key=lambda i: (gaps[i][0] + gaps[i][1]) / 2)
    labels = ["(no host event)"] * len(gaps)
    active: list[tuple[float, float, str]] = []
    nxt = 0
    for i in mids:
        mid = (gaps[i][0] + gaps[i][1]) / 2
        hi = bisect.bisect_right(starts, mid)
        active += events[nxt:hi]
        nxt = max(nxt, hi)
        active = [ev for ev in active if ev[1] >= mid]
        if active:
            labels[i] = min(active, key=lambda ev: ev[1] - ev[0])[2]
    return labels


def summarize(norm: dict) -> dict:
    """Device busy, top ops, exposed collectives and labelled idle gaps
    over the traced window, each a mean over the devices (seconds)."""
    t0, t1 = norm["window"]
    window_s = (t1 - t0) * 1e-9
    ndev = len(norm["devices"])
    if ndev == 0:
        raise ValueError("trace has no device with an 'XLA Ops' line")
    busy_s, exposed_s, coll_s = 0.0, 0.0, 0.0
    op_time = collections.Counter()
    gap_time = collections.Counter()
    for ops in norm["devices"]:
        ops = sorted(((op_name(name), max(s, t0), min(s + d, t1))
                      for name, s, d in ops if s + d > t0 and s < t1),
                     key=lambda op: (op[1], op[1] - op[2]))
        busy = union((s, e) for _, s, e in ops)
        busy_s += length(busy) * 1e-9
        own, leaf = self_times(ops)
        for (name, _, _), t in zip(ops, own):
            op_time[name] += t * 1e-9
        leaves = [op for op, is_leaf in zip(ops, leaf) if is_leaf]
        coll = union((s, e) for name, s, e in leaves
                     if COLLECTIVE.match(name))
        other = union((s, e) for name, s, e in leaves
                      if not COLLECTIVE.match(name))
        coll_s += length(coll) * 1e-9
        exposed_s += length(subtract(coll, other)) * 1e-9
        gaps = subtract([(t0, t1)], busy)
        for gap, label in zip(gaps, _label_gaps(gaps, norm["host"])):
            gap_time[label] += (gap[1] - gap[0]) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_s / ndev,
        "collective_s": coll_s / ndev,
        "collective_exposed_s": exposed_s / ndev,
        "device_ops": [[name, t / ndev]
                       for name, t in op_time.most_common(TOP)],
        "idle_gaps": [[name, t / ndev]
                      for name, t in gap_time.most_common(TOP)],
        "devices": ndev,
    }
