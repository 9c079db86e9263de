"""Scheduler (`serve.admission.loop`): mean over the window's ticks
that advanced a bucket of the `tick` span's host time, its duration
less the `chunk_wait` spans (waiting for the device) inside it, in ms."""
import bisect


def read(run):
    ticks = [s for s in run.spans
             if s.name == "tick" and "width" in s.args]
    waits = sorted((s.ts_us, s.dur_us) for s in run.spans
                   if s.name == "chunk_wait" and s.dur_us is not None)
    if not ticks:
        return None
    starts = [ts for ts, _ in waits]
    host = []
    for t in ticks:
        lo = bisect.bisect_left(starts, t.ts_us)
        hi = bisect.bisect_right(starts, t.ts_us + t.dur_us)
        host.append(t.dur_us - sum(d for _, d in waits[lo:hi]))
    return sum(host) / len(host) / 1e3
