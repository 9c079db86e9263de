"""Scheduler (`serve.admission.loop`): 95th percentile over the window's
jobs of the loop's `admit` instant minus its `submit` instant, in ms."""
from harness.load import quantile


def read(run):
    ids = set(run.window.get("job_ids", ()))
    first = {}
    for ev in run.spans:
        job = ev.args.get("job_id")
        if job in ids and ev.name in ("submit", "admit"):
            first.setdefault((ev.name, job), ev.ts_us)
    waits = [first[("admit", j)] - first[("submit", j)] for j in ids
             if ("admit", j) in first and ("submit", j) in first]
    if not waits:
        return None
    return quantile(waits, 0.95) / 1e3
