"""Scheduler (`serve.admission.loop`): mean over the window's ticks
that advanced a bucket of its active slots over the bucket's width, as
the `tick` span records them at dispatch, in %."""


def read(run):
    ticks = [s.args for s in run.spans
             if s.name == "tick" and s.args.get("width")]
    if not ticks:
        return None
    return 100 * sum(t["active"] / t["width"] for t in ticks) / len(ticks)
