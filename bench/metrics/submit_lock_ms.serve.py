"""Scheduler (`serve.admission.loop`): host time `AdmissionLoop.submit`
holds the loop's lock (its `submit_locked` spans, `build_problem`
included) per job submitted in the window, in ms."""


def read(run):
    spans = [s for s in run.spans
             if s.name == "submit_locked" and s.dur_us is not None]
    jobs = sum(s.args.get("jobs", 0) for s in spans)
    if not jobs:
        return None
    return sum(s.dur_us for s in spans) / jobs / 1e3
