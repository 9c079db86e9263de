"""Front end (`repro.solve.api`): mean host time of solve()'s own
`trace_compile` spans, from the call to device dispatch, in ms."""


def read(run):
    spans = [s.dur_us for s in run.spans
             if s.name == "trace_compile" and s.dur_us is not None]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e3
