"""Front end (`repro.solve.api`): mean over the window's solve() spans
of the seconds JAX spent tracing and lowering during the call (the
span's `trace_s` + `lower_s`), in ms."""


def read(run):
    calls = [s.args["trace_s"] + s.args["lower_s"] for s in run.spans
             if s.name == "solve" and "trace_s" in s.args]
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
