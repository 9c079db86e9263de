"""Front end (`repro.solve.api`): share of the window's solve() calls
that ran a program kept from an earlier call, in %: the `trace_compile`
spans whose `cache` arg is `hit`, over those that carry the arg."""


def read(run):
    outcomes = [s.args["cache"] for s in run.spans
                if s.name == "trace_compile" and "cache" in s.args]
    if not outcomes:
        return None
    return 100.0 * outcomes.count("hit") / len(outcomes)
