"""Front end (`repro.solve.api`): mean over the window's solve() spans
of the seconds JAX spent compiling or loading from the persistent
compile cache during the call (the span's `backend_s`), in ms."""


def read(run):
    calls = [s.args["backend_s"] for s in run.spans
             if s.name == "solve" and "backend_s" in s.args]
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
