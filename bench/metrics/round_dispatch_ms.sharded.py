"""Sharded round loop (`repro.solve.api`): mean host time of the
window's `round_dispatch` spans (the round's coefficients put on the
devices and the step enqueued), in ms.  The first round of each call
is left out: its dispatch traces and loads the call's program, which
`front_trace_ms.solve` and `front_load_ms.solve` read."""


def read(run):
    firsts = [(s.ts_us, s.ts_us + s.dur_us) for s in run.spans
              if s.name == "outer_round" and s.dur_us is not None
              and s.args.get("round") == 0]
    rounds = [s.dur_us for s in run.spans
              if s.name == "round_dispatch" and s.dur_us is not None
              and not any(t0 <= s.ts_us <= t1 for t0, t1 in firsts)]
    if not rounds:
        return None
    return sum(rounds) / len(rounds) / 1e3
