"""Client side of `AdmissionLoop`: the 95th percentile of the traced
window's job latencies (due time to result in hand), in ms.  The tail
is reported here without a bound: intermittent multi-second stalls of
the loop make it swing between runs (PERF.md, Open questions)."""
from harness.load import quantile


def read(run):
    lat = run.window.get("latencies_s")
    if not lat:
        return None
    return 1e3 * quantile(lat, 0.95)
