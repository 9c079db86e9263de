"""The benchmark's yardstick: cell lookup, device checks, the load
generator, the trace reduction and the correctness comparison.

Nothing here is imported by the program under test (`src/repro`), and
nothing here takes a number from it except the spans, counters and
kernel names a run records.
"""
