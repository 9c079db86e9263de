"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the cell's chips.
The cell (an entry of BENCHMARK.json's `workloads`) names a
configuration and a traffic mix, each found by name under `bench/`
(see `harness.spec`).  The run makes its data from the seed, warms up
(set-up), measures for `--seconds`, then checks what the timed path
produced against the plain reference.  With ``--trace 0`` the result
holds the cell's end-to-end metrics; with ``--trace 1`` a profiler trace
of the window gives its per-layer metrics.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with ``--trace 1`` also
`breakdown`, and last `checks`: every compared number with its limit.
The same numbers end standard error.  Without an accelerator, or with
fewer chips than the cell asks for, the run exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness.runner import NoAccelerator, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; no result",
              file=sys.stderr)
        return 3
    trace_dir = ROOT / ".bench_trace"
    try:
        result, lines = run_cell(args.workload, seed=args.seed,
                                 seconds=args.seconds,
                                 trace=bool(args.trace),
                                 t_start=T_START, trace_dir=trace_dir)
    except NoAccelerator as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
