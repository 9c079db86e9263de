"""Benchmark driver — one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--budget small|full]
                                            [--only fig2,fig4,...]

Prints ``name,us_per_call,derived`` CSV per row (the harness contract).
Every module runs in this one process, which holds the device: no
module may spawn a JAX child.  On the CPU the backend gets eight host
devices, so the sharded-tier rows have a ring to run on.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (bench_comm, bench_faults, bench_mixing, bench_serve,
               fig2_synthetic, fig3_real, fig4_hyperrep, fig5_fairloss,
               roofline, table1_convergence, table2_comm)

MODULES = {
    "table1": table1_convergence,
    "table2": table2_comm,
    "fig2": fig2_synthetic,
    "fig3": fig3_real,
    "fig4": fig4_hyperrep,
    "fig5": fig5_fairloss,
    "roofline": roofline,
    "mixing": bench_mixing,
    "comm": bench_comm,
    "serve": bench_serve,
    "faults": bench_faults,
}


def _smoke_aware(mod) -> bool:
    """A module declares its own cheap "smoke" tier (no JSON rewrite)
    by setting `SMOKE_AWARE = True`; the rest branch small-vs-
    everything-else, so smoke must map to small there or the cheapest
    request would run the full budget.  Derived from the module itself
    so a new benchmark cannot silently fall out of the smoke path."""
    return bool(getattr(mod, "SMOKE_AWARE", False))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", default="small",
                    choices=["smoke", "small", "full"])
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    args = ap.parse_args(argv)
    names = (args.only.split(",") if args.only else list(MODULES))
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        # read when the backend starts (the first jax computation; no
        # module computes at import), so this still takes effect here
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = MODULES.get(name)
        if mod is None:
            print(f"{name}/ERROR,0,unknown module (choose from "
                  f"{' '.join(MODULES)})")
            failures += 1
            continue
        budget = args.budget
        if budget == "smoke" and not _smoke_aware(mod):
            budget = "small"
        t0 = time.time()
        try:
            rows = mod.run(budget)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            failures += 1
            continue
        for row in rows:
            print(row.csv())
        print(f"# {name} finished in {time.time()-t0:.1f}s",
              file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
