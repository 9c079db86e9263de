"""Roofline report (§Roofline of EXPERIMENTS.md), two sections:

1. Dry-run sweep summary — reads the JSON produced by `python -m
   repro.launch.dryrun --all --accounting --out dryrun_singlepod.json`
   and emits per-(arch × shape) roofline terms, dominant bottleneck,
   and the useful-compute ratio.  The expensive compiles live in the
   dry-run so the benchmark suite stays fast; if the JSON is missing a
   note row is emitted instead.

2. Comm-fused mixing-kernel roofline — `mixing_traffic_model` counts
   the HBM stripe traversals of one compressed gossip step
   (compress→mix→decompress of an (n, d) state) on the XLA compose
   path vs the fused Pallas kernels, and the benchmark times both paths
   at representative shapes.  The model is what the ISSUE's ≥ 2.5×
   HBM-traffic-reduction acceptance reads; the measured wall-clock
   validates in interpret mode on CPU and runs the compiled kernels on
   a TPU: the fused tier takes its interpret flag from
   `repro.kernels.ops.pallas_interpret()`, which the platform decides.
   The modeled HBM times use the v5e peak from `repro.launch.mesh`.

Traversal accounting (one traversal = n·d·itemsize bytes through HBM):

  unfused, no EF (9): quant-params read; roundtrip read + write ŷ;
    mix read ŷ + write Wŷ; self-term correction read y, ŷ, Wŷ + write.
  unfused, EF (15): the above plus residual read y/hat + write src,
    params/roundtrip on src, payload read hat/q + write, hat update.
  fused, no EF (3): fused min/max read (no stripe write) + kernel
    read y + write out.
  fused, EF (6): fused residual min/max reads y, hat + kernel reads
    y, hat and writes out, payload.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from repro.launch.mesh import (PRODUCTION_DEVICE_KIND, chip_peaks,
                               roofline_terms)

from .common import Row, timed

SMOKE_AWARE = True   # genuine cheap smoke tier (benchmarks.run contract)

HBM_BW = chip_peaks(PRODUCTION_DEVICE_KIND)["hbm_bw"]
DEFAULT_PATH = os.path.join(os.path.dirname(__file__), "..",
                            "dryrun_singlepod.json")

# HBM stripe traversals per gossip step — see module docstring
TRAVERSALS = {
    "unfused": {False: 9, True: 15},
    "fused": {False: 3, True: 6},
}


def mixing_traffic_model(n: int, d: int, *, ef: bool = False,
                         itemsize: int = 4) -> dict:
    """Modeled HBM bytes of one compress→mix→decompress gossip of an
    (n, d) state: XLA compose path vs the comm-fused Pallas kernel."""
    stripe = float(n) * d * itemsize
    unfused = TRAVERSALS["unfused"][ef] * stripe
    fused = TRAVERSALS["fused"][ef] * stripe
    return {
        "stripe_bytes": stripe,
        "unfused_bytes": unfused,
        "fused_bytes": fused,
        "traffic_reduction": round(unfused / fused, 2),
        "unfused_hbm_s": unfused / HBM_BW,
        "fused_hbm_s": fused / HBM_BW,
    }


def rows_from_record(r: dict) -> Row | None:
    if r.get("skip_reason"):
        return Row(f"roofline/{r['arch']}/{r['shape']}", 0.0,
                   {"skipped": r["skip_reason"][:60]})
    if not r.get("ok"):
        return Row(f"roofline/{r['arch']}/{r['shape']}", 0.0,
                   {"FAILED": r.get("error", "?")[:80]})
    flops = r.get("flops_corrected") or r.get("flops", 0.0)
    byts = r.get("bytes_corrected") or r.get("hbm_bytes_accessed", 0.0)
    coll = r.get("collective_bytes_corrected") or \
        sum(r.get("collective_bytes", {}).values())
    terms = roofline_terms(flops, byts, coll)
    bottleneck = terms.pop("bottleneck")
    return Row(f"roofline/{r['arch']}/{r['shape']}", 0.0, {
        **{k: f"{v*1e3:.2f}ms" for k, v in terms.items()},
        "bottleneck": bottleneck,
        "model_flops_per_chip": f"{r.get('model_flops_per_chip', 0):.3g}",
        "useful_ratio": f"{r.get('useful_ratio', 0):.3f}",
        "mem_per_dev_GB": f"{r.get('peak_memory_per_device', 0)/1e9:.2f}",
    })


def _mixing_kernel_rows(budget: str) -> list[Row]:
    """Fused vs unfused compressed-gossip rows: modeled HBM bytes (the
    3-traversals→1 claim, per stripe pass of the kernel) + measured
    wall-clock for both paths."""
    from repro.comm import channel_init
    from repro.kernels import ops as kops
    from repro.topology import make_network
    from repro.topology.ops import make_mixing_op

    interp = kops.pallas_interpret()
    shapes = {"smoke": [(16, 512)],
              "small": [(64, 4096), (256, 4096)],
              "full": [(64, 4096), (256, 4096), (256, 16384)]}
    iters = {"smoke": 3, "small": 20, "full": 50}
    rows = []
    for n, d in shapes.get(budget, shapes["small"]):
        net = make_network("circulant", n, offsets=(1, 2))
        y = jax.random.normal(jax.random.PRNGKey(n + d), (n, d),
                              jnp.float32)
        for spec in ("int8", "int8+ef"):
            ef = spec.endswith("+ef")
            model = mixing_traffic_model(n, d, ef=ef)
            tag = f"roofline/mixing/n{n}_d{d}/{spec}"
            xla_op = make_mixing_op(net, backend="circulant", comm=spec)
            st0 = channel_init(xla_op.comm, "x", y,
                               jax.random.PRNGKey(0))
            unfused = jax.jit(lambda z, op=xla_op: op.mix_c(z, st0)[0])
            with kops.pallas_mode(True):
                fop = make_mixing_op(net, comm=spec)
                assert fop._fused_plan(y) is not None
                fused = jax.jit(lambda z, op=fop: op.mix_c(z, st0)[0])
                _, us_un = timed(unfused, y, iters=iters[budget],
                                 warmup=1)
                _, us_fu = timed(fused, y, iters=iters[budget],
                                 warmup=1)
            common = {
                "modeled_unfused_bytes": model["unfused_bytes"],
                "modeled_fused_bytes": model["fused_bytes"],
                "traffic_reduction": model["traffic_reduction"],
                "interpret": interp,
            }
            if interp:
                common["note"] = "interpret-mode wall-clock validates" \
                    ", does not measure"
            rows.append(Row(f"{tag}/unfused", us_un, {
                **common,
                "modeled_hbm_ms": round(model["unfused_hbm_s"] * 1e3, 4),
            }))
            rows.append(Row(f"{tag}/fused", us_fu, {
                **common,
                "modeled_hbm_ms": round(model["fused_hbm_s"] * 1e3, 4),
                "speedup_vs_unfused": round(us_un / us_fu, 3),
            }))
    return rows


def run(budget: str = "small", path: str | None = None) -> list[Row]:
    rows = _mixing_kernel_rows(budget)
    path = path or DEFAULT_PATH
    if not os.path.exists(path):
        rows.append(Row("roofline/missing", 0.0, {
            "note": f"run the dry-run sweep first to produce {path}"}))
        return rows
    with open(path) as f:
        records = json.load(f)
    rows.extend(r for r in (rows_from_record(r) for r in records)
                if r is not None)
    return rows


if __name__ == "__main__":
    import sys
    for row in run(sys.argv[1] if len(sys.argv) > 1 else "small"):
        print(row.csv())
