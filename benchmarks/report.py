"""Markdown report generator + benchmark regression gate.

Report mode (legacy positional usage) — EXPERIMENTS.md §Dry-run /
§Roofline tables:

    PYTHONPATH=src python -m benchmarks.report dryrun_singlepod.json \
        [dryrun_multipod.json]

Gate mode — rerun bench modules and fail (exit 1) on regression
against the checked-in ``benchmarks/results/bench_<name>.json``
baselines:

    PYTHONPATH=src python -m benchmarks.report --gate faults[,serve] \
        [--budget small] [--wall-tolerance 25]

Gate rules, per row (matched to its baseline row by ``name``):

  * every derived key containing "retrace" must be 0 in the fresh run
    (the zero-retrace acceptance every bench row carries);
  * ``us_per_call`` may not exceed baseline × ``--wall-tolerance``
    (slower-only: getting faster never fails the gate — wall clock on
    a shared box needs a generous multiplicative tolerance);
  * every derived key containing "bytes" must be *exactly* equal —
    the byte ledgers are deterministic accounting, not measurements,
    so any drift is a real protocol change;
  * every derived key containing "latency", "_p50" or "_p99" is a
    wall-clock-like measurement (the serve SLO row's Poisson p50/p99):
    slower-only, bounded by the same multiplicative
    ``--wall-tolerance``;
  * every baseline row must still be produced (coverage cannot
    silently shrink).

``--budget`` must match the budget the baseline was recorded at
(``small`` for the checked-in files).  Modules rewrite their results
JSON when rerun at that budget, so the gate snapshots the baseline
bytes first and restores them after — a gate run leaves the tree
clean.
"""
from __future__ import annotations

import json
import os
import sys

from repro.launch.mesh import roofline_terms


def _terms(r: dict) -> dict:
    flops = r.get("flops_corrected") or r.get("flops", 0.0)
    byts = r.get("bytes_corrected") or r.get("hbm_bytes_accessed", 0.0)
    coll = r.get("collective_bytes_corrected") or \
        sum(r.get("collective_bytes", {}).values())
    return roofline_terms(flops, byts, coll)


def _ms(x: float) -> str:
    return f"{x*1e3:.2f}"


def _lever(r: dict, bound: str) -> str:
    """One sentence: what would move the dominant term down (per brief)."""
    moe = "moe" in r["arch"] or "mixtral" in r["arch"]
    shape = r["shape"]
    if moe and shape in ("train_4k", "prefill_32k"):
        return "group-local routing kills the replicated dispatch (§Perf-1/2)"
    if shape == "train_4k":
        if bound == "collective_s":
            return "overlap TP all-reduce with matmuls; wider microbatches"
        return "fewer grad-accum microbatches (fewer remat re-reads) within HBM"
    if shape == "prefill_32k":
        return "flash-attention kernel (kernels/flash_attention) + fused TP collectives"
    if shape == "decode_32k":
        return "quantize KV cache bf16→int8; batch more requests per step"
    if shape == "long_500k":
        return "shorter SWA window or state-space arch; batch>1 decode"
    return "—"


def roofline_table(records: list[dict]) -> str:
    lines = [
        "| arch | shape | compute (ms) | memory (ms) | collective (ms) |"
        " bound | MODEL_FLOPs/chip | useful ratio | mem/dev GB |"
        " dominant-term lever |",
        "|---|---|---:|---:|---:|---|---:|---:|---:|---|",
    ]
    for r in records:
        key = f"| {r['arch']} | {r['shape']} "
        if r.get("skip_reason"):
            lines.append(key + f"| — | — | — | SKIP ({r['skip_reason'][:40]}…) | — | — | — | — |")
            continue
        if not r.get("ok"):
            lines.append(key + f"| — | — | — | FAIL | — | — | — | — |")
            continue
        t = _terms(r)
        lines.append(
            key +
            f"| {_ms(t['compute_s'])} | {_ms(t['memory_s'])} "
            f"| {_ms(t['collective_s'])} | {t['bottleneck'].replace('_s','')} "
            f"| {r.get('model_flops_per_chip', 0):.3g} "
            f"| {r.get('useful_ratio', 0):.3f} "
            f"| {r.get('peak_memory_per_device', 0)/1e9:.2f} "
            f"| {_lever(r, t['bottleneck'])} |")
    return "\n".join(lines)


def multipod_table(records: list[dict]) -> str:
    lines = [
        "| arch | shape | mesh | compile (s) | mem/dev GB | coll GB | status |",
        "|---|---|---|---:|---:|---:|---|",
    ]
    for r in records:
        st = "SKIP" if r.get("skip_reason") else (
            "OK" if r.get("ok") else "FAIL")
        coll = sum(r.get("collective_bytes", {}).values()) / 1e9
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r.get('compile_s', 0):.1f} "
            f"| {r.get('peak_memory_per_device', 0)/1e9:.2f} "
            f"| {coll:.3f} | {st} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Regression gate
# ---------------------------------------------------------------------------

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _gate_row(fresh, base, tol: float) -> list[str]:
    """Failure strings for one (fresh, baseline) row pair (empty = ok).
    `base` is None for rows with no baseline (new rows gate only their
    own retrace keys)."""
    fails = []
    for k, v in fresh["derived"].items():
        if "retrace" in k and float(v) != 0.0:
            fails.append(f"{k}={v} (must be 0)")
    if base is None:
        return fails
    wall, base_wall = fresh["us_per_call"], base["us_per_call"]
    if wall > base_wall * tol:
        fails.append(f"wall {wall:.1f}us > {tol}x baseline "
                     f"{base_wall:.1f}us")
    for k, v in base["derived"].items():
        got = fresh["derived"].get(k)
        if "bytes" in k:
            if got != v:
                fails.append(f"{k}={got} != baseline {v} (byte "
                             f"ledgers must be exact)")
        elif "latency" in k or "_p50" in k or "_p99" in k:
            # measured tail latency: slower-only, like wall clock
            if got is not None and float(got) > float(v) * tol:
                fails.append(f"{k}={got} > {tol}x baseline {v}")
    return fails


def gate(names: list[str], budget: str, tol: float) -> int:
    """Rerun `names` bench modules at `budget`, compare against the
    checked-in baselines, print per-row verdicts; 1 on any failure."""
    from .run import MODULES
    bad = 0
    for name in names:
        mod = MODULES.get(name)
        path = os.path.join(RESULTS_DIR, f"bench_{name}.json")
        if mod is None or not os.path.exists(path):
            print(f"GATE FAIL {name}: "
                  + ("unknown module" if mod is None
                     else f"no baseline at {path}"))
            bad += 1
            continue
        raw = open(path, "rb").read()       # snapshot: run() rewrites it
        baseline = {r["name"]: r for r in json.loads(raw)}
        try:
            rows = [{"name": r.name, "us_per_call": r.us_per_call,
                     "derived": r.derived} for r in mod.run(budget)]
        finally:
            with open(path, "wb") as f:     # gate runs leave tree clean
                f.write(raw)
        fresh = {r["name"]: r for r in rows}
        for row in rows:
            fails = _gate_row(row, baseline.get(row["name"]), tol)
            status = "FAIL " + "; ".join(fails) if fails else "ok"
            note = "" if row["name"] in baseline else " [no baseline]"
            print(f"gate {row['name']}{note}: {status}")
            bad += bool(fails)
        for missing in sorted(set(baseline) - set(fresh)):
            print(f"gate {missing}: FAIL baseline row not produced "
                  f"(coverage shrank)")
            bad += 1
    print(f"# gate: {'FAIL' if bad else 'ok'} "
          f"({bad} failing row(s), tolerance {tol}x, budget {budget})")
    return 1 if bad else 0


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if "--gate" in argv:
        import argparse
        ap = argparse.ArgumentParser(prog="benchmarks.report")
        ap.add_argument("--gate", required=True,
                        help="comma-separated bench module names")
        ap.add_argument("--budget", default="small",
                        choices=["smoke", "small", "full"])
        ap.add_argument("--wall-tolerance", type=float, default=25.0)
        args = ap.parse_args(argv)
        return gate(args.gate.split(","), args.budget,
                    args.wall_tolerance)
    single = json.load(open(argv[0]))
    print("## Roofline (single-pod 16×16)\n")
    print(roofline_table(single))
    if len(argv) > 1:
        multi = json.load(open(argv[1]))
        print("\n## Multi-pod compile matrix (2×16×16)\n")
        print(multipod_table(multi))
    return 0


if __name__ == "__main__":
    sys.exit(main())
