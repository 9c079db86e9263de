"""Round (`core.dagm`, `distributed.dagm_sharded`): the configuration's
analytic FLOPs per round times the rounds in the traced window, over the
window times the chips times the bf16 peak, in %."""
from harness.peaks import peaks


def read(run):
    rounds = run.window.get("rounds")
    if not rounds or not run.trace:
        return None
    peak = peaks(run.device["kind"])["flops_bf16"]
    flops = run.cell.config["flops_per_round"] * rounds
    return 100 * flops / (run.trace["window_s"] * run.chips * peak)
