"""Round (`core.dagm`, `distributed.dagm_sharded`): device self time of
the ops under the `inner_dgd` named scope per round in the traced window,
mean over the chips, in ms."""
from harness.scopes import device_ms


def read(run):
    return device_ms(run, "inner_dgd")
