"""Production mesh factory and the chip peak table.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 slice).
Multi-pod: (pod=2, data=16, model=16) = 512 chips, where the "pod" axis
crosses the DCN/ICI boundary.  Defined as a *function* so importing this
module never touches jax device state (the dry-run sets
--xla_force_host_platform_device_count=512 before any jax import).

Every mesh is built with Auto axes: the programs here place data with
`with_sharding_constraint` / `NamedSharding` and leave propagation to
the partitioner, which `jax.make_mesh`'s default Explicit axes reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# Published per-chip peaks, keyed by `jax.Device.device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
# architecture page).  Every figure is per chip:
#   peak_flops_bf16  dense bf16 FLOP/s          (197 TFLOP/s)
#   peak_ops_int8    int8 OP/s                  (393 TOP/s)
#   hbm_bytes        HBM capacity, bytes        (16 GB)
#   hbm_bw           HBM bandwidth, bytes/s     (819 GB/s)
#   ici_bw           chip-to-chip interconnect bandwidth, bytes/s, all
#                    links of the chip together (1,600 Gbit/s)
CHIP_PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "peak_ops_int8": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,
        "ici_bw": 1600e9 / 8,
    },
}

#: The chip the production meshes above are made of (v5e).
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Per-chip peaks for `device_kind`; a chip not in the table is an
    error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}") from None


def roofline_terms(flops: float, hbm_bytes: float,
                   ici_bytes: float) -> dict:
    """Per-chip lower bounds in seconds of one program on the
    production chip: its FLOPs over the bf16 peak, its HBM bytes over
    HBM bandwidth and its collective bytes over the chip's ICI
    bandwidth, plus the name of the largest ("bottleneck")."""
    peaks = chip_peaks(PRODUCTION_DEVICE_KIND)
    terms = {"compute_s": flops / peaks["peak_flops_bf16"],
             "memory_s": hbm_bytes / peaks["hbm_bw"],
             "collective_s": ici_bytes / peaks["ici_bw"]}
    terms["bottleneck"] = max(terms, key=terms.get)
    return terms


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices do not split into model={model}")
    return _auto_mesh((n // model, model), ("data", "model"))
