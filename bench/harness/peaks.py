"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A copy of `repro.launch.mesh.CHIP_PEAKS`, kept with the benchmark so
that a change to the program cannot move the yardstick.  Source: Google
Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
