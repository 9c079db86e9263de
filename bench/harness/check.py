"""The numbers that decide `correct`, each printed beside its limit."""
from __future__ import annotations

import numpy as np


def displacement_error(out, ref, start) -> float:
    """‖out − ref‖ / ‖ref − start‖: the gap to the reference as a share
    of how far the reference moved.  A run that returns its start reads
    1; one that matches the reference reads 0."""
    out, ref, start = (np.asarray(a, np.float64) for a in (out, ref, start))
    moved = np.linalg.norm(ref - start)
    if not np.isfinite(out).all():
        return float("inf")
    return float(np.linalg.norm(out - ref) / max(moved, 1e-30))


class Comparison:
    """Named numbers with their limits; `correct` when every number is
    finite and at most its limit."""

    def __init__(self, limits: dict):
        self.limits = dict(limits)
        self.values: dict[str, float] = {}

    def add(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for compared number {name!r}")
        self.values[name] = max(self.values.get(name, -np.inf),
                                float(value))

    @property
    def correct(self) -> bool:
        return bool(self.values) and set(self.values) == set(self.limits) \
            and all(np.isfinite(v) and v <= self.limits[k]
                    for k, v in self.values.items())

    def record(self) -> dict:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in self.values.items()}

    def lines(self) -> list[str]:
        return [f"check {k} = {v!r} (limit {self.limits[k]!r})"
                for k, v in self.values.items()]
