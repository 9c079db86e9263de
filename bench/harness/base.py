"""What every traffic generator shares: the comparison of the compared
answers with the plain reference, and the control that must fail it."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from .check import Comparison, displacement_error


class GeneratorBase:
    """A generator sets up in `__init__`, runs `window(seconds)`, then in
    `release()` frees the program and keeps `picked`: the compared
    answers, as (key, x, y).  It provides `start(key)` -> (x0, y0) and
    `reference(key, dtype, precision)` -> (x, y)."""

    picked: list
    log = staticmethod(print)

    def check(self, limits: dict) -> Comparison:
        """Every picked answer against the float32 reference at
        precision "highest"."""
        cmp = Comparison(limits)
        t0 = time.perf_counter()
        for key, x, y in self.picked:
            xr, yr = jax.device_get(self.reference(key))
            x0, y0 = self.start(key)
            cmp.add("x_err", displacement_error(x, xr, x0))
            cmp.add("y_err", displacement_error(y, yr, y0))
        self.log(f"reference of {len(self.picked)} answers: "
                 f"{time.perf_counter() - t0:.3f} s")
        return cmp

    def use_control(self) -> None:
        """Put the reference computed in bfloat16 in the program's place
        (the control that `check` has to fail)."""
        self.picked = [
            (key, *jax.device_get(self.reference(
                key, dtype=jnp.bfloat16, precision="default")))
            for key, _, _ in self.picked]
