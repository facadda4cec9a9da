"""The precision the reference computes in, and the control's.

``F64`` leaves every array as it is; ``Precision("bfloat16")`` rounds it to
bfloat16 after each step, as a program that computed in bfloat16 would.
"""
from __future__ import annotations

import numpy as np


class Precision:
    """Rounding applied after every step: none (float64) or bfloat16."""

    def __init__(self, name: str = "float64"):
        self.name = name
        if name == "float64":
            self.dtype = None
        elif name == "bfloat16":
            import ml_dtypes

            self.dtype = ml_dtypes.bfloat16
        else:
            raise ValueError(f"unknown precision {name!r}")

    def __call__(self, a):
        a = np.asarray(a, np.float64)
        if self.dtype is None:
            return a
        return a.astype(self.dtype).astype(np.float64)


F64 = Precision()
