"""The plain reference: float64 host arithmetic, independent of the program.

An answer ``x`` of ``A x = b`` is judged by its normwise backward error
``eta = ||b - A x|| / (||A||_inf ||x|| + ||b||)`` and its relative
residual ``||b - A x|| / ||b||``, both in float64 with this module's own
DIA product on the host.
"""
from __future__ import annotations

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)


def dia_matvec(offsets, bands: np.ndarray, x: np.ndarray) -> np.ndarray:
    """``y[i] = sum_k bands[k, i] x[i + offsets[k]]`` in the dtype given."""
    n = x.shape[-1]
    y = np.zeros_like(x)
    for k, off in enumerate(offsets):
        lo, hi = max(0, -off), min(n, n - off)
        y[lo:hi] += bands[k, lo:hi] * x[lo + off:hi + off]
    return y


class Operator:
    """A DIA operator held on the host in float64, with ``||A||_inf``."""

    def __init__(self, offsets, bands):
        self.offsets = tuple(int(o) for o in offsets)
        self.bands = np.asarray(bands, np.float64)
        self.inf_norm = float(np.abs(self.bands).sum(axis=0).max())

    def residuals(self, b, x) -> tuple:
        """``(relative residual, backward error)`` of ``x``, float64."""
        b64 = np.asarray(b, np.float64)
        x64 = np.asarray(x, np.float64)
        if not np.all(np.isfinite(x64)):
            return float("inf"), float("inf")
        rn = float(np.linalg.norm(b64 - dia_matvec(self.offsets, self.bands,
                                                   x64)))
        bn = float(np.linalg.norm(b64))
        return rn / bn, rn / (self.inf_norm * float(np.linalg.norm(x64)) + bn)

