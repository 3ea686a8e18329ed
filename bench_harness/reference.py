"""Plain references of what the entries compute, in float64 numpy.

They import nothing of the program and take nothing it made except the
inputs, which the harness generated from the seed.
"""

from __future__ import annotations

import numpy as np


def histogram(bins: np.ndarray, grad: np.ndarray, hess: np.ndarray,
              nbins: int) -> np.ndarray:
    """[nbins, 2] float64 (sum of grad, sum of hess) per bin."""
    bins = bins.reshape(-1)
    return np.stack([
        np.bincount(bins, grad.reshape(-1).astype(np.float64), nbins),
        np.bincount(bins, hess.reshape(-1).astype(np.float64), nbins),
    ], axis=1)


def allreduce_sum(shards: list) -> np.ndarray:
    """Elementwise float64 sum of every rank's payload."""
    out = np.zeros(shards[0].shape, np.float64)
    for s in shards:
        out += s
    return out


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Widest gap over the largest magnitude: max|got - want| / max|want|.
    NaN or a wrong shape reads as infinitely wrong."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
