"""Plain references of what the entries compute, in float64 numpy.

They import nothing of the program and take nothing it made except the
inputs, which the harness generated from the seed.
"""

from __future__ import annotations

import numpy as np


def histogram(bins: np.ndarray, grad: np.ndarray, hess: np.ndarray,
              nbins: int, block_rows: int = 4096) -> np.ndarray:
    """[nbins, 2] float64 (sum of grad, sum of hess) per bin.

    Entries form: ``bins``, ``grad`` and ``hess`` of one shape, one weight
    per entry. Dense form: ``bins`` [..., n, F] and ``grad``, ``hess``
    [..., n], each row's weights on every one of its F entries; summed
    over blocks of ``block_rows`` rows, so that no [n, F] float64 copy
    of the weights is ever made."""
    if bins.ndim == grad.ndim:
        bins = bins.reshape(-1)
        return np.stack([
            np.bincount(bins, grad.reshape(-1).astype(np.float64), nbins),
            np.bincount(bins, hess.reshape(-1).astype(np.float64), nbins),
        ], axis=1)
    f = bins.shape[-1]
    bins = bins.reshape(-1, f)
    ws = [w.reshape(-1).astype(np.float64) for w in (grad, hess)]
    out = np.zeros((nbins, 2))
    for a in range(0, len(bins), block_rows):
        b = bins[a:a + block_rows].reshape(-1)
        for c, w in enumerate(ws):
            out[:, c] += np.bincount(
                b, np.repeat(w[a:a + block_rows], f), nbins)
    return out


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
