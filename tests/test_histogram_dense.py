"""The dense histogram form (XGBoost's dense data: bins [p, n, F] of
global ids f*max_bin + u, one (grad, hess) pair per row) against the
float64 numpy reference, on seeded random data, through every build
method, on one rank and on four.

The row count is not a multiple of the dense kernel's row chunk and the
feature count not a multiple of its feature tile, so both partial blocks
are exercised. Tolerances are relative to the largest bin sum:

- TIGHT (5e-5): float32 accumulation. ``scatter`` reads ~1e-7 and the
  Pallas kernel at ``precision="high"`` (hi/lo bf16 split) ~3e-6; the
  Pallas kernel at ``"fast"`` (one bf16 rounding of each weight) reads
  ~2e-3 and must fail it, so a path that silently computed in bf16
  would be caught.
- BF16 (1e-2): the Pallas kernel at ``"fast"``, which rounds each
  weight to bf16 once.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rabit_tpu.models import histogram as H
from rabit_tpu.ops import pallas_kernels as PK
from rabit_tpu.parallel import make_mesh
from rabit_tpu.parallel.collectives import shard_over

TIGHT, BF16 = 5e-5, 1e-2
N, F, MAX_BIN = PK._DENSE_CHUNK + 52, PK._DENSE_FTILE + 72, 256

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 virtual devices")


def _dense(p, n=N, nfeat=F, max_bin=MAX_BIN, seed=0):
    """grad, hess [p, n] f32 and bins [p, n, F] int32 of global ids."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, max_bin, (p, n, nfeat))
    bins = (np.arange(nfeat) * max_bin + u).astype(np.int32)
    grad = rng.standard_normal((p, n)).astype(np.float32)
    hess = rng.random((p, n)).astype(np.float32)
    return grad, hess, bins


def _want(grad, hess, bins, nbins):
    return sum(H.host_histogram(grad[i], hess[i], bins[i], nbins)
               .astype(np.float64) for i in range(len(grad)))


def _rel_err(got, want):
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    return max(np.abs(got[:, c] - want[:, c]).max() / np.abs(want[:, c]).max()
               for c in range(2))


def _run(mesh, grad, hess, bins, nbins, method, precision="high"):
    return H.distributed_histogram(
        shard_over(mesh, grad), shard_over(mesh, hess),
        shard_over(mesh, bins), nbins, mesh, "workers", method,
        precision=precision)


@pytest.mark.parametrize("p", [1, 4])
@pytest.mark.parametrize("precision", ["high", "fast"])
@pytest.mark.parametrize("method", ["scatter", "pallas"])
def test_dense_matches_reference(monkeypatch, method, precision, p):
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    grad, hess, bins = _dense(p, seed=p)
    nbins = F * MAX_BIN
    err = _rel_err(_run(make_mesh(p), grad, hess, bins, nbins, method,
                        precision), _want(grad, hess, bins, nbins))
    bf16 = method == "pallas" and precision == "fast"
    assert err <= (BF16 if bf16 else TIGHT), err
    if method == "pallas" and precision == "fast":
        assert err > TIGHT, "fast must fail the tolerance high meets"


@pytest.mark.parametrize("method", ["scatter", "pallas"])
def test_dense_equals_flat(monkeypatch, method):
    """The same entries in both forms give the same histogram: bins
    reshaped to [p, n*F], each row's weights on every one of its
    entries."""
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    p, n, nfeat = 2, 700, 40
    grad, hess, bins = _dense(p, n, nfeat, seed=7)
    nbins = nfeat * MAX_BIN
    mesh = make_mesh(p)
    dense = np.asarray(_run(mesh, grad, hess, bins, nbins, method))
    flat = np.asarray(_run(
        mesh, np.repeat(grad, nfeat, axis=1), np.repeat(hess, nfeat, axis=1),
        bins.reshape(p, -1), nbins, method))
    assert _rel_err(dense, flat.astype(np.float64)) <= TIGHT


def test_dense_uneven_bins(monkeypatch):
    """max_bin that is no multiple of 128 (three hi groups, the last one
    partly past max_bin) and fewer features than one tile."""
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    grad, hess, bins = _dense(1, 300, 5, max_bin=300, seed=3)
    nbins = 5 * 300
    got = PK.histogram_dense_tpu(jnp.asarray(bins[0]), jnp.asarray(grad[0]),
                                 jnp.asarray(hess[0]), nbins)
    assert _rel_err(got, _want(grad, hess, bins, nbins)) <= TIGHT


@pytest.mark.parametrize("method", ["scatter", "pallas"])
def test_nbins_not_a_multiple_of_features_raises(monkeypatch, method):
    monkeypatch.setenv("RABIT_PALLAS_INTERPRET", "1")
    grad, hess, bins = _dense(1, 64, 6, seed=1)
    with pytest.raises(ValueError, match="not a multiple"):
        _run(make_mesh(1), grad, hess, bins, 6 * MAX_BIN + 1, method)
    with pytest.raises(ValueError, match="not a multiple"):
        H.local_histogram(jnp.asarray(grad[0]), jnp.asarray(hess[0]),
                          jnp.asarray(bins[0]), 6 * MAX_BIN + 1, method)


def test_host_histogram_dense_is_flat_in_blocks():
    """The reference's dense form equals its flat form of the same
    entries, in one block of rows or in many."""
    grad, hess, bins = _dense(1, 1000, 12, seed=9)
    nbins = 12 * MAX_BIN
    flat = H.host_histogram(np.repeat(grad[0], 12), np.repeat(hess[0], 12),
                            bins[0].reshape(-1), nbins)
    for block_rows in (4096, 64, 7):
        got = H.host_histogram(grad[0], hess[0], bins[0], nbins,
                               block_rows=block_rows)
        np.testing.assert_allclose(got, flat, rtol=1e-6, atol=1e-6)
