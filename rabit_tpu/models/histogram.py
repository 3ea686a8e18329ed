"""XGBoost-style gradient-histogram workload — the north-star benchmark
(BASELINE.json: "XGBoost gpu_hist gradient-histogram Allreduce").

In distributed tree boosting each worker bucketizes its rows into
feature bins, accumulates per-bin (grad, hess) sums, and allreduces the
histogram across workers (the reference's motivating use case,
doc/guide.md:137-143). The TPU-native design computes the local
histogram on device and reduces it over the mesh:

- ``method="pallas"``: the Pallas kernels of ``ops/pallas_kernels.py``,
  which turn the scatter into masked one-hot matmuls on the MXU (TPUs
  have no hardware scatter).
- ``method="scatter"``: ``segment_sum``, exact, on any backend.
- ``method="auto"``: ``pallas`` on a TPU, ``scatter`` elsewhere.

Two input forms. The flat form hands one (bin, grad, hess) triple per
entry. The dense form is XGBoost's dense data: ``bins`` int32 ``[n, F]``
of global ids ``f*max_bin + u`` (``max_bin = nbins // F``) with one
(grad, hess) pair per row, counted on every one of the row's F entries;
that ``bins`` has one axis more than ``grad`` is the whole signal. Both
give the same ``[nbins, 2]`` histogram.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..ops.reducers import SUM
from ..parallel.collectives import (
    shard_map, unchecked_shard_map, tree_allreduce, ring_allreduce,
    RING_MINCOUNT_DEFAULT)


def resolve_method(method: str) -> str:
    """The build method ``method="auto"`` stands for on this backend:
    the compiled Pallas kernel on a TPU, ``scatter`` elsewhere."""
    if method != "auto":
        return method
    from ..ops.pallas_kernels import pallas_available
    return "pallas" if pallas_available() else "scatter"


def _dense_features(grad, bins, nbins: int) -> int:
    """F of the dense form (``bins`` one axis more than ``grad``), or 0
    for the flat form; refuses an ``nbins`` that is not F * max_bin."""
    if bins.ndim != grad.ndim + 1:
        return 0
    nfeat = bins.shape[-1]
    if nbins % nfeat:
        raise ValueError(f"dense bins: nbins {nbins} is not a multiple of "
                         f"the {nfeat} features")
    return nfeat


def local_histogram(grad: jax.Array, hess: jax.Array, bins: jax.Array,
                    nbins: int, method: str = "auto",
                    precision: str = "high") -> jax.Array:
    """Per-worker histogram: returns [nbins, 2] with (sum_g, sum_h) per bin.

    Flat form: ``bins`` int32 [n] of flattened (feature, bucket) ids in
    [0, nbins), ``grad``/``hess`` [n]. Dense form: ``bins`` int32 [n, F]
    of global ids f*max_bin + u, max_bin = nbins // F (a ValueError
    where F does not divide nbins), ``grad``/``hess`` [n], each row's
    pair on every one of its F entries; the result is the flat form's of
    the same entries. Methods: "pallas" (MXU one-hot kernel, TPU only;
    the dense form gets its own kernel, in which each entry meets only
    its own feature's bins), "scatter" (segment_sum, exact, the dense
    form flattened), "auto" (pallas on TPU else scatter); any other
    name is a ValueError. ``precision`` selects the pallas
    accumulation: "high" (default, ~f32 accuracy) or "fast" (single
    bf16 dot, ~2e-4 rel err — an explicit perf opt-in).
    """
    method = resolve_method(method)
    if _dense_features(grad, bins, nbins):
        if method == "pallas":
            from ..ops.pallas_kernels import histogram_dense_tpu
            return histogram_dense_tpu(bins, grad, hess, nbins,
                                       precision=precision)
        grad, hess = (jnp.broadcast_to(v[:, None], bins.shape).reshape(-1)
                      for v in (grad, hess))
        bins = bins.reshape(-1)
    if method == "pallas":
        from ..ops.pallas_kernels import histogram_tpu
        return histogram_tpu(bins, grad, hess, nbins, precision=precision)
    if method == "scatter":
        return jax.ops.segment_sum(jnp.stack([grad, hess], axis=1), bins,
                                   num_segments=nbins)
    raise ValueError(f"unknown method {method!r}")


def distributed_histogram(grad, hess, bins, nbins: int, mesh: Mesh,
                          axis: str = "workers", method: str = "auto",
                          precision: str = "high") -> jax.Array:
    """Build local histograms on every mesh device and allreduce them.

    Inputs have a leading worker axis sharded over ``axis``:
    grad/hess [p, n_local], and bins [p, n_local] (flat form) or
    [p, n_local, F] (dense form: global ids f*max_bin + u with
    max_bin = nbins // F, a ValueError where F does not divide nbins;
    each row's pair counted on all F of its entries). Output [nbins, 2]
    replicated, the same for both forms of the same entries — the
    allreduced histogram every worker needs to find the best split.

    The host call is the phase ``rabit.hist.enqueue``
    (``telemetry.phase``), whose bytes are those of the three arrays
    handed; ``distributed_histogram.lower`` lowers the jitted program.
    """
    with telemetry.phase("rabit.hist.enqueue") as ph:
        if ph.live:
            # shape arithmetic: fewer Python calls than .nbytes, each of
            # which a profiler session's Python tracer charges
            ph.nbytes = sum(math.prod(a.shape) * a.dtype.itemsize
                            for a in (grad, hess, bins))
        return _distributed_histogram(grad, hess, bins, nbins, mesh, axis,
                                      method, precision)


@functools.partial(jax.jit,
                   static_argnames=("nbins", "mesh", "axis", "method",
                                    "precision"))
def _distributed_histogram(grad, hess, bins, nbins: int, mesh: Mesh,
                           axis: str = "workers", method: str = "auto",
                           precision: str = "high") -> jax.Array:
    method = resolve_method(method)
    use_ring = nbins * 2 >= RING_MINCOUNT_DEFAULT

    def per_shard(g, h, b):
        hist = local_histogram(g[0], h[0], b[0], nbins, method, precision)
        flat = hist.reshape(-1)
        red = (ring_allreduce if use_ring else tree_allreduce)(
            flat, axis, SUM)
        return red.reshape(hist.shape)

    # ring bodies need the replication checker off (ppermute chain), and
    # so does the pallas kernel (pallas_call's interpret evaluator is
    # vma-inconsistent across its trace passes); scatter over the psum
    # tree runs fully checked
    sm = unchecked_shard_map if use_ring or method == "pallas" else shard_map
    return sm(per_shard, mesh=mesh,
              in_specs=(P(axis), P(axis), P(axis)),
              out_specs=P())(grad, hess, bins)


distributed_histogram.lower = _distributed_histogram.lower


def host_histogram(grad: np.ndarray, hess: np.ndarray, bins: np.ndarray,
                   nbins: int, block_rows: int = 4096) -> np.ndarray:
    """Numpy reference (also the CPU baseline the reference library would
    feed its socket allreduce): [nbins, 2], summed in float64.

    Flat form: ``bins``, ``grad``, ``hess`` [n]. Dense form: ``bins``
    [n, F] and ``grad``, ``hess`` [n], each row's pair on all F of its
    entries, summed over blocks of ``block_rows`` rows so that no [n, F]
    float64 copy of the weights is made."""
    out = np.zeros((nbins, 2), dtype=np.float64)
    if bins.ndim == grad.ndim:
        np.add.at(out[:, 0], bins, grad.astype(np.float64))
        np.add.at(out[:, 1], bins, hess.astype(np.float64))
        return out.astype(np.float32)
    nfeat = bins.shape[-1]
    for a in range(0, len(bins), block_rows):
        b = bins[a:a + block_rows].reshape(-1)
        for c, v in enumerate((grad, hess)):
            w = np.repeat(v[a:a + block_rows].astype(np.float64), nfeat)
            out[:, c] += np.bincount(b, w, nbins)
    return out.astype(np.float32)


def make_inputs(n: int, nbins: int, p: int = 1, seed: int = 0):
    """Synthetic (grad, hess, bins) for p workers × n rows each."""
    rng = np.random.default_rng(seed)
    grad = rng.standard_normal((p, n)).astype(np.float32)
    hess = rng.random((p, n)).astype(np.float32)
    bins = rng.integers(0, nbins, size=(p, n)).astype(np.int32)
    return grad, hess, bins
