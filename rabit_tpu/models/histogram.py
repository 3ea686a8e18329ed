"""XGBoost-style gradient-histogram workload — the north-star benchmark
(BASELINE.json: "XGBoost gpu_hist gradient-histogram Allreduce").

In distributed tree boosting each worker bucketizes its rows into
feature bins, accumulates per-bin (grad, hess) sums, and allreduces the
histogram across workers (the reference's motivating use case,
doc/guide.md:137-143). The TPU-native design computes the local
histogram on device and reduces it over the mesh:

- ``method="matmul"``: one-hot × gradient matmul — keeps the FLOPs on
  the MXU, the right trade on TPU where matmul throughput dwarfs
  scatter throughput.
- ``method="scatter"``: ``segment_sum`` — less memory traffic for very
  large bin counts.
"""

from __future__ import annotations

import functools

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


def local_histogram(grad: jax.Array, hess: jax.Array, bins: jax.Array,
                    nbins: int, method: str = "auto",
                    precision: str = "high") -> jax.Array:
    """Per-worker histogram: returns [nbins, 2] with (sum_g, sum_h) per bin.

    ``bins`` is int32 [n] of flattened (feature, bucket) ids in
    [0, nbins). Methods: "pallas" (MXU one-hot kernel, TPU only),
    "matmul" (XLA scan of one-hot matmuls), "scatter" (segment_sum,
    exact), "auto" (pallas on TPU else scatter). ``precision`` selects
    the pallas accumulation: "high" (default, ~f32 accuracy) or "fast"
    (single bf16 dot, ~2e-4 rel err — an explicit perf opt-in).
    """
    method = resolve_method(method)
    if method == "pallas":
        from ..ops.pallas_kernels import histogram_tpu, _CHUNK
        n = grad.shape[0]
        pad = (-n) % _CHUNK
        if pad:
            bins = jnp.concatenate(
                [bins, jnp.full((pad,), nbins, bins.dtype)])
            grad = jnp.concatenate([grad, jnp.zeros((pad,), grad.dtype)])
            hess = jnp.concatenate([hess, jnp.zeros((pad,), hess.dtype)])
        return histogram_tpu(bins, grad, hess, nbins, precision=precision)
    gh = jnp.stack([grad, hess], axis=1)  # [n, 2]
    if method == "matmul":
        # Chunk rows so the one-hot stays VMEM-sized; accumulate over
        # chunks with scan (static trip count — jit-friendly). Padding
        # rows get bin id == nbins, whose one_hot row is all-zero.
        chunk = min(32768, max(1, gh.shape[0]))
        n = gh.shape[0]
        pad = (-n) % chunk
        if pad:
            bins = jnp.concatenate(
                [bins, jnp.full((pad,), nbins, bins.dtype)])
            gh = jnp.concatenate([gh, jnp.zeros((pad, 2), gh.dtype)])
        bins_c = bins.reshape(-1, chunk)
        gh_c = gh.reshape(-1, chunk, 2)

        def chunk_hist(b, g):
            onehot = jax.nn.one_hot(b, nbins, dtype=jnp.bfloat16)
            return jnp.dot(onehot.T, g.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)

        def body(acc, xs):
            return acc + chunk_hist(*xs), None

        # seed the carry with chunk 0 (not plain zeros) so it carries the
        # same varying-manual-axes as the data under a checked shard_map
        hist, _ = jax.lax.scan(
            body, chunk_hist(bins_c[0], gh_c[0]), (bins_c[1:], gh_c[1:]))
        return hist
    if method == "scatter":
        return jax.ops.segment_sum(gh, bins, num_segments=nbins)
    raise ValueError(f"unknown method {method!r}")


def distributed_histogram(grad, hess, bins, nbins: int, mesh: Mesh,
                          axis: str = "workers", method: str = "auto",
                          precision: str = "high") -> jax.Array:
    """Build local histograms on every mesh device and allreduce them.

    Inputs have a leading worker axis sharded over ``axis``:
    grad/hess [p, n_local], bins [p, n_local]. Output [nbins, 2]
    replicated — the allreduced histogram every worker needs to find the
    best split.

    The host call is the phase ``rabit.hist.enqueue``
    (``telemetry.phase``); ``distributed_histogram.lower`` lowers the
    jitted program.
    """
    with telemetry.phase("rabit.hist.enqueue"):
        return _distributed_histogram(grad, hess, bins, nbins, mesh, axis,
                                      method, precision)


@functools.partial(jax.jit,
                   static_argnames=("nbins", "mesh", "axis", "method",
                                    "precision"))
def _distributed_histogram(grad, hess, bins, nbins: int, mesh: Mesh,
                           axis: str = "workers", method: str = "auto",
                           precision: str = "high") -> jax.Array:
    use_ring = nbins * 2 >= RING_MINCOUNT_DEFAULT

    def per_shard(g, h, b):
        hist = local_histogram(g[0], h[0], b[0], nbins, method, precision)
        flat = hist.reshape(-1)
        red = (ring_allreduce if use_ring else tree_allreduce)(
            flat, axis, SUM)
        return red.reshape(hist.shape)

    # ring bodies need the replication checker off (ppermute chain), and
    # so does the pallas kernel (pallas_call's interpret evaluator is
    # vma-inconsistent across its trace passes); matmul/scatter over the
    # psum tree run fully checked
    sm = (unchecked_shard_map
          if use_ring or resolve_method(method) == "pallas"
          else shard_map)
    return sm(per_shard, mesh=mesh,
              in_specs=(P(axis), P(axis), P(axis)),
              out_specs=P())(grad, hess, bins)


distributed_histogram.lower = _distributed_histogram.lower


def host_histogram(grad: np.ndarray, hess: np.ndarray, bins: np.ndarray,
                   nbins: int) -> np.ndarray:
    """Numpy reference (also the CPU baseline the reference library would
    feed its socket allreduce): [nbins, 2]."""
    out = np.zeros((nbins, 2), dtype=np.float64)
    np.add.at(out[:, 0], bins, grad.astype(np.float64))
    np.add.at(out[:, 1], bins, hess.astype(np.float64))
    return out.astype(np.float32)


def make_inputs(n: int, nbins: int, p: int = 1, seed: int = 0):
    """Synthetic (grad, hess, bins) for p workers × n rows each."""
    rng = np.random.default_rng(seed)
    grad = rng.standard_normal((p, n)).astype(np.float32)
    hess = rng.random((p, n)).astype(np.float32)
    bins = rng.integers(0, nbins, size=(p, n)).astype(np.int32)
    return grad, hess, bins
