"""Distributed gradient-boosted decision stumps — the reference
library's motivating workload (distributed XGBoost: per-worker
histogram build + allreduce + identical split finding everywhere,
doc/guide.md:137-143) as a complete, fault-tolerant training program.

Every boosting round:
  1. each worker computes gradients/hessians of its data shard,
  2. builds a per-(feature, bucket) histogram locally,
  3. ``rabit.allreduce`` sums histograms across workers,
  4. every worker finds the SAME best split from the global histogram
     (deterministic -> no broadcast needed for the model),
  5. the model is checkpointed; killed workers respawn, reload, and
     catch up through result replay.

Training is deterministic, so the final model is bit-identical with and
without failures — the strongest possible recovery check (the test
asserts it). Runs standalone (world=1) or under the tracker:

    python -m rabit_tpu.tracker.launch -n 4 python examples/py/boosted_trees.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

if os.environ.get("RABIT_DATAPLANE") == "xla":
    # pin the backend before any computation: the config.update also
    # holds when something imported jax earlier (default cpu/gloo — set
    # JAX_PLATFORMS=tpu on a pod)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import numpy as np  # noqa: E402

import rabit_tpu as rabit  # noqa: E402

N_FEAT = 8
N_BINS = 16
LR = 0.4


def make_shard(rank: int, n: int = 2000, seed: int = 7):
    """Synthetic binary-classification shard (deterministic per rank)."""
    rng = np.random.default_rng(seed + rank)
    x = rng.random((n, N_FEAT), dtype=np.float32)
    logit = 3.0 * (x[:, 0] - 0.5) - 2.0 * (x[:, 1] - 0.5) + \
        1.0 * (x[:, 2] > 0.7)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    buckets = np.minimum((x * N_BINS).astype(np.int64), N_BINS - 1)
    return x, y, buckets


def local_histogram(g, h, buckets):
    """[N_FEAT, N_BINS, 2] of (sum_g, sum_h) — numpy's scatter-add here;
    the TPU path does the same through the Pallas kernel
    (rabit_tpu.models.histogram)."""
    hist = np.zeros((N_FEAT, N_BINS, 2), np.float64)
    for f in range(N_FEAT):
        np.add.at(hist[f, :, 0], buckets[:, f], g)
        np.add.at(hist[f, :, 1], buckets[:, f], h)
    return hist


def best_split(hist, reg_lambda=1.0, min_hess=1e-3):
    """Deterministic best (feature, bucket, w_left, w_right) by gain."""
    best = (-np.inf, 0, 0, 0.0, 0.0)
    for f in range(N_FEAT):
        gsum = hist[f, :, 0].sum()
        hsum = hist[f, :, 1].sum()
        gl = np.cumsum(hist[f, :, 0])[:-1]
        hl = np.cumsum(hist[f, :, 1])[:-1]
        gr, hr = gsum - gl, hsum - hl
        ok = (hl > min_hess) & (hr > min_hess)
        gain = np.where(
            ok,
            gl ** 2 / (hl + reg_lambda) + gr ** 2 / (hr + reg_lambda)
            - gsum ** 2 / (hsum + reg_lambda), -np.inf)
        b = int(np.argmax(gain))
        if gain[b] > best[0]:
            best = (float(gain[b]), f, b,
                    float(-gl[b] / (hl[b] + reg_lambda)),
                    float(-gr[b] / (hr[b] + reg_lambda)))
    return best[1:]


def predict_tree(buckets, tree):
    f, b, wl, wr = tree
    return np.where(buckets[:, f] <= b, wl, wr).astype(np.float64)


def main() -> None:
    rabit.init()
    rank, world = rabit.get_rank(), rabit.get_world_size()
    n_rounds = int(os.environ.get("N_ROUNDS", "10"))
    x, y, buckets = make_shard(rank)

    # resume: model is the list of stumps built so far
    version, model = rabit.load_checkpoint()
    model = model or []
    margin = np.zeros(len(y), np.float64)
    for tree in model:
        margin += LR * predict_tree(buckets, tree)

    for rnd in range(version, n_rounds):
        p = 1.0 / (1.0 + np.exp(-margin))
        g = (p - y).astype(np.float64)
        h = (p * (1.0 - p)).astype(np.float64)
        hist = local_histogram(g, h, buckets).reshape(-1)
        hist = rabit.allreduce(hist, rabit.SUM)  # the hot collective
        tree = best_split(hist.reshape(N_FEAT, N_BINS, 2))
        model.append(tree)
        margin += LR * predict_tree(buckets, tree)
        # global logloss (for the humans watching)
        p = np.clip(1.0 / (1.0 + np.exp(-margin)), 1e-9, 1 - 1e-9)
        part = np.array([-(y * np.log(p) + (1 - y) * np.log(1 - p)).sum(),
                         float(len(y))])
        tot = rabit.allreduce(part, rabit.SUM)
        if rank == 0:
            rabit.tracker_print(
                f"round {rnd}: global logloss {tot[0] / tot[1]:.5f}")
        rabit.checkpoint(model)

    # bit-identical everywhere: hash the model and verify via MAX==MIN
    digest = float(abs(hash(tuple(map(tuple, model)))) % (2 << 40))
    hi = rabit.allreduce(np.array([digest]), rabit.MAX)
    lo = rabit.allreduce(np.array([digest]), rabit.MIN)
    assert hi[0] == lo[0] == digest, "model diverged across ranks"
    if rank == 0:
        rabit.tracker_print(f"final model digest {int(digest)}")
    rabit.finalize()
    print(f"BOOST-OK rank={rank} world={world} trees={len(model)} "
          f"digest={int(digest)}")


if __name__ == "__main__":
    main()
