"""Dispatch-floor-cancelling slope timing of device work.

Each dispatch+fetch pays a fixed cost regardless of payload, so a
per-call time of a short kernel measures that cost, not the device. The
methodology (shared by bench.py and tools/kernel_hw_proof.py):

- ``run_fn(k, salt)`` must run k work-iterations inside ONE jitted
  dispatch (a ``lax.fori_loop`` cycling pre-staged device inputs);
- the slope (T(k_big) - T(k_small)) / (k_big - k_small) cancels the
  fixed dispatch+fetch cost;
- ``salt`` must perturb an input every timing (fold it into the
  accumulator init), so no timing can be served by a runtime that
  memoizes (executable, inputs) -> result;
- best-of-``reps`` per point shields against host latency spikes;
- a slope where the big batch is not measurably costlier than the small
  one is noise — remeasure, and only a caller that explicitly opts in
  (``allow_noisy``, CI smoke runs) gets a value instead of an error.
"""

from __future__ import annotations

import time


def slope_time(run_fn, k_small: int, k_big: int, *, salt_base: int = 100,
               reps: int = 2, attempts: int = 3,
               allow_noisy: bool = False) -> float:
    """Seconds of true device time per work-iteration of ``run_fn``.

    ``run_fn(k, salt)`` runs k iterations in one dispatch and returns
    something numpy-coercible (coercion forces the fetch).
    """
    import numpy as np

    if attempts < 1:
        raise ValueError(f"attempts must be >= 1, got {attempts}")

    def timed(k: int, salt: int) -> float:
        np.asarray(run_fn(k, salt))          # compile + warm
        best = float("inf")
        for rep in range(reps):
            t0 = time.perf_counter()
            np.asarray(run_fn(k, salt + 1 + rep))
            best = min(best, time.perf_counter() - t0)
        return best

    for attempt in range(attempts):
        t_small = timed(k_small, salt_base + 100 * attempt)
        t_big = timed(k_big, salt_base + 10 + 100 * attempt)
        if t_big > t_small * 1.2:
            return (t_big - t_small) / (k_big - k_small)
    if allow_noisy:                           # CI smoke: quality moot
        # the diff is noise (possibly negative); publish the whole-batch
        # per-iteration mean instead — an over-estimate that still
        # includes the dispatch floor, so a noisy value can never be
        # mistaken for an absurdly fast device measurement
        import warnings
        warnings.warn(
            "slope_time: unstable measurement; returning noisy upper "
            "bound t_big/k_big (smoke-quality only)", RuntimeWarning)
        return t_big / k_big
    raise RuntimeError(
        f"slope measurement unstable after {attempts} attempts "
        f"(t{k_small}={t_small:.4f}s t{k_big}={t_big:.4f}s)")
