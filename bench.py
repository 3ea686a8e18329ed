#!/usr/bin/env python
"""Benchmark: XGBoost-style gradient-histogram allreduce on TPU.

The north-star workload (BASELINE.json): each worker builds a per-bin
(grad, hess) histogram from its rows and allreduces it across the mesh.
The reference library does this on host CPUs feeding a socket
tree/ring (test/speed_test.cc measures the collective alone); our
TPU-native path does bucketize+accumulate on the MXU and reduces over
ICI in the same XLA program.

Headline metric: gradient-pair GB/s processed end-to-end (device-resident
inputs -> replicated histogram), vs the host-CPU numpy baseline doing the
same local histogram (the compute the reference would feed its
allreduce).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"correct", "platform", "device_kind", "device_count"}. Without a TPU it
exits non-zero and prints no result; ``RABIT_BENCH_SMOKE=1`` is the
explicit CPU rehearsal at smoke sizes, and its line says
``"platform": "cpu"``. ``--record`` also writes a ``BENCH_LOCAL_*``
artifact and appends to ``benchmarks/history.jsonl``.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
_ARTIFACTS = os.path.join(_REPO, "benchmarks", "artifacts")
sys.path.insert(0, _REPO)


# Slope-measurement sizing: k iterations cycle over a pool of K_STAGE
# pre-staged datasets (i & (K_STAGE-1)); every iteration streams a full
# dataset from HBM. K_BIG must put enough device time on the clock to
# clear the per-dispatch floor even for the fastest variant.
K_SMALL, K_BIG = 32, 256
K_STAGE = 32

# CI smoke override (tests/test_bench_smoke.py): shrink every size so
# the full bench contract — staging, slope, curve, correctness check,
# the ONE JSON line — runs in seconds on the CPU backend.
if os.environ.get("RABIT_BENCH_SMOKE") == "1":
    K_SMALL, K_BIG, K_STAGE = 4, 16, 4
# run_batch cycles the pool with i & (K_STAGE - 1)
assert K_STAGE & (K_STAGE - 1) == 0, "K_STAGE must be a power of two"


def _slope_bench(fn):
    """True device time per dataset via the shared slope methodology
    (``rabit_tpu.utils.slope``): fn(K, salt) runs K dataset-iterations
    in one jitted dispatch cycling a pre-staged pool (see K_STAGE);
    the K_SMALL->K_BIG slope cancels the fixed per-dispatch cost.
    Datasets are STAGED BEFORE timing — the realistic shape anyway:
    XGBoost's gradients come from the previous round's on-device predict
    pass, and in-loop threefry generation measurably dominated the
    kernel in rounds 1-2's numbers."""
    from rabit_tpu.utils.slope import slope_time
    return slope_time(fn, K_SMALL, K_BIG)


def _write_local_artifact(payload: dict) -> None:
    """Record a run (``--record``): one timestamped BENCH_LOCAL_* file
    plus the regression-sentinel history. Never written unless asked,
    so a run leaves the checkout as it found it."""
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    doc = dict(payload, timestamp_utc=ts)
    path = os.path.join(_ARTIFACTS, f"BENCH_LOCAL_{ts}.json")
    try:
        os.makedirs(_ARTIFACTS, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {path}", file=sys.stderr, flush=True)
    except OSError as e:  # pragma: no cover - artifact is best-effort
        print(f"# artifact write failed: {e}", file=sys.stderr, flush=True)
        return
    # every real run also lands in the regression-sentinel history, so
    # tools/bench_sentinel.py trends it against prior same-config runs
    try:
        from rabit_tpu.telemetry import history
        recs = history.records_from_artifact(
            doc, source=os.path.basename(path))
        n = history.append(history.history_path(_REPO), recs)
        print(f"# appended {n} history records", file=sys.stderr,
              flush=True)
    except Exception as e:  # pragma: no cover - history is best-effort
        print(f"# history append failed: {e}", file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description="histogram allreduce bench")
    ap.add_argument("--record", action="store_true",
                    help="write a BENCH_LOCAL_* artifact and append to "
                         "benchmarks/history.jsonl")
    args = ap.parse_args()

    import jax
    import numpy as np

    smoke = os.environ.get("RABIT_BENCH_SMOKE") == "1"
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    from rabit_tpu.utils import compile_cache
    print(f"# compile cache: {compile_cache.setup(_REPO)}",
          file=sys.stderr)
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu" and not smoke:
        sys.exit(f"bench.py: no TPU (JAX found {dev0.platform}); nothing "
                 "measured. RABIT_BENCH_SMOKE=1 runs the CPU rehearsal.")

    import functools

    import jax.numpy as jnp

    from rabit_tpu.parallel import make_mesh
    from rabit_tpu.models import histogram as H
    from rabit_tpu.parallel.collectives import shard_over

    p = len(jax.devices())
    n = 1 << 14 if smoke else 1 << 21    # rows per worker
    nbins = 1024         # flattened (feature, bucket) ids
    mesh = make_mesh(p)

    @functools.partial(jax.jit, static_argnames=("nrows",))
    def gen_batch(seed, nrows):
        # K_STAGE datasets staged on-device OUTSIDE the timed region: the
        # metric is device-resident inputs -> replicated histogram, and
        # round-3 profiling showed in-loop threefry generation cost
        # 2.8 ms/dataset — half the then-published "high" time was
        # measuring the PRNG, not the workload (XGBoost's gradients come
        # from the previous round's predict pass, already resident)
        key = jax.random.PRNGKey(seed)
        kb, kg, kh = jax.random.split(key, 3)
        b = jax.random.randint(kb, (K_STAGE, p, nrows), 0, nbins,
                               jnp.int32)
        g = jax.random.normal(kg, (K_STAGE, p, nrows), jnp.float32)
        h = jax.random.uniform(kh, (K_STAGE, p, nrows), jnp.float32)
        return b, g, h

    @functools.partial(jax.jit,
                       static_argnames=("k", "method", "prec"))
    def run_batch(data, salt, k, method, prec):
        # k iterations cycling over the staged pool, all through the
        # full distributed path (local histogram + mesh allreduce) in
        # ONE dispatch; the running sum keeps everything live. ``salt``
        # seeds the accumulator so no two timings share their inputs.
        b, g, h = data
        def one(i, acc):
            s = jnp.bitwise_and(i, K_STAGE - 1)
            return acc + H.distributed_histogram(
                g[s], h[s], b[s], nbins, mesh, "workers", method,
                precision=prec)
        return jax.lax.fori_loop(
            0, k, one, jnp.full((nbins, 2), salt * 1e-30, jnp.float32))

    on_tpu = jax.default_backend() == "tpu"
    data = jax.block_until_ready(gen_batch(7, n))
    variants = ([("pallas", "high"), ("pallas", "fast"),
                 ("scatter", "high")] if on_tpu
                else [("scatter", "high")])
    # a variant that fails fails the run: no other variant may quietly
    # take the headline's place
    results = {}
    for method, prec in variants:
        results[(method, prec)] = _slope_bench(
            lambda k, s, m=method, pr=prec: run_batch(data, s, k, m, pr))
    # headline: the library-DEFAULT path (high precision), best method
    high_only = {k: v for k, v in results.items() if k[1] == "high"}
    best_method, _ = min(high_only, key=high_only.get)
    t_dev = high_only[(best_method, "high")]

    nbytes = p * n * 12  # grad f32 + hess f32 + bins i32 per row
    dev_gbps = nbytes / t_dev / 1e9

    # bandwidth-vs-size curve for the headline variant (artifact only).
    # The main staged pool is dead from here — free it before staging
    # curve pools (the nn=1<<22 pool is 2x the main one; holding both
    # would OOM a 16 GB chip at p=8).
    del data
    curve = {}
    for nn in ((1 << 13,) if smoke else (1 << 18, 1 << 20, 1 << 22)):
        dd = jax.block_until_ready(gen_batch(7, nn))
        t = _slope_bench(
            lambda k, s, d=dd: run_batch(d, s, k, best_method, "high"))
        curve[nn] = round(p * nn * 12 / t / 1e9, 3)
        del dd

    # Host baseline: numpy histogram on one worker's rows, scaled to p
    # workers running serially on one host core-set (what the reference's
    # worker would do before its socket allreduce); min of 3 reps to
    # shield against host scheduling noise.
    grad, hess, bins = H.make_inputs(n, nbins, p=p, seed=1000)
    t_host = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        H.host_histogram(grad[0], hess[0], bins[0], nbins)
        t_host = min(t_host, (time.perf_counter() - t0) * p)
    host_gbps = nbytes / t_host / 1e9

    # correctness spot check on real (host-verified) data through the
    # same distributed path; atol follows the bf16 error model of the
    # hi/lo split (~2e-6 rel) with slack for f32 accumulation
    dev = tuple(shard_over(mesh, a) for a in (grad, hess, bins))
    got = np.asarray(H.distributed_histogram(
        dev[0], dev[1], dev[2], nbins, mesh, "workers", best_method,
        precision="high"))
    want = np.zeros((nbins, 2), np.float64)
    for i in range(p):
        want += H.host_histogram(grad[i], hess[i], bins[i], nbins)
    ok = np.allclose(got, want, rtol=1e-3,
                     atol=4e-3 * float(np.sqrt(p * n / nbins)))

    detail = {f"{m}/{pr}": round(t * 1e3, 3)
              for (m, pr), t in results.items()}
    print(f"# devices={p} n/worker={n} nbins={nbins} "
          f"headline={best_method}/high t_dev={t_dev*1e3:.2f}ms "
          f"t_host={t_host*1e3:.2f}ms correct={ok} detail={detail}",
          file=sys.stderr)
    # "correct" rides the headline line so the driver/CI can gate on a
    # numerically-broken path directly (advisor r3) instead of grepping
    # stderr for the spot-check verdict.
    line = {
        "metric": "histogram_allreduce_throughput",
        "value": round(dev_gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(dev_gbps / host_gbps, 3),
        "correct": bool(ok),
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "device_count": p,
    }
    if args.record:
        _write_local_artifact(dict(
            line,
            backend=jax.default_backend(),
            devices=p, rows_per_worker=n, nbins=nbins,
            method=best_method, precision="high",
            t_dev_ms=detail,
            gbps={f"{m}/{pr}": round(nbytes / t / 1e9, 3)
                  for (m, pr), t in results.items()},
            bandwidth_vs_rows=curve,
            t_host_ms=round(t_host * 1e3, 3),
            measurement=f"slope between K={K_SMALL} and K={K_BIG} "
                        "dataset-iterations inside single dispatches, "
                        "cycling a pool of "
                        f"{K_STAGE} pre-staged on-device datasets "
                        "(cancels the per-dispatch floor; staging "
                        "keeps threefry generation out of the timed "
                        "region)",
            correct=bool(ok)))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
