#!/usr/bin/env python
"""Bound the histogram kernel's remaining cost empirically (VERDICT r3
#4): separate the fixed cost (HBM streaming + the two one-hot mask
builds + one anchor dot) from the per-component cost (value select into
the hi side + its rows of the one MXU dot) by sweeping three kernel
variants:

- ``mask_only``: builds both masks and does ONE value-free dot
  (bin-count histogram) — no per-component select work at all;
- ``fast``: 2 components (grad, hess as bf16);
- ``high``: 4 components (bf16 hi/lo splits).

The (high - fast) / 2 slope is the marginal per-component cost; the
mask_only anchor is the floor the VPU mask construction + DMA sets.
Sweeps nbins in {256, 1024, 4096} x rows in {2^20, 2^21}; slope timing
per bench.py's methodology (pre-staged pools, in-dispatch fori_loop,
memoization salt). Writes HIST_SWEEP_<ts>.json.

Usage: python tools/histogram_sweep.py   (needs a TPU: run it through the chip
tool, ``chiprun -- python tools/histogram_sweep.py``)
"""

import datetime
import functools
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

K_SMALL, K_BIG, K_STAGE = 8, 64, 8


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from rabit_tpu.ops.pallas_kernels import (
        _CHUNK, _LANES, _atiles, _hist_compiler_params, _interpret,
        _out_struct, _histogram_tpu_impl)

    smoke = os.environ.get("RABIT_SWEEP_SMOKE") == "1"
    if smoke:
        # standalone smoke must not require the caller to also know
        # about the interpret flag (pallas compiles only on TPU)
        os.environ.setdefault("RABIT_PALLAS_INTERPRET", "1")
    backend = jax.default_backend()
    if backend != "tpu" and not smoke:
        raise SystemExit(f"needs a TPU backend, got {backend}")
    global K_SMALL, K_BIG
    if smoke:
        K_SMALL, K_BIG = 2, 4

    def _mask_only_body(atile: int, chunk: int, b_ref, out_ref):
        # the full kernel's mask construction verbatim (entries on
        # lanes), minus every per-component select: one value-free
        # count dot
        j = pl.program_id(0)
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        bb = b_ref[:].reshape(1, chunk)
        hi = jax.lax.shift_right_logical(bb, 7)
        lo = jax.lax.bitwise_and(bb, _LANES - 1)
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (_LANES, chunk), 0)
        iota_a = jax.lax.broadcasted_iota(
            jnp.int32, (atile, chunk), 0) + j * atile
        out_ref[:] += jax.lax.dot_general(
            (hi == iota_a).astype(jnp.bfloat16),
            (lo == iota_c).astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @functools.partial(jax.jit, static_argnames=("nbins",))
    def mask_only(bins, nbins):
        atile, nat = _atiles(nbins, 1)
        out = pl.pallas_call(
            functools.partial(_mask_only_body, atile, _CHUNK),
            grid=(nat, bins.shape[0] // _CHUNK),
            in_specs=[pl.BlockSpec((_CHUNK // _LANES, _LANES),
                                   lambda j, i: (i, 0))],
            out_specs=pl.BlockSpec((atile, _LANES), lambda j, i: (j, 0)),
            out_shape=_out_struct((nat * atile, _LANES), jnp.float32, bins),
            compiler_params=_hist_compiler_params(),
            interpret=_interpret(),
        )(bins.reshape(-1, _LANES))
        return out.reshape(-1)[:nbins]

    @functools.partial(jax.jit, static_argnames=("nrows", "nbins"))
    def gen_pool(seed, nrows, nbins):
        key = jax.random.PRNGKey(seed)
        kb, kg, kh = jax.random.split(key, 3)
        b = jax.random.randint(kb, (K_STAGE, nrows), 0, nbins, jnp.int32)
        g = jax.random.normal(kg, (K_STAGE, nrows), jnp.float32)
        h = jax.random.uniform(kh, (K_STAGE, nrows), jnp.float32)
        return b, g, h

    @functools.partial(jax.jit, static_argnames=("k", "variant", "nbins"))
    def run_batch(data, salt, k, variant, nbins):
        b, g, h = data

        def one(i, acc):
            s = jnp.bitwise_and(i, K_STAGE - 1)
            if variant == "mask_only":
                return acc + mask_only(b[s], nbins).sum()
            out = _histogram_tpu_impl(b[s], g[s], h[s], nbins, variant,
                                      _interpret())
            return acc + out.sum()
        return jax.lax.fori_loop(0, k, one, salt * jnp.float32(1e-30))

    def slope(fn):
        # shared dispatch-floor-cancelling methodology; noisy slopes
        # fail loudly except in CI smoke runs
        from rabit_tpu.utils.slope import slope_time
        return slope_time(fn, K_SMALL, K_BIG, allow_noisy=smoke)

    rows_list = (1 << 17,) if smoke else (1 << 20, 1 << 21)
    nbins_list = (256, 1024) if smoke else (256, 1024, 4096)
    table = []
    for nrows in rows_list:
        for nbins in nbins_list:
            data = jax.block_until_ready(gen_pool(7, nrows, nbins))
            row = {"rows": nrows, "nbins": nbins}
            for variant in ("mask_only", "fast", "high"):
                t = slope(lambda k, s, v=variant: run_batch(
                    data, jnp.float32(s), k, v, nbins))
                row[f"{variant}_ms"] = round(t * 1e3, 4)
                # bytes actually streamed: mask_only reads only the
                # 4-byte bin ids; fast/high also stream grad+hess f32
                nbytes = nrows * (4 if variant == "mask_only" else 12)
                row[f"{variant}_gbps"] = round(nbytes / t / 1e9, 3)
            # marginal cost of one value component (select + dot)
            row["per_component_ms"] = round(
                (row["high_ms"] - row["fast_ms"]) / 2, 4)
            # what fraction of the high path is the value-free floor
            row["mask_floor_frac_of_high"] = round(
                row["mask_only_ms"] / row["high_ms"], 3)
            del data
            table.append(row)
            print(json.dumps(row), flush=True)

    # correctness spot check: mask_only counts == np.bincount
    rng = np.random.default_rng(0)
    n, nb = (1 << 17, 256) if smoke else (1 << 20, 1024)
    b_np = rng.integers(0, nb, n).astype(np.int32)
    got = np.asarray(mask_only(jnp.asarray(b_np), nb))
    want = np.bincount(b_np, minlength=nb).astype(np.float64)
    ok = bool(np.allclose(got, want))
    print(f"mask_only counts correct={ok}", flush=True)
    assert ok, "mask-only count kernel wrong on hardware"

    if smoke:  # CI must not shed artifacts into the repo
        print("smoke ok")
        return
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_dir = os.path.join(_REPO, "benchmarks", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"HIST_SWEEP_{ts}.json")
    with open(path, "w") as f:
        json.dump({"backend": backend, "device": str(jax.devices()[0]),
                   "measurement": f"slope K={K_SMALL}->{K_BIG} over a "
                                  f"{K_STAGE}-dataset pre-staged pool",
                   "table": table, "timestamp_utc": ts}, f, indent=1)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
