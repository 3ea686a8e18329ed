#!/usr/bin/env python
"""End-to-end gradient-boosting round benchmark (VERDICT r3 #7): gives
the "histogram allreduce" north star an end-to-end per-boosting-round
number, not just a kernel number.

Phase A (always): 8 tracker-launched workers on the host run real
boosting rounds (benchmarks/boosted_round_worker.py) — per-round host
histogram build + socket allreduce, cluster-max timings.

Phase B (on a TPU; without one the run fails, except in the CPU smoke):
the same per-worker histogram workload
(rows x F contributions, same nbins) built by the Pallas kernel on one
chip, slope-timed (rabit_tpu.utils.slope). The derived
``tpu_round_ms`` = kernel build + the measured allreduce — the
end-to-end round a TPU worker pays when the build moves on-chip.

Writes BOOSTED_BENCH_<ts>.json and prints each phase as a JSON line.
RABIT_BOOSTED_SMOKE=1 shrinks sizes and skips the artifact (CI).
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def phase_a(smoke: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO  # import rabit_tpu from this checkout
    env["JAX_PLATFORMS"] = "cpu"
    if smoke:
        env.update(ROWS=str(1 << 12), N_ROUNDS="3")
    out = subprocess.run(
        [sys.executable, "-m", "rabit_tpu.tracker.launch", "-n", "8",
         sys.executable,
         os.path.join(REPO, "benchmarks", "boosted_round_worker.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    if out.returncode != 0:
        raise RuntimeError(f"phase A failed rc={out.returncode}:\n"
                           f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("{")][-1]
    return json.loads(line)


def phase_b(host: dict, smoke: bool) -> dict:
    """Kernel build time for the SAME per-worker workload on one chip."""
    import jax

    if smoke:
        jax.config.update("jax_platforms", "cpu")
        os.environ.setdefault("RABIT_PALLAS_INTERPRET", "1")
    elif jax.default_backend() != "tpu":
        raise SystemExit(f"boosted_bench: phase B needs a TPU, JAX found "
                         f"{jax.default_backend()}; nothing measured")

    import functools

    import jax.numpy as jnp

    from rabit_tpu.models import histogram as H
    from rabit_tpu.utils.slope import slope_time

    n = host["contributions_per_worker"]
    nbins = host["nbins"]
    k_stage, k_small, k_big = (2, 2, 4) if smoke else (16, 16, 128)

    @functools.partial(jax.jit, static_argnames=("nrows",))
    def gen(seed, nrows):
        key = jax.random.PRNGKey(seed)
        kb, kg, kh = jax.random.split(key, 3)
        return (jax.random.randint(kb, (k_stage, nrows), 0, nbins,
                                   jnp.int32),
                jax.random.normal(kg, (k_stage, nrows), jnp.float32),
                jax.random.uniform(kh, (k_stage, nrows), jnp.float32))

    method = "pallas" if not smoke else "matmul"

    @functools.partial(jax.jit, static_argnames=("k",))
    def run(data, salt, k):
        b, g, h = data
        def one(i, acc):
            s = jnp.bitwise_and(i, k_stage - 1)
            return acc + H.local_histogram(g[s], h[s], b[s], nbins,
                                           method=method,
                                           precision="high")
        return jax.lax.fori_loop(
            0, k, one, jnp.full((nbins, 2), salt * 1e-30, jnp.float32))

    data = jax.block_until_ready(gen(7, n))
    t = slope_time(lambda k, s: run(data, jnp.float32(s), k),
                   k_small, k_big, allow_noisy=smoke)
    return {"tpu_kernel_ms_per_round": round(t * 1e3, 3),
            "tpu_round_ms": round(t * 1e3 +
                                  host["allreduce_ms_per_round"], 3),
            "kernel_method": method}


def main() -> None:
    smoke = os.environ.get("RABIT_BOOSTED_SMOKE") == "1"
    host = phase_a(smoke)
    print(json.dumps({"phase": "host_8_workers", **host}), flush=True)
    tpu = phase_b(host, smoke)
    tpu["speedup_vs_host_round"] = round(
        host["host_round_ms"] / tpu["tpu_round_ms"], 2)
    print(json.dumps({"phase": "tpu_kernel", **tpu}), flush=True)

    if smoke:
        print("smoke ok")
        return
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_dir = os.path.join(REPO, "benchmarks", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BOOSTED_BENCH_{ts}.json")
    with open(path, "w") as f:
        json.dump({"benchmark": "end-to-end gradient-boosting round: "
                                "8 host workers (build + socket "
                                "allreduce) and single-chip Pallas "
                                "build at the same shape",
                   "host": host, "tpu": tpu, "timestamp_utc": ts},
                  f, indent=1)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
