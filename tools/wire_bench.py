#!/usr/bin/env python
"""Measured byte savings of EQuARX-style wire quantization
(VERDICT r4 #7: turn "halves / quarters the bytes each hop moves" —
parallel/collectives.py — into numbers).

Two phases:

**host**: the tracker-launched XLA data plane (CPU gloo, world 4) times
K float-SUM allreduces per wire mode ∈ {none, bf16, int8} at small and
large payloads (tests/workers/wire_bench_worker.py asserts correctness
so a broken wire can't win). Reported next to the ANALYTIC bytes each
ring hop moves — n/p*4 (f32), n/p*2 (bf16), n/p*(1 + 4/256) (int8 data
+ per-block scales) — so the artifact shows both what the wire saves by
construction and what that buys in wall-clock on this fabric (loopback
TCP on one core: expect the win to appear only once payloads are
bandwidth-bound, and encode/decode compute to eat it below that).

**tpu** (needs a TPU; run it through the chip tool): on one
chip there is no inter-chip hop, so the measurable quantity is the
encode+decode overhead itself — slope-timed device cost per element of
decode(encode(x)) vs an f32 identity pass, the compute a multi-chip
ring pays per hop to move fewer bytes.

Writes WIRE_BENCH_<ts>.json at the repo root.
Usage: python tools/wire_bench.py [--host-only|--tpu-only|--smoke]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "workers", "wire_bench_worker.py")

sys.path.insert(0, REPO)
from rabit_tpu.parallel.collectives import _INT8_BLOCK  # noqa: E402


def hop_bytes(n: int, world: int, wire: str) -> int:
    chunk = n // world
    if wire == "bf16":
        return chunk * 2
    if wire == "int8":
        return chunk + (chunk // _INT8_BLOCK) * 4
    return chunk * 4


def run_host(world: int, n: int, k: int, wire: str) -> dict:
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               RABIT_DATAPLANE="xla", RABIT_DATAPLANE_MINBYTES="0",
               WIRE_BENCH_N=str(n), WIRE_BENCH_K=str(k))
    if wire != "none":
        env["RABIT_DATAPLANE_WIRE"] = wire
    else:
        env.pop("RABIT_DATAPLANE_WIRE", None)
    out = subprocess.run(
        [sys.executable, "-m", "rabit_tpu.tracker.launch", "-n", str(world),
         sys.executable, WORKER, "rabit_dataplane=xla",
         "rabit_dataplane_minbytes=0"],
        capture_output=True, text=True, timeout=900, cwd=REPO, env=env)
    assert out.returncode == 0, (out.stdout[-800:], out.stderr[-2000:])
    m = re.search(r"WIREBENCH (\{.*\})", out.stdout)
    assert m, out.stdout[-800:]
    row = json.loads(m.group(1))
    row["hop_bytes"] = hop_bytes(n, world, wire)
    return row


def run_tpu(smoke: bool) -> list:
    """Encode/decode overhead per element on the device (see module
    docstring). Requires a reachable backend; CPU in smoke."""
    import jax
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        # never record a CPU run as device evidence
        raise RuntimeError(
            f"tpu phase needs a TPU backend, got {jax.default_backend()}")
    import jax.numpy as jnp
    from jax import lax

    from rabit_tpu.parallel.collectives import _wire_decode, _wire_encode
    from rabit_tpu.utils.slope import slope_time

    n = 4096 if smoke else 1 << 22  # 16 MB of f32 at full size
    k_small, k_big = (2, 4) if smoke else (8, 64)

    def make_run(wire):
        @jax.jit
        def run(x, salt, k):
            def body(_, acc):
                y = acc + salt
                if wire is not None:
                    y = _wire_decode(_wire_encode(y, wire), wire, y.shape)
                return y * 0.5 + acc * 0.5
            return lax.fori_loop(0, k, body, x)
        x = jnp.linspace(-1.0, 1.0, n, dtype=jnp.float32)
        return lambda kk, salt: run(x, jnp.float32(salt), kk)

    rows = []
    for wire in (None, "bf16", "int8"):
        s = slope_time(make_run(wire), k_small, k_big, allow_noisy=smoke)
        rows.append({"wire": wire or "none", "n": n,
                     "backend": jax.default_backend(),
                     "s_per_iter": s, "ns_per_elem": s / n * 1e9})
    base = rows[0]["s_per_iter"]
    for r in rows[1:]:
        r["overhead_vs_f32"] = r["s_per_iter"] - base
    return rows


def _write(result: dict) -> None:
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_dir = os.path.join(REPO, "benchmarks", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"WIRE_BENCH_{ts}.json")
    with open(path, "w") as f:
        json.dump(dict(result, timestamp_utc=ts), f, indent=1)
    print(f"wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host-only", action="store_true")
    ap.add_argument("--tpu-only", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI contract check: tiny sizes, CPU, no artifact")
    args = ap.parse_args()

    result = {}
    if not args.tpu_only:
        world = 4
        grid = [(4096, 3)] if args.smoke else [(65536, 10), (4194304, 10)]
        rows = []
        for n, k in grid:
            for wire in ("none", "bf16", "int8"):
                row = run_host(world, n, k, wire)
                rows.append(row)
                print(json.dumps(row), flush=True)
        result["host"] = rows
    if not args.host_only:
        try:
            rows = run_tpu(args.smoke)
        except Exception as e:
            print(f"tpu phase failed: {e}", file=sys.stderr)
            if args.smoke:
                raise
            if result.get("host"):
                # minutes of completed host measurement: keep it (the
                # artifact records the device phase as absent), then
                # still exit nonzero so the failure is visible
                _write(result)
            sys.exit(1)
        result["tpu"] = rows
        for r in rows:
            print(json.dumps(r), flush=True)

    if args.smoke:
        print("smoke ok")
        return
    if "host" not in result:
        # --tpu-only (a chip-only run): carry the newest host
        # capture forward so every artifact is self-contained, and say
        # where it came from
        import glob
        prev = sorted(
            glob.glob(os.path.join(REPO, "benchmarks", "artifacts",
                                   "WIRE_BENCH_*.json"))
            + glob.glob(os.path.join(REPO, "WIRE_BENCH_*.json")),
            key=os.path.basename)
        for path in reversed(prev):
            with open(path) as f:
                old = json.load(f)
            if old.get("host"):
                result["host"] = old["host"]
                result["host_from"] = os.path.basename(path)
                break
    _write(result)


if __name__ == "__main__":
    main()
