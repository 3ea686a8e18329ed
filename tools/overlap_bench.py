#!/usr/bin/env python
"""Async-overlap benchmark: bucketed gradient sync, sequential vs
overlapped, over a 4-process gloo fleet.

Spawns 4 ``benchmarks/overlap_round_worker.py`` processes (XLA engine,
real cross-process collectives). Each step is N buckets of
backward-compute followed by that bucket's gradient allreduce; the sync
series blocks inside every ``rabit.allreduce`` (wire fully exposed),
the overlap series issues ``rabit.allreduce_async`` and computes the
next bucket while the previous one rides the wire. Workers assert the
two series reduce BIT-IDENTICALLY. Records the two fleet-mean step
times:

- ``bucket_step_ms_sync`` — DDP-naive: compute, block, repeat;
- ``bucket_step_ms_overlap`` — issue-and-continue: bucket b's wire
  time hides behind bucket b+1's compute.

Writes ``benchmarks/artifacts/OVERLAP_BENCH_<ts>.json`` and appends
both series to ``benchmarks/history.jsonl`` (normalized records via
``rabit_tpu/telemetry/history.py``) so ``tools/bench_sentinel.py``
trends them like any other committed perf series.

``--smoke`` (CI tier 0j) skips the fleet entirely and runs the
in-process async-dispatch round-trip instead: issue ->
overlap -> await on an 8-virtual-device mesh, with a live watchdog
guard riding the in-flight op, double-wait idempotency, and bit-parity
against the sync collective.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NPROC = 4


def smoke() -> int:
    """In-process issue/await round-trip on a virtual-device mesh: the
    async handle must deliver the sync collective's exact bits, keep a
    watchdog deadline armed per in-flight op (and never trip it), stay
    idempotent across double waits, and leave the in-flight window
    empty."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from rabit_tpu.ops.reducers import SUM
    from rabit_tpu.parallel import collectives as C
    from rabit_tpu.utils.watchdog import Watchdog

    mesh = Mesh(np.array(jax.devices()[:8]), ("proc",))
    rng = np.random.default_rng(7)
    x = rng.standard_normal((8, 4096)).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("proc")))

    ref = np.asarray(C.device_allreduce(xs, mesh, SUM, method="ring"))
    wd = Watchdog(floor_ms=60000, abort=False)
    guard = wd.guard("allreduce", nbytes=x.nbytes)
    h = C.device_allreduce_async(xs, mesh, SUM, method="ring", guard=guard)
    assert isinstance(h.ready(), bool)
    out = np.asarray(h.wait())
    assert np.array_equal(ref, out), "async result diverged from sync"
    assert np.array_equal(ref, np.asarray(h.wait())), \
        "double wait() not idempotent"
    assert wd.expired_total == 0, "watchdog tripped on a healthy op"
    assert C.inflight_count() == 0, "in-flight window not drained"

    # hier schedule: three overlapped phases, one awaitable
    groups = ((0, 1, 2, 3), (4, 5, 6, 7))
    ref2 = np.asarray(C.device_hier_allreduce(xs, mesh, SUM, groups=groups))
    h2 = C.device_hier_allreduce_async(xs, mesh, SUM, groups=groups)
    assert np.array_equal(ref2, np.asarray(h2.wait())), \
        "async hier diverged from sync hier"
    assert C.inflight_count() == 0
    wd.close()
    print("overlap smoke ok")
    return 0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_fleet() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO  # import rabit_tpu from this checkout
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one local CPU device per process
    port = _free_port()
    worker = os.path.join(REPO, "benchmarks", "overlap_round_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(NPROC), str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO) for i in range(NPROC)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(f"rank {i} failed rc={p.returncode}:\n"
                               f"{out[-2000:]}")
    lines = [ln for ln in outs[0].splitlines() if ln.startswith("{")]
    if not lines:
        raise RuntimeError(f"rank 0 emitted no result line:\n{outs[0]}")
    return json.loads(lines[-1])


def ingest(result: dict, source: str, ts: str) -> int:
    """Both series into the committed history, sharing the run's config
    fields so each trends against its own like-for-like past."""
    from rabit_tpu.telemetry import history
    config = {k: result[k] for k in ("world", "n_buckets", "bucket_elems",
                                     "dtype", "compute_dim",
                                     "compute_reps")}
    added = 0
    for metric in ("bucket_step_ms_sync", "bucket_step_ms_overlap"):
        doc = dict(config, metric=metric, value=result[metric],
                   unit="ms", timestamp_utc=ts)
        added += history.append(history.history_path(REPO),
                                history.records_from_artifact(
                                    doc, source=source))
    return added


def main() -> int:
    ap = argparse.ArgumentParser(
        description="sequential vs overlapped bucketed gradient sync")
    ap.add_argument("--smoke", action="store_true",
                    help="in-process async-dispatch round-trip (CI); "
                         "no fleet, no artifact/history writes")
    args = ap.parse_args()
    if args.smoke:
        return smoke()
    result = run_fleet()
    print(json.dumps(result), flush=True)
    ratio = result["bucket_step_ms_overlap"] / result["bucket_step_ms_sync"]
    print(f"overlap/sync = {ratio:.3f}", flush=True)
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_dir = os.path.join(REPO, "benchmarks", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    name = f"OVERLAP_BENCH_{ts}.json"
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump({"benchmark": "bucketed gradient sync over a 4-process "
                                "gloo fleet, sequential blocking vs "
                                "async-overlapped (compute hides wire)",
                   "timestamp_utc": ts, **result}, f, indent=1)
        f.write("\n")
    added = ingest(result, name, ts)
    print(f"wrote {path} ({added} history records)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
