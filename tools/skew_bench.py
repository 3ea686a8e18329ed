#!/usr/bin/env python
"""Straggler-adaptive allreduce benchmark: flat vs skew-adapted round
time over a lagging 4-process gloo fleet.

Spawns 4 ``benchmarks/skew_round_worker.py`` processes (XLA engine,
real cross-process collectives) with one rank sleeping ``LAG_MS``
before every round, runs both series in-process on the same fabric,
and records the two fleet-mean round times:

- ``skew_round_ms_flat`` — ``rabit_skew_adapt`` off: every rank pays
  the laggard's delay inside the flat ring;
- ``skew_round_ms_adapted`` — knob on, digest naming the laggard:
  pre-aggregation overlaps the early ranks' reduction with the delay.

Writes ``benchmarks/artifacts/SKEW_BENCH_<ts>.json`` and appends both
series to ``benchmarks/history.jsonl`` (one normalized record each via
``rabit_tpu/telemetry/history.py``), so ``tools/bench_sentinel.py``
trends them like any other committed perf series. ``--smoke`` shrinks
sizes and skips the artifact/history writes (CI).
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

from rabit_tpu.telemetry import history  # noqa: E402

NPROC = 4


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_fleet(smoke: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO  # import rabit_tpu from this checkout
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one local CPU device per process
    if smoke:
        env.update(PAYLOAD=str(1 << 16), LAG_MS="20", N_ROUNDS="3",
                   N_WARMUP="1")
    port = _free_port()
    worker = os.path.join(REPO, "benchmarks", "skew_round_worker.py")
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
    """Both series into the committed history, sharing the run's
    config fields so each trends against its own like-for-like past."""
    config = {k: result[k] for k in ("world", "payload_elems", "dtype",
                                     "lag_rank", "lag_ms")}
    added = 0
    for metric in ("skew_round_ms_flat", "skew_round_ms_adapted"):
        doc = dict(config, metric=metric, value=result[metric],
                   unit="ms", timestamp_utc=ts)
        added += history.append(history.history_path(REPO),
                                history.records_from_artifact(
                                    doc, source=source))
    return added


def main() -> int:
    ap = argparse.ArgumentParser(
        description="flat vs skew-adapted allreduce round bench")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, no artifact/history writes")
    args = ap.parse_args()
    result = run_fleet(args.smoke)
    print(json.dumps(result), flush=True)
    if args.smoke:
        assert result["skew_round_ms_flat"] > 0
        assert result["skew_round_ms_adapted"] > 0
        print("smoke ok")
        return 0
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_dir = os.path.join(REPO, "benchmarks", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    name = f"SKEW_BENCH_{ts}.json"
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump({"benchmark": "allreduce rounds over a lagging "
                                "4-process gloo fleet, flat ring vs "
                                "skew-adapted (pre-aggregation)",
                   "timestamp_utc": ts, **result}, f, indent=1)
        f.write("\n")
    added = ingest(result, name, ts)
    print(f"wrote {path} ({added} history records)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
