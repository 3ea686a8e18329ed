#!/usr/bin/env python
"""Run the REFERENCE's own recovery programs (test/model_recover.cc,
local_recover.cc, lazy_recover.cc — built out-of-tree with the mock
failure-injection engine) under OUR tracker shim, with scripted kills
respawned like ``dmlc-submit --local-num-attempt`` (VERDICT r3 #6).

This is the protocol-fidelity proof next to the speed head-to-head: the
unmodified reference binaries — their dmlc tracker wire protocol, their
link-repair loop, their mock kill schedules (exit 255 + respawn with an
advanced DMLC_NUM_ATTEMPT) — all pass against tools/dmlc_tracker_shim.py.
Scenarios mirror /root/reference/test/test.mk:13-37 (world 10, 10k
doubles, up to 8 scripted kills incl. die-same and die-hard).

Writes REF_RECOVER_<ts>.json. ``--quick`` runs a CI-sized subset.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from socket_vs_reference import build_reference  # noqa: E402

# (name, program, nworkers, expect_respawns, args) — transcribed from
# the reference's test.mk targets (rabit_debug dropped: it only adds
# stderr volume). expect_respawns is the DETERMINISTIC number of
# scripted kills that actually fire (a kill at trial 0 advances that
# rank's attempt counter, so a later same-rank trial-0 entry never
# fires — e.g. die_same's mock=0,1,1,0 after mock=0,0,1,0). Enforcing
# the exact count matters: the reference's asserts also exit(255), so
# without it a shim protocol bug could retry itself into a pass.
SCENARIOS = [
    ("model_recover_10_10k", "model_recover", 10, 2,
     ["10000", "mock=0,0,1,0", "mock=1,1,1,0", "rabit_bootstrap_cache=-1",
      "rabit_reduce_ring_mincount=1"]),
    ("model_recover_10_10k_die_same", "model_recover", 10, 4,
     ["10000", "mock=0,0,1,0", "mock=1,1,1,0", "mock=0,1,1,0",
      "mock=4,1,1,0", "mock=9,1,1,0", "rabit_bootstrap_cache=1"]),
    ("model_recover_10_10k_die_hard", "model_recover", 10, 6,
     ["10000", "mock=0,0,1,0", "mock=1,1,1,0", "mock=1,1,1,1",
      "mock=0,1,1,0", "mock=4,1,1,0", "mock=9,1,1,0", "mock=8,1,2,0",
      "mock=4,1,3,0", "rabit_bootstrap_cache=1"]),
    ("local_recover_10_10k", "local_recover", 10, 5,
     ["10000", "mock=0,0,1,0", "mock=1,1,1,0", "mock=0,1,1,0",
      "mock=4,1,1,0", "mock=9,1,1,0", "mock=1,1,1,1"]),
    ("lazy_recover_10_10k_die_hard", "lazy_recover", 10, 6,
     ["10000", "mock=0,0,1,0", "mock=1,1,1,0", "mock=1,1,1,1",
      "mock=0,1,1,0", "mock=4,1,1,0", "mock=9,1,1,0", "mock=8,1,2,0",
      "mock=4,1,3,0"]),
    ("lazy_recover_10_10k_die_same", "lazy_recover", 10, 4,
     ["10000", "mock=0,0,1,0", "mock=1,1,1,0", "mock=0,1,1,0",
      "mock=4,1,1,0", "mock=9,1,1,0"]),
    ("ringallreduce_10_10k", "model_recover", 10, 0,
     ["10000", "rabit_reduce_ring_mincount=10"]),
]

QUICK = [
    ("model_recover_4_1k_quick", "model_recover", 4, 2,
     ["1000", "mock=0,0,1,0", "mock=1,1,1,0", "rabit_bootstrap_cache=-1"]),
    ("local_recover_4_1k_quick", "local_recover", 4, 1,
     ["1000", "mock=2,1,1,0"]),
]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized subset (world 4, 1k doubles)")
    args = ap.parse_args()
    scenarios = QUICK if args.quick else SCENARIOS

    shim = os.path.join(REPO, "tools", "dmlc_tracker_shim.py")
    rows = []
    failed = False
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO  # import rabit_tpu from this checkout
    with tempfile.TemporaryDirectory() as wd:
        binaries = {}
        for prog in {s[1] for s in scenarios}:
            binaries[prog] = build_reference(wd, test_src=prog, mock=True)
        for name, prog, world, expect_respawns, sargs in scenarios:
            t0 = time.perf_counter()
            out = subprocess.run(
                [sys.executable, shim, "-n", str(world),
                 "--max-attempts", "20", binaries[prog], *sargs],
                capture_output=True, text=True, timeout=600, env=env)
            dt = time.perf_counter() - t0
            respawns = out.stderr.count("[ref-launcher] worker")
            ok = out.returncode == 0 and respawns == expect_respawns
            failed = failed or not ok
            rows.append({"scenario": name, "world": world,
                         "rc": out.returncode, "respawns": respawns,
                         "expected_respawns": expect_respawns,
                         "seconds": round(dt, 2)})
            print(json.dumps(rows[-1]), flush=True)
            if not ok:
                print(out.stdout[-2000:], file=sys.stderr)
                print(out.stderr[-2000:], file=sys.stderr)

    if args.quick:  # CI must not shed artifacts into the repo
        return 1 if failed else 0
    ts = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y%m%dT%H%M%SZ")
    out_dir = os.path.join(REPO, "benchmarks", "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"REF_RECOVER_{ts}.json")
    with open(path, "w") as f:
        json.dump({
            "benchmark": "reference test/{model,local,lazy}_recover.cc "
                         "(mock engine, unmodified) under our tracker "
                         "shim with exit-255 respawns, scenarios from "
                         "test/test.mk:13-37",
            "rows": rows, "timestamp_utc": ts}, f, indent=1)
        f.write("\n")
    print(f"wrote {path}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
