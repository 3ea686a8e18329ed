"""The program's own host phases reach a traced run's result line: the
``--trace 1`` rehearsal of the allreduce cell and of ``higgs_deep_nodes``
reports the per-layer metrics read from them (those read from a device
need a chip, and stay silent here).

    python -m pytest bench_harness/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2 ** 31 + 4242

PHASES = {"epsilon_tree_allreduce_4chip": ("dispatch_us.allreduce",
                                           ["allreduce_resolve_us",
                                            "allreduce_enqueue_us"]),
          "higgs_deep_nodes": ("dispatch_us.hist", ["hist_enqueue_us"])}


@pytest.mark.parametrize("workload", sorted(PHASES))
def test_traced_rehearsal_reports_phases(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench_harness", "run.py"),
         "--workload", workload, "--seed", str(SEED), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    got = {k: v["value"] for k, v in out["metrics"].items()}
    outside, phases = PHASES[workload]
    assert all(got.get(m, 0) > 0 for m in phases), got
    # the phases lie inside the call the harness times from outside
    assert sum(got[m] for m in phases) <= got[outside]
