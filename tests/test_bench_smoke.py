"""The bench contract, pinned in CI: ``bench.py`` must run end to end
and print ONE JSON line with the driver-required keys. Rounds 1-2 lost
their perf evidence to bench-time failures; a broken bench is a broken
round, so the full path — staging, slope measurement, bandwidth curve,
correctness check, JSON emission — runs here on the CPU backend at
smoke sizes (RABIT_BENCH_SMOKE=1)."""

import json
import os
import subprocess
import sys

import pytest

from tests.test_integration import ROOT


def _hermetic_env(**overrides):
    """CPU-pinned subprocess env that imports rabit_tpu from this
    checkout."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(overrides)
    env["PYTHONPATH"] = ROOT  # import rabit_tpu from this checkout
    return env


def test_bench_smoke_contract():
    env = _hermetic_env(RABIT_BENCH_SMOKE="1")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, (out.stdout.decode()[-2000:],
                                 out.stderr.decode()[-2000:])
    # the contract: the LAST stdout line is the one JSON result line
    line = out.stdout.decode().strip().splitlines()[-1]
    res = json.loads(line)
    assert set(res) == {"metric", "value", "unit", "vs_baseline", "correct",
                        "platform", "device_kind", "device_count"}
    # a CPU rehearsal says so on its own line
    assert res["platform"] == "cpu"
    assert res["metric"] == "histogram_allreduce_throughput"
    assert res["unit"] == "GB/s"
    assert res["value"] > 0
    assert res["vs_baseline"] > 0
    # the numeric spot check (distributed path vs host oracle) rides the
    # result line itself so the driver/CI can gate on it directly
    assert res["correct"] is True
    # artifacts are written only with --record
    assert b"BENCH_LOCAL" not in out.stderr


def test_kernel_hw_proof_smoke_contract():
    """tools/kernel_hw_proof.py must run its full path — both histogram
    branches, flash fwd+bwd parity, forward chain and fused-backward
    chain slopes — on the CPU backend in interpret mode, so the capture
    tool is known to work before chip time is spent on it."""
    env = _hermetic_env(
        RABIT_KERNEL_PROOF_SMOKE="1",
        RABIT_PALLAS_INTERPRET="1",
    )
    before = set(os.listdir(ROOT))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "kernel_hw_proof.py")],
        capture_output=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, (out.stdout.decode()[-2000:],
                                 out.stderr.decode()[-2000:])
    text = out.stdout.decode()
    assert "flash_block: fwd=True bwd=True" in text
    assert "flash fwd+bwd chain" in text
    assert text.strip().endswith("smoke ok")
    # smoke must not shed NEW artifacts into the repo (committed
    # evidence artifacts from real runs are expected to exist)
    fresh = set(os.listdir(ROOT)) - before
    assert not [p for p in fresh if p.startswith("KERNEL_HW")], fresh


@pytest.mark.skipif(
    not all(os.path.isfile(os.path.join(ROOT, "native", "build", b))
            for b in ("speed_test", "mpirun", "orted")),
    reason="speed_test / launcher shims not built")
def test_socket_vs_mpi_smoke_contract():
    """tools/socket_vs_mpi.py (the reference's speed_test.mpi role) must
    run the same speed_test binary through BOTH launch paths — tracker/
    socket and mpirun-shim/MPI — at smoke sizes without shedding an
    artifact."""
    env = _hermetic_env()
    before = set(os.listdir(ROOT))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "socket_vs_mpi.py"),
         "--smoke"], capture_output=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, (out.stdout.decode()[-2000:],
                                 out.stderr.decode()[-2000:])
    text = out.stdout.decode()
    assert text.strip().endswith("smoke ok")
    rows = [json.loads(ln) for ln in text.splitlines()
            if ln.startswith("{")]
    assert rows and all(r["socket_mbs"]["sum"] > 0 and
                        r["mpi_mbs"]["sum"] > 0 for r in rows)
    fresh = set(os.listdir(ROOT)) - before
    assert not [p for p in fresh if p.startswith("SOCKET_VS_MPI")], fresh


def test_wire_bench_smoke_contract():
    """tools/wire_bench.py (VERDICT r4 #7) must run both phases — the
    tracker-launched XLA-plane timing across wire modes and the
    encode/decode overhead slope — at smoke sizes, artifact-free."""
    env = _hermetic_env()
    before = set(os.listdir(ROOT))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "wire_bench.py"),
         "--smoke"], capture_output=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, (out.stdout.decode()[-2000:],
                                 out.stderr.decode()[-2000:])
    text = out.stdout.decode()
    assert text.strip().endswith("smoke ok")
    rows = [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]
    host = [r for r in rows if "s_per_op" in r]
    dev = [r for r in rows if "s_per_iter" in r]
    assert {r["wire"] for r in host} == {"none", "bf16", "int8"}
    assert {r["wire"] for r in dev} == {"none", "bf16", "int8"}
    # the analytic hop-bytes column is the design claim being measured
    by_wire = {r["wire"]: r["hop_bytes"] for r in host}
    assert by_wire["bf16"] * 2 == by_wire["none"]
    assert by_wire["int8"] < by_wire["none"] // 3
    fresh = set(os.listdir(ROOT)) - before
    assert not [p for p in fresh if p.startswith("WIRE_BENCH")], fresh

