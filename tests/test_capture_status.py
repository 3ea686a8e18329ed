"""The capture-status gates, pinned in CI: a gate of
tools/capture_status.py that accepted a CPU, stale, or incorrect
artifact would report evidence that does not exist. Synthetic artifacts
exercise accept and reject paths for every gate."""

import importlib.util
import json
import os
import subprocess
import sys

from tests.test_integration import ROOT


def _load(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "capture_status", os.path.join(ROOT, "tools", "capture_status.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.REPO = str(tmp_path)
    return mod


def _write(tmp_path, name, payload):
    with open(os.path.join(str(tmp_path), name), "w") as f:
        json.dump(payload, f)


FRESH_TS = "20260731T120000Z"
STALE_TS = "20260730T120000Z"


def _full_set(tmp_path, ts=FRESH_TS, backend="tpu"):
    _write(tmp_path, f"KERNEL_HW_{ts}.json",
           {"backend": backend, "complete": True,
            "flash_bwd_fused_vs_xla": {}, "timestamp_utc": ts})
    _write(tmp_path, f"FLAGSHIP_HW_{ts}.json",
           {"backend": backend, "flash_attn": True, "timestamp_utc": ts})
    _write(tmp_path, f"FLAGSHIP_HW_{ts[:-3]}01Z.json",
           {"backend": backend, "flash_attn": False, "timestamp_utc": ts})
    _write(tmp_path, f"WIRE_BENCH_{ts}.json",
           {"tpu": [{"backend": backend}], "timestamp_utc": ts})
    _write(tmp_path, f"BENCH_LOCAL_{ts}.json",
           {"backend": backend, "correct": True, "timestamp_utc": ts})


def test_empty_repo_reports_every_gap(tmp_path):
    mod = _load(tmp_path)
    assert set(mod.missing()) == set(mod.KNOWN)


def test_fresh_tpu_set_is_complete(tmp_path):
    mod = _load(tmp_path)
    _full_set(tmp_path)
    assert mod.missing() == {}


def test_stale_artifacts_do_not_satisfy(tmp_path):
    mod = _load(tmp_path)
    _full_set(tmp_path, ts=STALE_TS)
    assert set(mod.missing()) == set(mod.KNOWN)


def test_cpu_fallback_does_not_satisfy(tmp_path):
    mod = _load(tmp_path)
    _full_set(tmp_path, backend="cpu")
    assert set(mod.missing()) == set(mod.KNOWN)


def test_incorrect_bench_does_not_satisfy(tmp_path):
    mod = _load(tmp_path)
    _full_set(tmp_path)
    _write(tmp_path, f"BENCH_LOCAL_{FRESH_TS}.json",
           {"backend": "tpu", "correct": False, "timestamp_utc": FRESH_TS})
    assert set(mod.missing()) == {"bench_local"}


def test_corrupt_artifact_is_ignored_not_fatal(tmp_path):
    mod = _load(tmp_path)
    _full_set(tmp_path)
    with open(os.path.join(str(tmp_path), "KERNEL_HW_zzz.json"), "w") as f:
        f.write("{not json")
    assert mod.missing() == {}


def test_have_unknown_item_fails_loudly():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "capture_status.py"),
         "--have", "no_such_item"],
        capture_output=True, timeout=60)
    assert out.returncode == 2
    assert b"unknown item" in out.stderr
