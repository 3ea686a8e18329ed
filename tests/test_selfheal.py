"""Tier-1 self-healing data plane units (ISSUE 13): the frame CRC
(``RbtFrameCrc32`` vs ``zlib.crc32``), the chaos ``bitflip`` rule and
its proxy corruption, the three-rung watchdog ladder (retry -> reform
-> abort, with ``rabit_watchdog_abort=0`` stopping at reform), the
cached-round in-collective retry (``RABIT_COLLECTIVE_RETRIES``), the
native recovery-counter drain, and lint rule R004 — all in-process;
the 4-rank scenarios live in test_selfheal_cluster.py
(doc/fault_tolerance.md "Self-healing data plane")."""

import ast
import ctypes
import importlib.util
import os
import socket
import threading
import time
import zlib

import numpy as np
import pytest

from rabit_tpu import telemetry
from rabit_tpu.chaos import ChaosProxy, Rule, Schedule
from rabit_tpu.engine import dataplane as dp_mod
from rabit_tpu.ops.reducers import DTYPE_ENUM
from rabit_tpu.utils.watchdog import WATCHDOG_EXIT_CODE, Watchdog

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(ROOT, "native", "build", "librabit_tpu_core.so")

needs_native = pytest.mark.skipif(not os.path.isfile(LIB),
                                  reason="native core not built")


# -- frame CRC (native) ----------------------------------------------------

@needs_native
def test_frame_crc_matches_zlib():
    """The wire CRC must be the standard zlib polynomial: tests and
    tools can then verify captured frames without the native lib."""
    lib = ctypes.CDLL(LIB)
    lib.RbtFrameCrc32.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.RbtFrameCrc32.restype = ctypes.c_uint32
    for payload in (b"", b"\x00", b"rabit", bytes(range(256)) * 41,
                    np.arange(1024, dtype=np.int64).tobytes()):
        assert lib.RbtFrameCrc32(payload, len(payload)) == \
            zlib.crc32(payload), payload[:16]
    # single-bit damage anywhere must change the checksum
    base = bytearray(bytes(range(256)))
    crc0 = lib.RbtFrameCrc32(bytes(base), len(base))
    for pos in (0, 100, 255):
        dmg = bytearray(base)
        dmg[pos] ^= 0x01
        assert lib.RbtFrameCrc32(bytes(dmg), len(dmg)) != crc0


# -- chaos bitflip rule ----------------------------------------------------

def test_bitflip_rule_validation():
    with pytest.raises(ValueError, match="bitflip"):
        Rule("bitflip")  # unanchored corruption is never what you want
    r = Rule("bitflip", after_bytes=64)
    assert r.max_times == 1  # transient corruption by default
    assert Rule("bitflip", window_s=(0, 1), max_times=3).max_times == 3
    assert Rule("bitflip", conn=2).conn == 2
    back = Rule.from_dict(r.to_dict())
    assert back.kind == "bitflip" and back.after_bytes == 64
    assert back.max_times == 1


def _echo_server():
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    srv.settimeout(10.0)

    def loop():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            try:
                while True:
                    data = conn.recv(65536)
                    if not data:
                        break
                    conn.sendall(data)
            except OSError:
                pass
            finally:
                conn.close()

    threading.Thread(target=loop, daemon=True).start()
    return srv


def _round_trip(host, port, payload, timeout=10.0):
    with socket.create_connection((host, port), timeout=timeout) as conn:
        conn.sendall(payload)
        conn.shutdown(socket.SHUT_WR)
        out = b""
        while True:
            chunk = conn.recv(65536)
            if not chunk:
                return out
            out += chunk


def test_proxy_bitflip_corrupts_silently_then_budget_spends():
    """The corruption shape: bytes still flow (no reset, no stall, same
    length) but 1-4 of them are wrong — exactly what only an
    end-to-end checksum can catch."""
    payload = bytes(range(256)) * 64  # 16 KiB
    srv = _echo_server()
    try:
        sched = Schedule([Rule("bitflip", after_bytes=1, max_times=1)],
                         seed=3)
        with ChaosProxy(*srv.getsockname(), sched) as proxy:
            out = _round_trip(proxy.host, proxy.port, payload)
            assert len(out) == len(payload), "bitflip must not tear"
            diffs = [i for i, (a, b) in enumerate(zip(out, payload))
                     if a != b]
            assert 1 <= len(diffs) <= 4, diffs
            assert [e[1] for e in proxy.events] == ["bitflip"]
            # budget spent (max_times=1): the retry sails through clean
            assert _round_trip(proxy.host, proxy.port, payload) == payload
    finally:
        srv.close()


# -- watchdog three-rung ladder --------------------------------------------

def test_ladder_fires_retry_reform_abort_in_order():
    telemetry.reset(enabled=True)
    events = []
    wd = Watchdog(floor_ms=80, abort=True,
                  abort_fn=lambda c: events.append(("abort", c)))
    try:
        # deadline 0.08s, grace floor 0.5s: retry ~0.08s, reform
        # ~0.58s, abort ~1.08s
        with wd.guard("stuck.phase", nbytes=64,
                      on_expire=lambda: events.append(("retry",)),
                      on_reform=lambda: events.append(("reform",))):
            deadline = time.monotonic() + 5.0
            while len(events) < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
        assert [e[0] for e in events] == ["retry", "reform", "abort"]
        assert events[2][1] == WATCHDOG_EXIT_CODE
        rows = {(c["name"], c.get("provenance", ""))
                for c in telemetry.snapshot()["counters"]}
        for name in ("watchdog.expired", "watchdog.reform",
                     "watchdog.abort"):
            assert (name, "recovery") in rows, (name, rows)
    finally:
        wd.close()
        telemetry.reset(enabled=False)


def test_ladder_abort_opt_out_stops_at_reform_and_drops_guard():
    """The rabit_watchdog_abort=0 fix: pre-ladder the monitor kept
    spinning on the expired guard forever with no record; now the stall
    is noted and the guard is dropped at the reform rung."""
    codes = []
    reforms = []
    wd = Watchdog(floor_ms=50, abort=False, abort_fn=codes.append)
    try:
        with wd.guard("stuck.phase",
                      on_reform=lambda: reforms.append(1)) as g:
            deadline = time.monotonic() + 3.0
            while not reforms and time.monotonic() < deadline:
                time.sleep(0.02)
            time.sleep(0.05)
            with wd._cv:
                assert g not in wd._guards, "guard must drop at reform"
        assert reforms == [1]
        assert g.expired and g.reformed
        assert codes == [], "abort rung must never fire with abort=0"
    finally:
        wd.close()


def test_ladder_reform_hook_failure_does_not_block_abort():
    codes = []
    wd = Watchdog(floor_ms=50, abort=True, abort_fn=codes.append)
    try:
        def bad_reform():
            raise RuntimeError("interrupt plane unavailable")

        with wd.guard("stuck.phase", on_reform=bad_reform):
            deadline = time.monotonic() + 5.0
            while not codes and time.monotonic() < deadline:
                time.sleep(0.02)
        assert codes == [WATCHDOG_EXIT_CODE]
    finally:
        wd.close()


# -- in-collective dataplane retry -----------------------------------------

def _bare_dataplane(retries):
    """An XlaDataPlane skeleton for exercising _invoke without jax or a
    formed world: the collective itself is monkeypatched per test."""
    dp = dp_mod.XlaDataPlane.__new__(dp_mod.XlaDataPlane)
    dp._lib = None
    dp._fail_at = None
    dp._invocations = 0
    dp._retries = retries
    dp.retries_total = 0
    dp._rank = 0
    dp._world = 2
    dp._formed_epoch = None
    dp.ensure_world = lambda epoch: None
    dp._teardown = lambda: None
    return dp


def _invoke(dp, arr, epoch=0):
    return dp._invoke(arr.ctypes.data, arr.size,
                      DTYPE_ENUM[np.dtype(arr.dtype)], 2, epoch, None)


def test_invoke_retries_rerun_round_from_pristine_inputs():
    telemetry.reset(enabled=True)
    try:
        dp = _bare_dataplane(retries=3)
        seen = []

        def allreduce(buf, op):
            seen.append(buf.copy())
            if len(seen) < 3:
                buf[:] = -1  # partial result left in place...
                raise RuntimeError("device lost")
            buf *= 2

        dp._allreduce = allreduce
        arr = np.arange(8, dtype=np.float64)
        assert _invoke(dp, arr) == 0
        # idempotence: every attempt reduced the SAME operands, never
        # the previous attempt's partial result
        assert len(seen) == 3
        for s in seen:
            np.testing.assert_array_equal(s, np.arange(8, dtype=np.float64))
        np.testing.assert_array_equal(arr, np.arange(8) * 2.0)
        assert dp.retries_total == 2
        assert dp._invocations == 1  # retries share one round id
        rows = {(c["name"], c.get("op", "")): c["count"]
                for c in telemetry.snapshot()["counters"]}
        assert rows[("recovery.retry", "dataplane")] == 2
        assert ("recovery.link_reset", "dataplane") not in rows
    finally:
        telemetry.reset(enabled=False)


def test_invoke_exhausted_retries_escalate_to_link_reset():
    telemetry.reset(enabled=True)
    try:
        dp = _bare_dataplane(retries=1)
        calls = []
        teardowns = []

        def allreduce(buf, op):
            calls.append(1)
            raise RuntimeError("still down")

        dp._allreduce = allreduce
        dp._teardown = lambda: teardowns.append(1)
        arr = np.arange(4, dtype=np.int64)
        assert _invoke(dp, arr) == 1  # nonzero -> C++ link reset path
        assert len(calls) == 2  # first try + one retry
        assert len(teardowns) == 2  # after the retry AND at escalation
        rows = {(c["name"], c.get("op", "")): c["count"]
                for c in telemetry.snapshot()["counters"]}
        assert rows[("recovery.retry", "dataplane")] == 1
        assert rows[("recovery.link_reset", "dataplane")] == 1
    finally:
        telemetry.reset(enabled=False)


def test_invoke_retries_disabled_preserves_single_shot_path():
    """RABIT_COLLECTIVE_RETRIES unset: first failure -> return 1, no
    retry, no input caching — byte-identical to the pre-ladder
    behavior."""
    telemetry.reset(enabled=True)
    try:
        dp = _bare_dataplane(retries=0)
        calls = []

        def allreduce(buf, op):
            calls.append(1)
            raise RuntimeError("down")

        dp._allreduce = allreduce
        arr = np.arange(4, dtype=np.int64)
        assert _invoke(dp, arr) == 1
        assert len(calls) == 1
        assert dp.retries_total == 0
        names = {c["name"] for c in telemetry.snapshot()["counters"]}
        assert "recovery.retry" not in names
        assert "recovery.link_reset" in names
    finally:
        telemetry.reset(enabled=False)


def test_collective_retries_env_parsing(monkeypatch):
    monkeypatch.setattr(dp_mod, "_require_private_api", lambda: None)
    monkeypatch.delenv("RABIT_COLLECTIVE_RETRIES", raising=False)
    assert dp_mod.XlaDataPlane(None)._retries == 0  # off by default
    monkeypatch.setenv("RABIT_COLLECTIVE_RETRIES", "7")
    assert dp_mod.XlaDataPlane(None)._retries == 7
    monkeypatch.setenv("RABIT_COLLECTIVE_RETRIES", "-3")
    assert dp_mod.XlaDataPlane(None)._retries == 0  # clamped, not armed
    monkeypatch.setenv("RABIT_COLLECTIVE_RETRIES", "lots")
    with pytest.raises(ValueError, match="RABIT_COLLECTIVE_RETRIES"):
        dp_mod.XlaDataPlane(None)


# -- native recovery-counter drain -----------------------------------------

class _FakeStatsLib:
    """Stands in for librabit_tpu_core: hands back scripted monotonic
    recovery counters through the RbtRecoveryStats out-params."""

    def __init__(self):
        self.vals = (0, 0, 0)
        self.rc = 0

    def RbtRecoveryStats(self, r, f, s):  # noqa: N802 - C ABI name
        r._obj.value, f._obj.value, s._obj.value = self.vals
        return self.rc

    def RbtGetRank(self):  # noqa: N802 - C ABI name
        return 0


def _bare_engine(lib):
    from rabit_tpu.engine.native import NativeEngine
    eng = NativeEngine.__new__(NativeEngine)
    eng._lib = lib
    eng._recovery_seen = (0, 0, 0)
    return eng


@needs_native
def test_drain_recovery_stats_emits_exact_deltas():
    telemetry.reset(enabled=True)
    try:
        lib = _FakeStatsLib()
        eng = _bare_engine(lib)

        def counts():
            return {(c["name"], c.get("op", "")): c["count"]
                    for c in telemetry.snapshot()["counters"]}

        lib.vals = (2, 1, 0)
        eng._drain_recovery_stats()
        assert counts() == {("recovery.retry", "native_round"): 2,
                            ("recovery.frame_reject", "frame_crc"): 1}
        eng._drain_recovery_stats()  # no movement -> no new events
        assert counts()[("recovery.retry", "native_round")] == 2
        lib.vals = (3, 1, 2)
        eng._drain_recovery_stats()
        got = counts()
        assert got[("recovery.retry", "native_round")] == 3
        assert got[("recovery.frame_reject", "frame_crc")] == 1
        assert got[("recovery.link_resurrect", "link")] == 2
        # a failed read (engine not initialised) must not corrupt the
        # last-seen baseline
        lib.rc = -1
        lib.vals = (100, 100, 100)
        eng._drain_recovery_stats()
        assert eng._recovery_seen == (3, 1, 2)
        assert counts()[("recovery.retry", "native_round")] == 3
    finally:
        telemetry.reset(enabled=False)


def test_metric_families_register_recovery_gauges():
    from rabit_tpu.telemetry import prom
    assert "rabit_dataplane_retries_total" in prom.METRIC_FAMILIES
    assert "rabit_frame_crc_rejects_total" in prom.METRIC_FAMILIES


def test_trace_report_maps_recovery_events_to_rungs():
    spec = importlib.util.spec_from_file_location(
        "repo_trace_report", os.path.join(ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._recovery_rung("recovery.frame_reject") == "frame"
    assert mod._recovery_rung("recovery.retry") == "retry"
    assert mod._recovery_rung("recovery.link_resurrect") == "reconnect"
    assert mod._recovery_rung("recovery.world_reform") == "reform"
    assert mod._recovery_rung("watchdog.abort") == "abort"
    assert mod._recovery_rung("recovery.totally_new") == "-"


# -- lint rule R004 --------------------------------------------------------

def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "repo_lint", os.path.join(ROOT, "tools", "lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_r004_flags_uncounted_recovery_path():
    lint = _load_lint()
    rel = os.path.join("rabit_tpu", "engine", "dataplane.py")
    src = ("def _invoke(self):\n"
           "    return 0\n"
           "def _form_world(self):\n"
           "    pass\n")
    issues = lint._r004_issues(rel, ast.parse(src))
    assert [(i[2], i[1]) for i in issues] == [("R004", 1), ("R004", 3)]
    assert "provenance counter" in issues[0][3]


def test_r004_counted_paths_and_unmapped_files_pass():
    lint = _load_lint()
    rel = os.path.join("rabit_tpu", "engine", "dataplane.py")
    src = ("def _invoke(self):\n"
           "    telemetry.count('recovery.retry', provenance='recovery')\n"
           "def _form_world(self):\n"
           "    telemetry.record_span('x', 0.0)\n")
    assert lint._r004_issues(rel, ast.parse(src)) == []
    other = os.path.join("rabit_tpu", "utils", "retry.py")
    assert lint._r004_issues(other, ast.parse("def f():\n    pass\n")) == []


def test_r004_missing_recovery_path_is_reported():
    lint = _load_lint()
    rel = os.path.join("rabit_tpu", "utils", "watchdog.py")
    issues = lint._r004_issues(rel, ast.parse("x = 1\n"))
    assert len(issues) == 1 and issues[0][2] == "R004"
    assert "_reform" in issues[0][3] and "not found" in issues[0][3]


def test_repo_is_r004_clean():
    lint = _load_lint()
    bad = []
    for path in lint.iter_py_files(["rabit_tpu", "tools"]):
        bad += [i for i in lint.check_file(path) if i[2] == "R004"]
    assert bad == [], bad
