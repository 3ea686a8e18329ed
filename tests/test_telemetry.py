"""Collective telemetry contract (the observability PR's tentpole):

- span capture, counter math, and ring-buffer bounding under churn;
- disabled mode is a true no-op — shared null span, nullcontext
  annotations, and (the acceptance bar) a byte-identical jaxpr for the
  bucketed MLP train step with telemetry off vs on;
- Chrome-trace export schema (phases, monotonic ts) and
  summary-totals-agree-with-counters;
- dispatch provenance counters (fallback / table / explicit);
- the tracker's ``metrics`` wire command and fleet-merged table, both
  in-process (fast) and through a real 2-worker native cluster (slow);
- the leveled logger and the schema-emitting tools.
"""

import contextlib
import importlib.util
import json
import os
import socket
import struct
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from rabit_tpu import telemetry
from rabit_tpu.models import mlp
from rabit_tpu.ops.reducers import SUM
from rabit_tpu.parallel import device_allreduce, dispatch, make_mesh
from rabit_tpu.parallel.collectives import shard_over
from rabit_tpu.telemetry.aggregate import format_fleet_table, merge_summaries
from rabit_tpu.telemetry.export import build_chrome_trace, build_summary
from rabit_tpu.telemetry.recorder import NULL_SPAN, Recorder, size_bucket
from rabit_tpu.telemetry.schema import make_header, matches
from rabit_tpu.tracker.tracker import MAGIC, Tracker
from rabit_tpu.utils import log
from rabit_tpu.utils.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKERS = os.path.join(ROOT, "tests", "workers")
LIB = os.path.join(ROOT, "native", "build", "librabit_tpu_core.so")
NDEV = len(jax.devices())


@pytest.fixture
def telem():
    """Module-level recorder enabled for one test, disabled after (the
    process default — telemetry must never leak into other tests)."""
    telemetry.reset(capacity=256, enabled=True)
    yield
    telemetry.reset(enabled=False)


# ------------------------------------------------------ recorder: spans


def test_span_capture_and_counter_math():
    r = Recorder(capacity=64, enabled=True)
    with r.span("allreduce", nbytes=4096, op="sum", method="ring",
                wire="bf16"):
        pass
    snap = r.snapshot()
    assert snap["recorded"] == 1 and snap["dropped"] == 0
    (s,) = snap["spans"]
    assert s["name"] == "allreduce" and s["bytes"] == 4096
    assert s["op"] == "sum" and s["method"] == "ring" and s["wire"] == "bf16"
    assert s["dur"] >= 0.0
    (c,) = snap["counters"]
    assert c["bucket"] == "<=4KiB"
    assert c["count"] == 1 and c["bytes"] == 4096
    assert c["max_s"] == pytest.approx(c["total_s"])
    assert sum(c["hist_log2_us"].values()) == 1


def test_record_span_aggregates_per_key():
    r = Recorder(capacity=64, enabled=True)
    for d in (0.001, 0.002, 0.004):
        r.record_span("allreduce", d, nbytes=1 << 20, op="sum",
                      method="ring")
    r.record_span("allreduce", 0.008, nbytes=1 << 20, op="sum",
                  method="tree")
    snap = r.snapshot()
    by_method = {c["method"]: c for c in snap["counters"]}
    ring, tree = by_method["ring"], by_method["tree"]
    assert ring["count"] == 3 and tree["count"] == 1
    assert ring["bytes"] == 3 << 20
    assert ring["total_s"] == pytest.approx(0.007)
    assert ring["max_s"] == pytest.approx(0.004)
    assert sum(ring["hist_log2_us"].values()) == 3
    assert tree["max_s"] == pytest.approx(0.008)


def test_ring_buffer_bounded_under_churn():
    r = Recorder(capacity=32, enabled=True)
    for i in range(100):
        r.record_span(f"s{i}", 0.001, nbytes=i)
    snap = r.snapshot()
    assert snap["recorded"] == 100
    assert snap["dropped"] == 68
    assert len(snap["spans"]) == 32
    # the survivors are the most recent 32, chronological
    assert [s["name"] for s in snap["spans"]] == \
        [f"s{i}" for i in range(68, 100)]
    t0s = [s["t0"] for s in snap["spans"]]
    assert t0s == sorted(t0s)
    # counters stay exact regardless of ring churn
    assert sum(c["count"] for c in snap["counters"]) == 100


def test_capacity_must_be_positive():
    with pytest.raises(ValueError, match="capacity"):
        Recorder(capacity=0, enabled=True)


def test_counter_only_events_have_no_span():
    r = Recorder(capacity=8, enabled=True)
    r.count("dispatch", nbytes=512, op="sum", method="tree",
            provenance="fallback")
    snap = r.snapshot()
    assert snap["spans"] == [] and snap["recorded"] == 0
    (c,) = snap["counters"]
    assert c["count"] == 1 and c["total_s"] == 0.0
    assert c["provenance"] == "fallback"


def test_size_bucket_edges():
    assert size_bucket(0) == "0B"
    assert size_bucket(1) == "<=1KiB"
    assert size_bucket(1024) == "<=1KiB"
    assert size_bucket(1025) == "<=4KiB"
    assert size_bucket(1 << 28) == "<=256MiB"
    assert size_bucket((1 << 28) + 1) == ">256MiB"


# ----------------------------------------------------- disabled = no-op


def test_disabled_recorder_is_noop():
    r = Recorder(capacity=8, enabled=False)
    sp = r.span("x", nbytes=100)
    assert sp is NULL_SPAN and sp.live is False
    with sp:
        pass
    r.record_span("x", 0.5)
    r.count("x")
    snap = r.snapshot()
    assert snap["recorded"] == 0 and snap["spans"] == [] \
        and snap["counters"] == []


def test_module_span_is_shared_null_when_disabled():
    telemetry.reset(enabled=False)
    assert telemetry.span("a") is telemetry.span("b") is NULL_SPAN
    assert not telemetry.enabled()


def test_trace_annotation_modes(telem):
    live = telemetry.trace_annotation("rabit_allreduce_ring")
    assert not isinstance(live, contextlib.nullcontext)
    with live:
        pass
    telemetry.set_enabled(False)
    off = telemetry.trace_annotation("rabit_allreduce_ring")
    assert isinstance(off, contextlib.nullcontext)
    with off:
        pass


def test_configure_from_config(telem):
    telemetry.configure(Config({"rabit_telemetry": "0"}))
    assert not telemetry.enabled()
    telemetry.configure(Config({"rabit_telemetry": "1",
                                "rabit_telemetry_buffer": "2K"}))
    assert telemetry.enabled()
    assert telemetry.stats()["capacity"] == 2048
    # a config without telemetry keys leaves the state alone
    telemetry.configure(Config({"rabit_engine": "empty"}))
    assert telemetry.enabled()
    # DMLC_ alias normalizes like every other parameter
    telemetry.configure(Config({"DMLC_TELEMETRY": "0"}))
    assert not telemetry.enabled()


# ------------------------------------------------------------ exporters


def _recorded(n=3):
    r = Recorder(capacity=64, enabled=True)
    for i in range(n):
        r.record_span("allreduce", 0.001 * (i + 1), nbytes=1 << (10 + i),
                      op="sum", method="ring", wire="bf16")
    return r.snapshot()


def test_chrome_trace_schema_and_monotonic_ts():
    snap = _recorded()
    # a second recording thread must land on its own (dense) track
    r = Recorder(capacity=8, enabled=True)
    r.record_span("a", 0.001)
    t = threading.Thread(target=lambda: r.record_span("b", 0.001))
    t.start()
    t.join()

    doc = build_chrome_trace(snap, rank=3)
    assert matches(doc, "telemetry_trace")
    meta, *events = doc["traceEvents"]
    assert meta["ph"] == "M" and meta["name"] == "process_name"
    assert all(e["ph"] == "X" for e in events)
    assert all(e["pid"] == 3 for e in events)
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    assert events[0]["dur"] == pytest.approx(0.001 * 1e6, rel=1e-6)
    assert events[0]["args"]["bytes"] == 1 << 10
    assert events[0]["args"]["method"] == "ring"
    assert events[0]["args"]["wire"] == "bf16"

    two = [e for e in build_chrome_trace(r.snapshot())["traceEvents"]
           if e["ph"] == "X"]
    assert {e["tid"] for e in two} == {0, 1}


def test_summary_totals_agree_with_counters():
    snap = _recorded(5)
    doc = build_summary(snap, rank=2, world_size=4)
    assert matches(doc, "telemetry_summary")
    assert doc["rank"] == 2 and doc["world_size"] == 4
    assert sum(c["count"] for c in doc["counters"]) == snap["recorded"] == 5
    assert sum(c["bytes"] for c in doc["counters"]) == \
        sum(s["bytes"] for s in snap["spans"])
    assert sum(c["total_s"] for c in doc["counters"]) == \
        pytest.approx(sum(s["dur"] for s in snap["spans"]))


def test_export_at_shutdown(tmp_path, monkeypatch, telem):
    monkeypatch.setenv("RABIT_TELEMETRY_EXPORT", str(tmp_path))
    telemetry.record_span("allreduce", 0.002, nbytes=4096, op="sum",
                          method="ring")
    paths = telemetry.export_at_shutdown(rank=1, world_size=2)
    assert sorted(os.path.basename(p) for p in paths) == \
        ["telemetry_summary_rank1.json", "telemetry_trace_rank1.json"]
    summary = json.loads(open(paths[0]).read())
    assert matches(summary, "telemetry_summary") and summary["rank"] == 1
    trace = json.loads(open(paths[1]).read())
    assert matches(trace, "telemetry_trace")
    # single-process runs tag files "local"; disabled exports nothing
    local = telemetry.export_at_shutdown()
    assert all("local" in p for p in local)
    telemetry.set_enabled(False)
    assert telemetry.export_at_shutdown(rank=1) == []


# -------------------------------------------------- dispatch provenance

VALID_TABLE = {
    "schema": dispatch.SCHEMA,
    "backend": "cpu",
    "table": {
        "float_sum": [
            {"max_n": 10000, "method": "tree", "wire": None},
            {"max_n": None, "method": "bidir", "wire": None},
        ],
        "other": [
            {"max_n": None, "method": "ring", "wire": None},
        ],
    },
}


@pytest.fixture
def no_table(monkeypatch):
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", "none")
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE", raising=False)
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE_MINCOUNT", raising=False)
    dispatch.clear_cache()
    yield
    dispatch.clear_cache()


@pytest.fixture
def table_file(tmp_path, monkeypatch):
    p = tmp_path / "COLLECTIVE_SWEEP_test.json"
    p.write_text(json.dumps(VALID_TABLE))
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", str(p))
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE", raising=False)
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE_MINCOUNT", raising=False)
    dispatch.clear_cache()
    yield p
    dispatch.clear_cache()


def _dispatch_rows():
    return [c for c in telemetry.snapshot()["counters"]
            if c["name"] == "dispatch"]


def test_dispatch_provenance_fallback(no_table, telem):
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(100, f32, SUM, 8)[0] == "tree"
    (row,) = _dispatch_rows()
    assert row["provenance"] == "fallback"
    assert row["method"] == "tree" and row["op"] == "sum"
    assert row["bytes"] == 400


def test_dispatch_provenance_table(table_file, telem):
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    (row,) = _dispatch_rows()
    assert row["provenance"] == "table" and row["method"] == "bidir"


def test_dispatch_provenance_explicit(no_table, telem):
    f32 = np.dtype(np.float32)
    dispatch.resolve(100, f32, SUM, 8, method="swing")
    (row,) = _dispatch_rows()
    assert row["provenance"] == "explicit" and row["method"] == "swing"


def test_dispatch_records_nothing_when_disabled(no_table):
    telemetry.reset(enabled=False)
    dispatch.resolve(100, np.dtype(np.float32), SUM, 8)
    assert telemetry.snapshot()["counters"] == []


# ------------------------------------- device collectives + jaxpr purity

needs_mesh = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")


@needs_mesh
def test_device_allreduce_records_span(no_table, telem):
    mesh = make_mesh(8)
    xs = np.ones((8, 1000), np.float32)
    out = device_allreduce(shard_over(mesh, xs), mesh, SUM)
    np.testing.assert_allclose(np.asarray(out), np.full(1000, 8.0))
    spans = [s for s in telemetry.snapshot()["spans"]
             if s["name"] == "allreduce"]
    (s,) = spans
    assert s["bytes"] == 1000 * 4 and s["op"] == "sum"
    assert s["method"] in ("tree", "ring", "bidir", "swing")
    assert s["dur"] > 0.0


@needs_mesh
def test_device_allreduce_silent_when_disabled(no_table):
    telemetry.reset(enabled=False)
    mesh = make_mesh(8)
    out = device_allreduce(shard_over(mesh, np.ones((8, 64), np.float32)),
                           mesh, SUM)
    np.testing.assert_allclose(np.asarray(out), np.full(64, 8.0))
    assert telemetry.snapshot()["spans"] == []


def _prims(jaxpr):
    """Ordered primitive names, recursing into sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, ClosedJaxpr):
                    out.extend(_prims(sub.jaxpr))
                elif isinstance(sub, Jaxpr):
                    out.extend(_prims(sub))
    return out


@needs_mesh
def test_telemetry_keeps_bucketed_step_jaxpr_pure(no_table):
    """Acceptance bar: the traced jaxpr of a bucketed MLP train step is
    IDENTICAL with telemetry off and on — spans are host-side and the
    named_scope annotations are metadata-only. jit caches are cleared
    between traces so the comparison actually retraces."""
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))
    params, x, y = mlp.make_sharded_inputs(
        mesh, batch=16, in_dim=12, hidden=8, out_dim=4, seed=7)
    step = mlp.make_train_step(mesh, lr=0.5, grad_sync="bucket")

    def trace():
        jax.clear_caches()
        return _prims(jax.make_jaxpr(step)(params, x, y).jaxpr)

    telemetry.reset(enabled=False)
    off = trace()
    telemetry.reset(capacity=256, enabled=True)
    try:
        on = trace()
    finally:
        telemetry.reset(enabled=False)
    assert off == on
    # and identical to the pre-telemetry dispatch count
    # (test_bucketing.test_bucket_reduces_dispatch_count's 6 ppermutes)
    assert off.count("ppermute") == 6


# --------------------------------------- tracker metrics + fleet table


def _send_u32(s, v):
    s.sendall(struct.pack("<I", v))


def _send_str(s, txt):
    b = txt.encode()
    _send_u32(s, len(b))
    s.sendall(b)


def _recv_all(s, n):
    out = b""
    while len(out) < n:
        chunk = s.recv(n - len(out))
        if not chunk:
            raise ConnectionError("closed")
        out += chunk
    return out


def _recv_u32(s):
    return struct.unpack("<I", _recv_all(s, 4))[0]


def _recv_str(s):
    return _recv_all(s, _recv_u32(s)).decode()


def _register(tr, task_id):
    """Speak the start command, drain the assignment, ack ready."""
    s = socket.create_connection((tr.host, tr.port), timeout=10)
    _send_u32(s, MAGIC)
    _send_str(s, "start")
    _send_str(s, task_id)
    _send_u32(s, 0)
    _send_str(s, "127.0.0.1")
    _send_u32(s, 9999)
    _send_u32(s, 0)
    _send_str(s, "")
    for _ in range(2):       # rank, world
        _recv_u32(s)
    _recv_u32(s)             # epoch
    _recv_str(s)             # coord host
    for _ in range(2):       # coord port, single_host
        _recv_u32(s)
    _recv_u32(s)             # parent
    for _ in range(_recv_u32(s)):
        _recv_u32(s)         # tree neighbors
    _recv_u32(s), _recv_u32(s)   # ring prev/next
    for _ in range(_recv_u32(s)):
        _recv_u32(s), _recv_str(s), _recv_u32(s), _recv_str(s)
    _recv_u32(s)             # naccept
    _send_u32(s, 1)          # ready ack
    s.close()


def _command(tr, cmd, task_id, payload=None):
    s = socket.create_connection((tr.host, tr.port), timeout=10)
    try:
        _send_u32(s, MAGIC)
        _send_str(s, cmd)
        _send_str(s, task_id)
        _send_u32(s, 0)
        if payload is not None:
            _send_str(s, payload)
        return _recv_u32(s)
    finally:
        s.close()


def test_tracker_metrics_command_and_fleet_table():
    """The fast wire-protocol test: metrics payloads are acked, stored
    per task_id, bad JSON is rejected without clobbering, and the fleet
    table prints when the last rank shuts down."""
    tr = Tracker(1, ready_timeout=5.0).start()
    try:
        _register(tr, "a")
        r = Recorder(capacity=8, enabled=True)
        r.record_span("allreduce", 0.002, nbytes=1 << 20, op="sum",
                      method="ring")
        doc = build_summary(r.snapshot(), rank=0, world_size=1)
        assert _command(tr, "metrics", "a", json.dumps(doc)) == 1
        assert _command(tr, "metrics", "a", "{not json") == 0
        assert _command(tr, "shutdown", "a") == 1
        assert tr.join(10)
        fleet = tr.merged_metrics()
        assert fleet is not None and matches(fleet, "telemetry_fleet")
        assert fleet["ranks"] == [0] and fleet["recorded"] == 1
        table = [m for m in tr.messages
                 if m.startswith("telemetry: 1 rank(s)")]
        assert table and "ring" in table[0] and "allreduce" in table[0]
    finally:
        tr.stop()


def test_merge_summaries_and_format():
    def summary(rank, count, dur):
        r = Recorder(capacity=8, enabled=True)
        for _ in range(count):
            r.record_span("allreduce", dur, nbytes=1 << 20, op="sum",
                          method="ring")
        return build_summary(r.snapshot(), rank=rank, world_size=2)

    fleet = merge_summaries({
        "a": summary(0, 2, 0.001),
        "b": summary(1, 3, 0.004),
        "junk": make_header("capture_status"),  # foreign doc: skipped
        "bogus": {"schema": "nope"},
    })
    assert matches(fleet, "telemetry_fleet")
    assert fleet["num_ranks"] == 2 and sorted(fleet["ranks"]) == [0, 1]
    assert fleet["recorded"] == 5
    (row,) = fleet["counters"]
    assert row["count"] == 5 and row["bytes"] == 5 << 20
    assert row["total_s"] == pytest.approx(0.014)
    assert row["max_s"] == pytest.approx(0.004)
    assert sum(row["hist_log2_us"].values()) == 5
    table = format_fleet_table(fleet)
    assert table.startswith("telemetry: 2 rank(s), 5 span(s), 0 dropped")
    assert "allreduce" in table and "ring" in table


@pytest.mark.slow
@pytest.mark.skipif(not os.path.isfile(LIB),
                    reason="native core not built")
def test_fleet_aggregation_native_cluster(tmp_path):
    """End to end: a 2-worker native cluster with telemetry on exports
    per-rank artifacts and the tracker prints the merged fleet table."""
    tr = Tracker(2).start()
    procs = []
    try:
        for tid in ("a", "b"):
            env = dict(os.environ, PYTHONPATH=ROOT,
                       RABIT_TELEMETRY="1",
                       RABIT_TELEMETRY_EXPORT=str(tmp_path))
            env.update(tr.env(tid))
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(WORKERS, "telemetry_worker.py")],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        for p in procs:
            _, err = p.communicate(timeout=120)
            assert p.returncode == 0, err.decode(errors="replace")[-2000:]
        assert tr.join(30), "tracker did not observe both shutdowns"
        fleet = tr.merged_metrics()
        assert fleet is not None
        assert sorted(fleet["ranks"]) == [0, 1]
        names = {r["name"] for r in fleet["counters"]}
        assert "engine.allreduce" in names
        assert any(m.startswith("telemetry: 2 rank(s)")
                   for m in tr.messages)
        for rank in range(2):
            sdoc = json.loads(
                (tmp_path / f"telemetry_summary_rank{rank}.json")
                .read_text())
            assert matches(sdoc, "telemetry_summary")
            assert sdoc["rank"] == rank and sdoc["world_size"] == 2
            tdoc = json.loads(
                (tmp_path / f"telemetry_trace_rank{rank}.json").read_text())
            assert matches(tdoc, "telemetry_trace")
            assert any(e.get("ph") == "X" for e in tdoc["traceEvents"])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        tr.stop()


# ------------------------------------------------------- leveled logger


def test_log_levels_and_identity(capsys):
    try:
        log.set_debug(False)
        log.clear_identity()
        log.log_debug("hidden %d", 1)
        log.log_info("hello %s", "world")
        err = capsys.readouterr().err
        assert "hidden" not in err
        assert "hello world" in err and err.startswith("[rabit_tpu ")
        log.set_debug(True)
        log.set_identity(3, 8)
        log.log_debug("traced %d", 7)
        log.log_warn("boom %d", 2)
        err = capsys.readouterr().err
        assert "traced 7" in err and "warning: boom 2" in err
        assert " r3/8 " in err
        # warn prints even with debug off
        log.set_debug(False)
        log.log_warn("still")
        assert "warning: still" in capsys.readouterr().err
    finally:
        log.set_debug(False)
        log.clear_identity()


# ------------------------------------------------- tools: schema + smoke


def test_capture_status_json_schema():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "capture_status.py"),
         "--json"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r.returncode in (0, 1), r.stderr
    doc = json.loads(r.stdout)
    assert matches(doc, "capture_status")
    assert doc["complete"] == (r.returncode == 0)
    assert isinstance(doc["missing"], dict)


def test_trace_report_smoke_and_render(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         "--smoke", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "telemetry smoke ok" in r.stdout
    summary = tmp_path / "telemetry_summary_smoke.json"
    assert summary.is_file()
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(summary)],
        capture_output=True, text=True, timeout=60, env=env, cwd=ROOT)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "allreduce" in r2.stdout and "|" in r2.stdout


def test_trace_report_rejects_unknown_schema(tmp_path):
    p = tmp_path / "weird.json"
    p.write_text(json.dumps({"schema": "someone_else/v9"}))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(p)],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r.returncode != 0


# -------------------------------------------------------- lint T001 CI


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "rabit_lint", os.path.join(ROOT, "tools", "lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_span_contract_holds_on_repo():
    lint = _load_lint()
    for rel in lint.SPAN_REQUIRED:
        issues = lint.check_file(os.path.join(ROOT, rel))
        assert not [i for i in issues if i[2] == "T001"], issues


def test_lint_flags_uninstrumented_collective(tmp_path, monkeypatch):
    lint = _load_lint()
    bare = tmp_path / "bare.py"
    bare.write_text("def device_allreduce(xs):\n    return xs\n")
    rel = os.path.relpath(str(bare), lint.REPO)
    monkeypatch.setitem(lint.SPAN_REQUIRED, rel,
                        {"device_allreduce", "vanished_entry"})
    codes = [c for (_, _, c, _) in lint.check_file(str(bare))]
    assert codes.count("T001") == 2  # missing span + missing function

    good = tmp_path / "good.py"
    good.write_text("def device_allreduce(xs):\n"
                    "    with telemetry.span('allreduce'):\n"
                    "        return xs\n")
    rel = os.path.relpath(str(good), lint.REPO)
    monkeypatch.setitem(lint.SPAN_REQUIRED, rel, {"device_allreduce"})
    assert not [c for (_, _, c, _) in lint.check_file(str(good))
                if c == "T001"]
