"""Dispatch-table contract: loader validation, resolve() fallback
semantics, the wire size gate (satellite of the adaptive-dispatch PR),
and the sweep tool's CI smoke contract.

These tests run without any mesh — resolve() is pure table/env logic —
so they stay cheap enough for the quick tier.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from rabit_tpu.ops.reducers import SUM, MAX, BITOR
from rabit_tpu.parallel import dispatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VALID_TABLE = {
    "schema": dispatch.SCHEMA,
    "backend": "cpu",
    "table": {
        "float_sum": [
            {"max_n": 10000, "method": "tree", "wire": None},
            {"max_n": 500000, "method": "bidir", "wire": None},
            {"max_n": None, "method": "swing", "wire": "int8"},
        ],
        "other": [
            {"max_n": 10000, "method": "tree", "wire": None},
            {"max_n": None, "method": "ring", "wire": None},
        ],
    },
}


@pytest.fixture
def no_table(monkeypatch):
    """Isolate from the committed repo-root artifact and env."""
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


# ---------------------------------------------------------------- loader


def test_load_table_valid(table_file):
    t = dispatch.load_table()
    assert t is not None
    assert t["float_sum"][0]["method"] == "tree"


def test_load_table_env_disable(monkeypatch, table_file):
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", "none")
    dispatch.clear_cache()
    assert dispatch.load_table() is None


@pytest.mark.parametrize("mutate", [
    lambda d: d.__setitem__("schema", "rabit_tpu.collective_sweep/v99"),
    lambda d: d.pop("table"),
    lambda d: d["table"].pop("other"),
    # last row must be open-ended (max_n null) to cover every size
    lambda d: d["table"]["float_sum"][-1].__setitem__("max_n", 999),
    lambda d: d["table"]["other"][0].__setitem__("method", "quantum"),
    lambda d: d["table"]["float_sum"][0].__setitem__("wire", "fp4"),
    # "flat" (hier degradation target) must itself be a flat method
    lambda d: d["table"]["float_sum"][0].__setitem__("flat", "hier"),
    # a table timed on another backend must not steer this one
    lambda d: d.__setitem__("backend", "tpu"),
    lambda d: d.pop("backend"),
])
def test_load_table_rejects_malformed(tmp_path, monkeypatch, mutate):
    bad = json.loads(json.dumps(VALID_TABLE))
    mutate(bad)
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", str(p))
    dispatch.clear_cache()
    assert dispatch.load_table() is None


def test_load_table_accepts_v1_schema(tmp_path, monkeypatch):
    """Committed pre-lag sweep artifacts (schema v1) must keep loading
    after the v2 bump — the lag columns are additive."""
    old = json.loads(json.dumps(VALID_TABLE))
    old["schema"] = "rabit_tpu.collective_sweep/v1"
    p = tmp_path / "COLLECTIVE_SWEEP_v1.json"
    p.write_text(json.dumps(old))
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", str(p))
    dispatch.clear_cache()
    try:
        assert dispatch.load_table() is not None
    finally:
        dispatch.clear_cache()


def test_load_table_not_json(tmp_path, monkeypatch):
    p = tmp_path / "bad.json"
    p.write_text("{truncated")
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", str(p))
    dispatch.clear_cache()
    assert dispatch.load_table() is None


def test_load_table_missing_file(monkeypatch):
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", "/nonexistent/t.json")
    dispatch.clear_cache()
    assert dispatch.load_table() is None


def test_committed_artifact_loads():
    """The repo-root artifact (if one is committed) must satisfy its own
    loader — a commit that breaks this ships a dead table."""
    newest = dispatch._newest_sweep()
    if newest is None:
        pytest.skip("no committed sweep artifact")
    dispatch.clear_cache()
    try:
        assert dispatch.load_table(newest) is not None
    finally:
        dispatch.clear_cache()


# ------------------------------------------------ remembered discovery


@pytest.fixture
def prof():
    from rabit_tpu.telemetry import profile
    profile.reset(enabled=True)
    yield profile
    profile.reset(enabled=False)


def _table_counts(profile):
    rows = [r for r in profile.snapshot()["jit_cache"]
            if r["fn"] == "dispatch_table"]
    return (rows[0]["hits"], rows[0]["misses"]) if rows else (0, 0)


@pytest.fixture
def artifacts(tmp_path, monkeypatch):
    """An empty artifacts directory and repo root for discovery, with
    no table named in the environment."""
    arts, root = tmp_path / "artifacts", tmp_path / "root"
    arts.mkdir()
    root.mkdir()
    monkeypatch.setattr(dispatch, "artifacts_dir", lambda: str(arts))
    monkeypatch.setattr(dispatch, "_repo_root", lambda: str(root))
    monkeypatch.delenv("RABIT_DISPATCH_TABLE", raising=False)
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE", raising=False)
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE_MINCOUNT", raising=False)
    dispatch.clear_cache()
    yield arts
    dispatch.clear_cache()


@pytest.fixture
def fs_calls(monkeypatch):
    """Counts every glob, stat, listing and open dispatch could make."""
    import glob
    calls = []

    def counting(name, real):
        def f(*a, **kw):
            calls.append(name)
            return real(*a, **kw)
        return f

    for mod, name in ((glob, "glob"), (glob, "iglob"),
                      (os.path, "getmtime"), (os.path, "exists"),
                      (os, "stat"), (os, "listdir"), (os, "scandir")):
        monkeypatch.setattr(mod, name, counting(name, getattr(mod, name)))
    monkeypatch.setattr(dispatch, "open", counting("open", open),
                        raising=False)
    return calls


def test_remembered_table_makes_no_filesystem_call(artifacts, fs_calls,
                                                   prof):
    (artifacts / "COLLECTIVE_SWEEP_test.json").write_text(
        json.dumps(VALID_TABLE))
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    assert {"glob", "open"} <= set(fs_calls)
    fs_calls.clear()
    for _ in range(100):
        assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    assert fs_calls == []
    assert _table_counts(prof) == (100, 1)


@pytest.mark.parametrize("reset", [dispatch.clear_cache,
                                   lambda: dispatch.epoch_reset(4)],
                         ids=["clear_cache", "epoch_reset"])
def test_reset_discovers_a_dropped_table(artifacts, fs_calls, prof, reset):
    f32 = np.dtype(np.float32)
    assert dispatch.load_table() is None
    assert dispatch.table_source() == "documented constants (no table found)"
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "ring"  # fallback
    (artifacts / "COLLECTIVE_SWEEP_test.json").write_text(
        json.dumps(VALID_TABLE))
    fs_calls.clear()
    assert dispatch.load_table() is None  # not before the reset
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "ring"
    assert fs_calls == []
    reset()
    assert dispatch.load_table() == VALID_TABLE["table"]
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    assert "glob" in fs_calls
    assert _table_counts(prof)[1] == 2  # one discovery before, one after


def test_table_env_change_takes_effect_without_clear(table_file, prof,
                                                     monkeypatch):
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", "none")
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "ring"
    assert dispatch.table_source() == (
        "documented constants (RABIT_DISPATCH_TABLE=none)")
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", str(table_file))
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    assert dispatch.table_source().startswith(f"table {table_file}")
    assert _table_counts(prof) == (0, 3)


_BOUNDARIES = (1024, 10000, dispatch.RING_MINCOUNT_DEFAULT, 500000,
               dispatch.WIRE_MINCOUNT_DEFAULT)


@pytest.mark.parametrize("source", ["table", "fallback"])
@pytest.mark.parametrize("dtype,op", [(np.float32, SUM), (np.uint32, BITOR)],
                         ids=["sum_f32", "bitor_u32"])
def test_remembered_table_resolves_as_fresh(source, dtype, op, tmp_path,
                                            monkeypatch):
    """The remembered table gives the same ``(method, wire)`` as one
    loaded afresh, at and around every row boundary of VALID_TABLE, the
    fallback crossover, the BitOR override and the wire gate."""
    p = tmp_path / "COLLECTIVE_SWEEP_test.json"
    p.write_text(json.dumps(VALID_TABLE))
    monkeypatch.setenv("RABIT_DISPATCH_TABLE",
                       str(p) if source == "table" else "none")
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "int8")
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE_MINCOUNT", raising=False)
    dt = np.dtype(dtype)
    sizes = sorted({b + d for b in _BOUNDARIES for d in (-1, 0, 1)})
    dispatch.clear_cache()
    try:
        remembered = [dispatch.resolve(n, dt, op, 8) for n in sizes]
        fresh = []
        for n in sizes:
            dispatch.clear_cache()
            fresh.append(dispatch.resolve(n, dt, op, 8))
    finally:
        dispatch.clear_cache()
    assert remembered == fresh
    # both sources were really consulted: the table's rows differ from
    # the fallback constants at 50k-500k float elements
    methods = {m for m, _ in remembered}
    if source == "table" and op == SUM:
        assert {"tree", "bidir", "swing"} <= methods
    else:
        assert methods <= {"tree", "ring"}


# ------------------------------------------------------- resolve: method


def test_resolve_fallback_thresholds(no_table):
    # pre-table behavior: tree below 32k elements, ring at/above
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(100, f32, SUM, 8)[0] == "tree"
    assert dispatch.resolve(dispatch.RING_MINCOUNT_DEFAULT - 1,
                            f32, SUM, 8)[0] == "tree"
    assert dispatch.resolve(dispatch.RING_MINCOUNT_DEFAULT,
                            f32, SUM, 8)[0] == "ring"


def test_resolve_bitor_override(no_table):
    # tree BitOR all-gathers, so big BitOR payloads go to the ring even
    # below the generic crossover
    u32 = np.dtype(np.uint32)
    assert dispatch.resolve(100, u32, BITOR, 8)[0] == "tree"
    assert dispatch.resolve(2048, u32, BITOR, 8)[0] == "ring"


def test_resolve_swing_nonpow2_degrades(no_table):
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(10**6, f32, SUM, 8, method="swing")[0] == "swing"
    assert dispatch.resolve(10**6, f32, SUM, 6, method="swing")[0] == "ring"


def test_resolve_explicit_method_passthrough(no_table):
    f32 = np.dtype(np.float32)
    groups = ((0, 1, 2, 3), (4, 5, 6, 7))  # hier needs a real grouping
    for m in dispatch.METHODS:
        assert dispatch.resolve(100, f32, SUM, 8, method=m,
                                groups=groups)[0] == m
    with pytest.raises(ValueError, match="method"):
        dispatch.resolve(100, f32, SUM, 8, method="bogus")


def test_resolve_hier_degrades_without_grouping(no_table, monkeypatch):
    """Explicit hier on a world with no usable host grouping runs the
    flat ring — the same degradation contract as swing on a
    non-power-of-two world. Degenerate groupings (all ranks one host,
    one rank per host, ragged) count as unusable."""
    monkeypatch.delenv("RABIT_HIER", raising=False)
    monkeypatch.delenv("RABIT_HIER_GROUP", raising=False)
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(100, f32, SUM, 8, method="hier")[0] == "ring"
    # all-one-host and one-rank-per-host are flat worlds
    one_host = (tuple(range(8)),)
    per_rank = tuple((i,) for i in range(8))
    ragged = ((0, 1, 2), (3, 4, 5, 6, 7))
    for g in (one_host, per_rank, ragged):
        assert dispatch.resolve(100, f32, SUM, 8, method="hier",
                                groups=g)[0] == "ring"
    # rabit_hier=0 disables the schedule even with a genuine grouping
    monkeypatch.setenv("RABIT_HIER", "0")
    good = ((0, 1, 2, 3), (4, 5, 6, 7))
    assert dispatch.resolve(100, f32, SUM, 8, method="hier",
                            groups=good)[0] == "ring"


def test_resolve_hier_table_row_consults_grouping(tmp_path, monkeypatch):
    """An auto-dispatch table row saying hier engages only when the
    grouping is genuinely two-level; otherwise the row's ``flat``
    column applies."""
    monkeypatch.delenv("RABIT_HIER", raising=False)
    monkeypatch.delenv("RABIT_HIER_GROUP", raising=False)
    monkeypatch.delenv("RABIT_DATAPLANE_WIRE", raising=False)
    table = {
        "schema": dispatch.SCHEMA,
    "backend": "cpu",
        "table": {
            "float_sum": [
                {"max_n": 10000, "method": "tree", "wire": None},
                {"max_n": None, "method": "hier", "wire": None,
                 "flat": "bidir"},
            ],
            "other": [{"max_n": None, "method": "ring", "wire": None}],
        },
    }
    p = tmp_path / "COLLECTIVE_SWEEP_hier.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", str(p))
    dispatch.clear_cache()
    try:
        f32 = np.dtype(np.float32)
        groups = ((0, 1, 2, 3), (4, 5, 6, 7))
        assert dispatch.resolve(10**6, f32, SUM, 8,
                                groups=groups)[0] == "hier"
        assert dispatch.resolve(10**6, f32, SUM, 8)[0] == "bidir"
        # grouping present but hierarchy disabled -> flat column too
        monkeypatch.setenv("RABIT_HIER", "0")
        assert dispatch.resolve(10**6, f32, SUM, 8,
                                groups=groups)[0] == "bidir"
    finally:
        dispatch.clear_cache()


def test_resolve_consults_table(table_file):
    f32 = np.dtype(np.float32)
    i32 = np.dtype(np.int32)
    assert dispatch.resolve(5000, f32, SUM, 8)[0] == "tree"
    assert dispatch.resolve(50000, f32, SUM, 8)[0] == "bidir"
    assert dispatch.resolve(10**6, f32, SUM, 8)[0] == "swing"
    # non-(float,SUM) payloads use the "other" section
    assert dispatch.resolve(50000, i32, SUM, 8)[0] == "ring"
    assert dispatch.resolve(50000, f32, MAX, 8)[0] == "ring"


# --------------------------------------------------------- resolve: wire


def test_wire_off_without_env(no_table):
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(10**7, f32, SUM, 8)[1] is None


def test_wire_env_gated_by_mincount(no_table, monkeypatch):
    """Satellite (a): a config/env-requested wire stays OFF below the
    size gate — small payloads run unquantized by default."""
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "int8")
    gate = dispatch.wire_mincount()
    assert gate == dispatch.WIRE_MINCOUNT_DEFAULT
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(gate - 1, f32, SUM, 8)[1] is None
    assert dispatch.resolve(gate, f32, SUM, 8)[1] == "int8"


def test_wire_mincount_env_override(no_table, monkeypatch):
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "bf16")
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE_MINCOUNT", "1K")
    f32 = np.dtype(np.float32)
    assert dispatch.wire_mincount() == 1024
    # method pinned to ring: auto would pick tree at these sizes and the
    # wire (a ppermute-payload codec) never engages on the tree path
    assert dispatch.resolve(1023, f32, SUM, 8, method="ring")[1] is None
    assert dispatch.resolve(1024, f32, SUM, 8, method="ring")[1] == "bf16"


def test_wire_explicit_percall_beats_gate(no_table, monkeypatch):
    """Satellite (a): explicit per-call ``wire=`` overrides the gate in
    both directions — tiny payloads CAN be quantized on request, and
    ``wire=None`` keeps a huge payload exact even with the env set."""
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "int8")
    f32 = np.dtype(np.float32)
    assert dispatch.resolve(64, f32, SUM, 8, method="ring",
                            wire="bf16")[1] == "bf16"
    assert dispatch.resolve(10**7, f32, SUM, 8, method="ring",
                            wire=None)[1] is None
    assert dispatch.resolve(10**7, f32, SUM, 8, method="ring",
                            wire="none")[1] is None


def test_wire_table_gate(table_file, monkeypatch):
    """With a table, the bucket's wire flag (did quantized beat exact at
    this size?) replaces the flat mincount gate."""
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "int8")
    f32 = np.dtype(np.float32)
    # buckets 1+2 say wire never paid; open bucket says it did
    assert dispatch.resolve(5000, f32, SUM, 8)[1] is None
    assert dispatch.resolve(50000, f32, SUM, 8)[1] is None
    assert dispatch.resolve(10**6, f32, SUM, 8)[1] == "int8"


def test_wire_explicit_mincount_beats_table(table_file, monkeypatch):
    """Precedence: an explicitly configured mincount wins over the
    table's wire column in BOTH directions — 0 forces the gate open
    where the table says wire never pays, a huge value keeps it shut
    where the table says it does."""
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "int8")
    f32 = np.dtype(np.float32)
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE_MINCOUNT", "0")
    assert dispatch.resolve(5000, f32, SUM, 8, method="ring")[1] == "int8"
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE_MINCOUNT", "1G")
    assert dispatch.resolve(10**6, f32, SUM, 8, method="ring")[1] is None


def test_wire_never_on_tree_or_nonfloat(no_table, monkeypatch):
    monkeypatch.setenv("RABIT_DATAPLANE_WIRE", "bf16")
    f32, i32 = np.dtype(np.float32), np.dtype(np.int32)
    assert dispatch.resolve(10**7, f32, SUM, 8, method="tree")[1] is None
    assert dispatch.resolve(10**7, i32, SUM, 8)[1] is None
    assert dispatch.resolve(10**7, f32, MAX, 8)[1] is None


# ------------------------------------------------------------ sweep smoke


@pytest.mark.slow
def test_sweep_smoke_emits_valid_artifact(tmp_path):
    """CI contract: ``collective_sweep.py --smoke`` must run on the CPU
    mesh and emit an artifact the dispatch loader accepts."""
    out = tmp_path / "SWEEP_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RABIT_DISPATCH_TABLE", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "collective_sweep.py"),
         "--smoke", "--world", "8", "--out", str(out)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "smoke ok" in r.stdout
    data = json.loads(out.read_text())
    assert data["schema"] == dispatch.SCHEMA
    assert data["smoke"] is True
    try:
        assert dispatch.load_table(str(out)) is not None
    finally:
        dispatch.clear_cache()
