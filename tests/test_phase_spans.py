"""Host phases (``telemetry.phase``) inside ``device_allreduce`` and
``distributed_histogram``, and the benchmark readers that map them onto a
profiler trace (``bench_harness/program_spans.py``).

- off (telemetry off, no profiler session): nothing is recorded, no
  annotation is made, and the jaxprs are those of the jitted programs;
- inside ``jax.profiler.start_trace``: one span tree per call, with
  parent ids, and no phase waits for the device;
- a recorder span mapped by the ``bench.round`` anchor lands within 50 µs
  of its ``TraceAnnotation`` on the xplane;
- the readers return None on dropped spans or unmatched rounds.
"""

import glob
import time
import types

import numpy as np
import pytest

import jax
from jax._src.array import ArrayImpl

from bench_harness import program_spans
from bench_harness import trace as T
from rabit_tpu import telemetry
from rabit_tpu.models import histogram as H
from rabit_tpu.ops.reducers import SUM
from rabit_tpu.parallel import device_allreduce, make_mesh
from rabit_tpu.parallel.collectives import shard_over

P = 4
pytestmark = pytest.mark.skipif(len(jax.devices()) < P,
                                reason=f"needs {P} virtual devices")

ALLREDUCE_TREE = {"rabit.allreduce": None,
                  "rabit.allreduce.resolve": "rabit.allreduce",
                  "rabit.dispatch.load_table": "rabit.allreduce.resolve",
                  "rabit.allreduce.enqueue": "rabit.allreduce"}


@pytest.fixture
def quiet():
    """Telemetry off and the recorder empty, before and after."""
    telemetry.reset(enabled=False)
    yield
    telemetry.reset(enabled=False)


@pytest.fixture
def inputs():
    mesh = make_mesh(P)
    rng = np.random.default_rng(0)
    xs = shard_over(mesh, rng.standard_normal((P, 4096)).astype(np.float32))
    g, h = (shard_over(mesh, rng.standard_normal((P, 500)).astype(np.float32))
            for _ in range(2))
    b = shard_over(mesh, rng.integers(0, 64, (P, 500)).astype(np.int32))
    return mesh, xs, (g, h, b)


def _calls(mesh, xs, ghb):
    ar = device_allreduce(xs, mesh, SUM)
    hist = H.distributed_histogram(*ghb, 64, mesh)
    return ar, hist


def _want(xs, ghb):
    g, h, b = (np.asarray(a) for a in ghb)
    want = sum(H.host_histogram(g[i], h[i], b[i], 64) for i in range(P))
    return np.asarray(xs).sum(0), want


@pytest.fixture
def profiling(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()


def _xplane(tmp_path) -> str:
    (f,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return f


# -- off ----------------------------------------------------------------------

def test_off_records_nothing_and_annotates_nothing(quiet, inputs,
                                                   monkeypatch):
    made = []

    class Recording:
        is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

        def __init__(self, name):
            made.append(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    mesh, xs, ghb = inputs
    ar, hist = _calls(mesh, xs, ghb)
    want_ar, want_hist = _want(xs, ghb)
    np.testing.assert_allclose(np.asarray(ar), want_ar, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hist), want_hist, rtol=1e-4,
                               atol=1e-4)
    snap = telemetry.snapshot()
    assert made == [] and snap["phases"] == [] and snap["counters"] == []
    assert telemetry.phase("rabit.x") is telemetry.NULL_SPAN


def _jaxprs(mesh, xs, ghb):
    ar = jax.make_jaxpr(lambda x: device_allreduce(x, mesh, SUM))(xs)
    hist = jax.make_jaxpr(lambda g, h, b: H.distributed_histogram(
        g, h, b, 64, mesh))(*ghb)
    return str(ar), str(hist)


def test_jaxprs_unchanged_by_phases(quiet, inputs, tmp_path):
    """The phases are host-side: the traced programs are the same off,
    inside a profiler session, and with telemetry on; and the public
    histogram traces to its jitted program alone."""
    mesh, xs, ghb = inputs
    off = _jaxprs(mesh, xs, ghb)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _jaxprs(mesh, xs, ghb)
    finally:
        jax.profiler.stop_trace()
    assert traced == off
    telemetry.set_enabled(True)
    on_hist = str(jax.make_jaxpr(lambda g, h, b: H.distributed_histogram(
        g, h, b, 64, mesh))(*ghb))
    assert on_hist == off[1]
    inner = str(jax.make_jaxpr(lambda g, h, b: H._distributed_histogram(
        g, h, b, 64, mesh))(*ghb))
    assert inner == off[1]


# -- inside a profiler session ------------------------------------------------

def _trees(phases):
    """{root id: [(name, parent name)]} of the recorded phases."""
    by_id = {p["id"]: p for p in phases}

    def root(p):
        while p["parent"]:
            p = by_id[p["parent"]]
        return p["id"]

    out = {}
    for p in phases:
        parent = by_id[p["parent"]]["name"] if p["parent"] else None
        out.setdefault(root(p), []).append((p["name"], parent))
    return out


def test_profiler_session_records_one_tree_per_call(quiet, inputs,
                                                    profiling):
    mesh, xs, ghb = inputs
    for _ in range(3):
        jax.block_until_ready(_calls(mesh, xs, ghb))
    snap = telemetry.snapshot()
    trees = list(_trees(snap["phases"]).values())
    assert len(trees) == 6
    ar = [t for t in trees if ("rabit.allreduce", None) in t]
    hist = [t for t in trees if ("rabit.hist.enqueue", None) in t]
    assert len(ar) == len(hist) == 3
    for t in ar:
        assert sorted(t) == sorted(ALLREDUCE_TREE.items())
    assert all(t == [("rabit.hist.enqueue", None)] for t in hist)
    by = {p["name"]: p for p in snap["phases"]}
    assert by["rabit.allreduce"]["bytes"] == 4096 * 4
    for p in snap["phases"]:
        assert p["dur"] > 0 and p["start"] <= time.perf_counter()
        if p["parent"]:
            parent = next(q for q in snap["phases"] if q["id"] == p["parent"])
            assert parent["start"] <= p["start"]
            assert p["start"] + p["dur"] <= parent["start"] + parent["dur"]
    # the counter rows count the phases; the blocking span stays off
    rows = {c["name"]: c for c in snap["counters"]}
    assert {n: rows[n]["count"] for n in [*ALLREDUCE_TREE,
                                          "rabit.hist.enqueue"]} == \
        dict.fromkeys([*ALLREDUCE_TREE, "rabit.hist.enqueue"], 3)
    assert snap["spans"] == [] and snap["phases_dropped"] == 0


@pytest.mark.parametrize("layout", ["flat", "dense"])
def test_hist_enqueue_records_the_bytes_handed(quiet, layout):
    """rabit.hist.enqueue's bytes are those of the three arrays handed,
    in the flat form ([p, n*F] each) and the dense one (bins [p, n, F],
    weights [p, n])."""
    mesh = make_mesh(P)
    rng = np.random.default_rng(1)
    n, nfeat, max_bin = 300, 6, 16
    bins = np.arange(nfeat) * max_bin + rng.integers(0, max_bin,
                                                     (P, n, nfeat))
    g, h = rng.standard_normal((2, P, n)).astype(np.float32)
    if layout == "flat":
        g, h = (np.repeat(v, nfeat, axis=1) for v in (g, h))
        bins = bins.reshape(P, -1)
    ghb = [shard_over(mesh, a) for a in (g, h, bins.astype(np.int32))]
    telemetry.reset(enabled=True)
    jax.block_until_ready(H.distributed_histogram(*ghb, nfeat * max_bin,
                                                  mesh))
    (ph,) = [p for p in telemetry.snapshot()["phases"]
             if p["name"] == "rabit.hist.enqueue"]
    per_row = 4 * nfeat * (3 if layout == "flat" else 1) + \
        (0 if layout == "flat" else 8)
    assert ph["bytes"] == sum(a.nbytes for a in ghb) == P * n * per_row


def test_explicit_parent_and_thread_nesting(quiet, profiling):
    with telemetry.phase("a") as a:
        with telemetry.phase("b"):
            with telemetry.phase("c", parent=a):
                pass
    ph = {p["name"]: p for p in telemetry.snapshot()["phases"]}
    assert ph["a"]["parent"] == 0
    assert ph["b"]["parent"] == ph["c"]["parent"] == ph["a"]["id"]


def test_no_phase_waits_for_the_device(quiet, inputs, monkeypatch,
                                       profiling):
    def refuse(*a, **k):
        raise AssertionError("a phase waited for the device")

    mesh, xs, ghb = inputs
    jax.block_until_ready(_calls(mesh, xs, ghb))   # compiled before
    telemetry.reset(enabled=False)
    with monkeypatch.context() as m:
        m.setattr(ArrayImpl, "block_until_ready", refuse)
        m.setattr(jax, "block_until_ready", refuse)
        outs = _calls(mesh, xs, ghb)
    assert len(telemetry.snapshot()["phases"]) == 5
    want_ar, want_hist = _want(xs, ghb)
    np.testing.assert_allclose(np.asarray(outs[0]), want_ar, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(outs[1]), want_hist, rtol=1e-4,
                               atol=1e-4)


def test_phases_fold_into_exact_counters_past_the_store(quiet, monkeypatch):
    """More phases than the store keeps: the oldest are dropped and
    counted, the counter rows stay exact."""
    from rabit_tpu.telemetry import recorder
    monkeypatch.setattr(recorder, "PHASE_CAPACITY", 8)
    telemetry.reset(enabled=True)
    for i in range(21):
        with telemetry.phase("rabit.x", nbytes=10):
            pass
        if i == 9:
            assert telemetry.counter_rows("rabit.x")[0]["count"] == 10
    snap = telemetry.snapshot()
    (row,) = [c for c in snap["counters"] if c["name"] == "rabit.x"]
    assert row["count"] == 21 and row["bytes"] == 210
    assert len(snap["phases"]) == 8 and snap["phases_dropped"] == 13
    assert row["total_s"] >= sum(p["dur"] for p in snap["phases"])


# -- onto the trace's clock ---------------------------------------------------

def test_mapped_phase_lands_on_its_annotation(quiet, inputs, tmp_path):
    """The harness's rounds: the recorder's rabit.allreduce phases,
    mapped by the bench.round anchor, against the same phases'
    TraceAnnotations on the xplane."""
    mesh, xs, ghb = inputs
    jax.block_until_ready(_calls(mesh, xs, ghb))
    records = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(20):
                ts = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.round"):
                    device_allreduce(xs, mesh, SUM).block_until_ready()
                records.append({"start": ts, "t": time.perf_counter() - ts})
    finally:
        jax.profiler.stop_trace()
    path = _xplane(tmp_path)
    run = types.SimpleNamespace(entry="allreduce", records=records,
                                trace=T.load(path))
    off = program_spans.offset_ns(run)
    assert off is not None
    mapped = sorted(p["start"] * 1e9 + off
                    for p in program_spans.phases(run)
                    if p["name"] == "rabit.allreduce")
    from jax.profiler import ProfileData
    on_trace = sorted(ev.start_ns for plane in ProfileData.from_file(path).planes
                      if plane.name.startswith("/host:")
                      for line in plane.lines for ev in line.events
                      if ev.name == "rabit.allreduce")
    assert len(mapped) == len(on_trace) == 20
    assert max(abs(a - b) for a, b in zip(mapped, on_trace)) < 50e3


# -- the readers on made-up runs ----------------------------------------------

def _phase(i, name, start, dur, parent=0):
    return {"id": i, "parent": parent, "name": name, "start": start,
            "dur": dur, "bytes": 0}


def _run(monkeypatch, phases, dropped=0, rounds=2, host_rounds=None,
         ops=((10.0e9 + 0.2e6, 10.0e9 + 0.8e6),), names=None):
    """Two rounds of 1 ms from perf_counter 5.0 s, on a trace whose clock
    reads 10.0 s there; one device with ``ops``."""
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "phases": phases, "phases_dropped": dropped})
    records = [{"start": 5.0 + 1e-3 * r, "t": 1e-3} for r in range(rounds)]
    starts = [10.0e9 + 1e6 * r for r in range(
        rounds if host_rounds is None else host_rounds)]
    host = [(s, s + 1e6, "bench.round") for s in starts] + \
        [(10.0e9, 10.0e9 + 1e6 * rounds, "bench.window")]
    names = names or [f"rabit_hist_build.{i}" for i in range(len(ops))]
    tr = T.Trace({0: list(ops)}, {0: names}, {0: [False] * len(ops)}, host)
    return types.SimpleNamespace(entry="allreduce", records=records,
                                 trace=tr)


RESOLVE = [_phase(1, "rabit.allreduce.resolve", 5.0 + 0.1e-3, 0.4e-3),
           _phase(2, "rabit.allreduce.resolve", 5.0 + 1.1e-3, 0.2e-3)]


def test_readers_on_a_made_up_window(monkeypatch):
    run = _run(monkeypatch, RESOLVE)
    assert program_spans.median_us(run, "allreduce",
                                   "rabit.allreduce.resolve") == \
        pytest.approx(300.0)
    assert program_spans.offset_ns(run) == pytest.approx(5.0e9)
    # resolve covers 0.1-0.5 ms and 1.1-1.3 ms of a 2 ms window; the
    # device runs 0.2-0.8 ms: idle inside resolve is 0.1 + 0.2 ms
    assert program_spans.idle_inside(run, "allreduce",
                                      "rabit.allreduce.resolve") == \
        pytest.approx(15.0)
    run.entry = "histogram"
    assert program_spans.kernel_ms(run, "histogram",
                                   "rabit_hist_build") == pytest.approx(0.3)
    assert program_spans.median_us(run, "allreduce",
                                   "rabit.allreduce.resolve") is None


def test_kernel_ms_reads_only_the_named_kernel(monkeypatch):
    run = _run(monkeypatch, RESOLVE, ops=((10.0e9, 10.0e9 + 0.5e6),
                                          (10.0e9 + 0.5e6, 10.0e9 + 2e6)),
               names=["_histogram_tpu_impl.1", "reduce.2"])
    assert program_spans.kernel_ms(run, "allreduce",
                                   "rabit_hist_build") is None


@pytest.mark.parametrize("case", ["dropped", "unmatched", "none_recorded",
                                  "no_trace"])
def test_readers_return_none(monkeypatch, case):
    run = _run(monkeypatch, [] if case == "none_recorded" else RESOLVE,
               dropped=int(case == "dropped"),
               host_rounds=1 if case == "unmatched" else None)
    if case == "no_trace":
        run.trace = None
    idle = program_spans.idle_inside(run, "allreduce",
                                     "rabit.allreduce.resolve")
    med = program_spans.median_us(run, "allreduce", "rabit.allreduce.resolve")
    assert idle is None
    assert (med is None) == (case in ("dropped", "none_recorded"))
