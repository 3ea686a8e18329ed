"""Tests of the benchmark harness, on the CPU at its rehearsal sizes.

    python -m pytest bench_harness/tests -q

They drive every cell of BENCHMARK.json through the same code a chip run
takes (``--rehearse``), see the control and the planted faults fail the
correctness check, reduce a trace recorded on the chip, and hold
BENCHMARK.json to the contract's names and keys.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
HARNESS = os.path.dirname(HERE)
ROOT = os.path.dirname(HARNESS)

from bench_harness import peaks, reference, work  # noqa: E402
from bench_harness import trace as T  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2 ** 31 + 12345      # past 32 signed bits, as the driver's are


def _env(pythonpath: str | None = ROOT) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return env


def _run(root: str, workload: str, *extra, env=None):
    cmd = [sys.executable, os.path.join(root, "bench_harness", "run.py"),
           "--workload", workload, "--seed", str(SEED), "--seconds", "1",
           *extra]
    return subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          cwd=root, env=env or _env())


def _line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _reported(workload: str, kind: str) -> set:
    return {m["name"] for m in BENCH[kind]
            if workload in m.get("workloads", [workload])}


# -- every cell, rehearsed through the harness --------------------------------

@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearse_cell(workload, trace):
    out = _line(_run(ROOT, workload, "--trace", trace, "--rehearse"))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    if trace == "0":
        # every end-to-end metric the cell owns; no device on the CPU, so
        # the trace readers there find nothing and stay silent
        assert set(out["metrics"]) == _reported(workload, "end_to_end")
    else:
        assert set(out["metrics"]) <= _reported(workload, "per_layer")
        assert {"busy_s", "window_s"} <= set(out["device"])
    assert out["device"]["count"] >= 1


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    """The configuration's lower-precision path (bf16 histogram, bf16
    wire) must come out as not correct."""
    out = _line(_run(ROOT, workload, "--trace", "0", "--rehearse",
                     "--control"))
    assert out["correct"] is False and out["failed"] > 0


def test_refuses_cpu():
    proc = _run(ROOT, CELLS[0], "--trace", "0")
    assert proc.returncode != 0 and "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench_harness/
    cannot run: no result, non-zero exit."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HARNESS, tmp_path / "bench_harness",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), CELLS[0], "--trace", "0", "--rehearse",
                env=_env(pythonpath=None))
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_new_cell_is_files_and_entries_only(tmp_path):
    """A later PR adds a configuration, a mix and a metric as new files
    plus entries in BENCHMARK.json, and edits nothing else."""
    shutil.copytree(HARNESS, tmp_path / "bench_harness",
                    ignore=shutil.ignore_patterns("__pycache__"))
    h = tmp_path / "bench_harness"
    cfg = json.loads((h / "configs" / "higgs.json").read_text())
    cfg["rehearsal"] = {"rows": 585, "method": "pallas"}
    (h / "configs" / "higgs_half.json").write_text(json.dumps(cfg))
    mix = {"entry": "histogram", "grad_pool": 2,
           "tree": {"level": 1, "trees": 2, "split_seed": 7}}
    (h / "traffic" / "pairs.json").write_text(json.dumps(mix))
    (h / "metrics" / "rounds_done.py").write_text(
        "def read(run):\n    return len(run.records)\n")
    (h / "limits" / "higgs_pairs.json").write_text(
        (h / "limits" / "higgs_deep_nodes.json").read_text())
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="higgs_half",
                                 file="bench_harness/configs/higgs_half.json"))
    bench["workloads"].append({"name": "higgs_pairs", "config": "higgs_half",
                               "traffic": "pairs", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "rounds_done", "unit": "rounds",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["higgs_pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _line(_run(str(tmp_path), "higgs_pairs", "--trace", "0",
                     "--rehearse"))
    assert out["correct"] is True
    assert out["metrics"]["rounds_done"]["value"] == out["attempted"]


# -- the dense layout, made ready for a wide build added later -----------------

def _dense_as_entries(orig):
    """Test-only stand-in for a program that takes the dense form: it
    flattens [p, n, F] bins and [p, n] weights to the entries form and
    calls the program; the entries form passes through."""
    def f(grad, hess, bins, nbins, mesh, axis="workers", method="auto",
          precision="high"):
        import jax.numpy as jnp
        if bins.ndim == grad.ndim + 1:
            grad, hess = (jnp.broadcast_to(v[..., None], bins.shape)
                          .reshape(v.shape[0], -1) for v in (grad, hess))
            bins = bins.reshape(bins.shape[0], -1)
        return orig(grad, hess, bins, nbins, mesh, axis, method,
                    precision=precision)
    return f


def _add_dense_cell(root) -> None:
    """A checkout's benchmark under ``root`` with one more cell, made as a
    later PR makes it, of new files and entries only: an epsilon-shaped
    configuration in the dense layout, the level mix, a limits file, and
    the cell's name in the lists of rows_per_s and the histogram
    per-layer metrics."""
    shutil.copytree(HARNESS, root / "bench_harness",
                    ignore=shutil.ignore_patterns("__pycache__"))
    h = root / "bench_harness"
    cfg = json.loads((h / "configs" / "epsilon.json").read_text())
    del cfg["wire"]
    cfg.update(rows=100_000, layout="dense", label_rate=0.5, margin_std=1.0,
               precision="high", control={"precision": "fast"},
               reduced={"rows": "one rank of four"},
               rehearsal={"rows": 300, "features": 32, "method": "pallas"})
    (h / "configs" / "epsilon_dense.json").write_text(json.dumps(cfg))
    (h / "limits" / "epsilon_wide_build.json").write_text(
        (h / "limits" / "higgs_level.json").read_text())
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][1], name="epsilon_dense",
                                 file="bench_harness/configs/epsilon_dense.json",
                                 reduced=["rows"]))
    bench["workloads"].append({"name": "epsilon_wide_build",
                               "config": "epsilon_dense", "traffic": "level",
                               "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "higgs_level" in m.get("workloads", []) and \
                m["name"] != "round_ms_p95.level":
            m["workloads"].append("epsilon_wide_build")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.mark.parametrize("case", ["sound", "control", "_hist_drop_half",
                                  "_hist_alter"])
def test_dense_cell_rehearses(tmp_path, monkeypatch, case):
    """The dense cell runs through the histogram entry with the stand-in
    underneath, reports rows_per_s and setup_s, and comes out correct;
    its check refuses the control and the planted faults."""
    import importlib.util
    from rabit_tpu.models import histogram as H
    _add_dense_cell(tmp_path)
    spec = importlib.util.spec_from_file_location(
        "dense_bench_run", tmp_path / "bench_harness" / "run.py")
    R = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(R)
    f = _dense_as_entries(H.distributed_histogram)
    if case.startswith("_hist"):
        f = globals()[case](f)
    monkeypatch.setattr(H, "distributed_histogram", f)
    monkeypatch.setattr(sys, "path", list(sys.path))
    out = R.run("epsilon_wide_build", SEED, 0.5, False, rehearse=True,
                control=case == "control")
    assert out["correct"] is (case == "sound")
    if case == "sound":
        assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
        assert out["metrics"]["rows_per_s"]["value"] > 0


def test_dense_layout_is_entries_reshaped():
    """For one seed the dense inputs are the entries inputs reshaped and
    un-broadcast, and the reference gives both the same histogram, in
    one block of rows or in many."""
    import functools
    import jax
    from bench_harness.entries.histogram import LAYOUTS, _gen
    from bench_harness.run import seed_key
    f = 48
    kw = dict(sizes=(37, 300), features=f, max_bin=256, label_rate=0.5,
              margin_std=1.0, pool=2)
    (eb, eg, eh), (db, dg, dh) = (
        jax.device_get(jax.jit(functools.partial(_gen, layout=lay, **kw))(
            seed_key(SEED))) for lay in LAYOUTS)
    for j, n in enumerate(kw["sizes"]):
        assert db[j].shape == (1, n, f) and dg[0][j].shape == (1, n)
        assert np.array_equal(db[j].reshape(1, -1), eb[j])
        for k in range(kw["pool"]):
            for e, d in ((eg, dg), (eh, dh)):
                assert np.array_equal(np.repeat(d[k][j], f, axis=1), e[k][j])
            want = reference.histogram(eb[j], eg[k][j], eh[k][j], f * 256)
            got = reference.histogram(db[j], dg[k][j], dh[k][j], f * 256)
            assert np.array_equal(got, want)
            np.testing.assert_allclose(
                reference.histogram(db[j], dg[k][j], dh[k][j], f * 256,
                                    block_rows=16), want, rtol=1e-12)


def _histogram_cell(workload: str, **cfg):
    from bench_harness import run as R
    from bench_harness.entries import histogram as E
    from rabit_tpu.parallel import make_mesh
    cell = R.load_cell(workload, rehearse=True)
    return E.build({**cell["config"], **cfg}, cell["traffic"],
                   R.seed_key(SEED), np.random.default_rng([SEED, 0]),
                   make_mesh(1))


# sha256 of the entries layout's node arrays and row counts at the
# rehearsal sizes and SEED, taken before the dense layout came: adding it
# left every input of the existing cells as it was, to the bit
ENTRIES_DIGESTS = {
    "higgs_level":
        "1fafc98f59ecc5acaa50f7c59036bb92134e910c1281c42b0f475813d860a573",
    "higgs_deep_nodes":
        "e29ffe6b58f9054f09abad6e83c4f050c7f1636c0614f197657a14529ac9f56f",
}


@pytest.mark.parametrize("workload", sorted(ENTRIES_DIGESTS))
def test_entries_layout_inputs_unchanged(workload):
    import hashlib
    c = _histogram_cell(workload)
    h = hashlib.sha256()
    for node in c.nodes:
        for a in node:
            a = np.asarray(a)
            h.update(repr((a.shape, a.dtype.str)).encode())
            h.update(a.tobytes())
    h.update(repr(c.rows).encode())
    assert h.hexdigest() == ENTRIES_DIGESTS[workload]


@pytest.mark.parametrize("layout", ["entries", "dense"])
def test_recorded_bytes(monkeypatch, layout):
    """Each call records the bytes of the inputs it hands: 12 B per entry
    in the entries layout, 4 B per entry + 8 B per row in the dense."""
    from bench_harness import run as R
    from rabit_tpu.models import histogram as H
    monkeypatch.setattr(H, "distributed_histogram",
                        _dense_as_entries(H.distributed_histogram))
    c = _histogram_cell("higgs_deep_nodes", layout=layout)
    rec = {}
    nodes = [i for i, _ in c.round(0, rec, R._annotator(False))]
    f = c.features
    per_row = 12 * f if layout == "entries" else 4 * f + 8
    assert rec["bytes"] == [c.rows[i] * per_row for i in nodes]


def test_unknown_layout_refused():
    with pytest.raises(ValueError, match="layout"):
        _histogram_cell("higgs_level", layout="csr")


# -- planted faults: the timed path broken underneath -------------------------

def _inproc(monkeypatch, workload, target, attr, wrap):
    from bench_harness import run as R
    import importlib
    mod = importlib.import_module(target)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    return R.run(workload, SEED, 0.5, False, rehearse=True)


def _hist_drop_half(orig):
    def f(grad, hess, bins, nbins, mesh, axis="workers", method="auto",
          precision="high"):
        n = grad.shape[1] // 2
        return 2.0 * orig(grad[:, :n], hess[:, :n], bins[:, :n], nbins,
                          mesh, axis, method, precision=precision)
    return f


def _hist_alter(orig):
    def f(*a, **k):
        return orig(*a, **k).at[0, 0].add(1.0)
    return f


def _ar_no_exchange(orig):
    def f(xs, mesh, op, **k):
        from jax.sharding import PartitionSpec as P
        from rabit_tpu.parallel.collectives import unchecked_shard_map
        ax = mesh.axis_names[0]
        return unchecked_shard_map(lambda x: x[0], mesh=mesh,
                                   in_specs=P(ax), out_specs=P())(xs)
    return f


def _ar_drop_half(orig):
    def f(xs, mesh, op, **k):
        import jax.numpy as jnp
        p = xs.shape[0]
        keep = (jnp.arange(p) < p // 2).astype(xs.dtype)[:, None]
        return 2.0 * orig(xs * keep, mesh, op, **k)
    return f


def _ar_alter(orig):
    def f(*a, **k):
        return orig(*a, **k).at[0].add(1.0)
    return f


HIST = "rabit_tpu.models.histogram", "distributed_histogram"
AR = "rabit_tpu.parallel.collectives", "device_allreduce"
FAULTS = [(w, *HIST, f) for w in ("higgs_level", "higgs_deep_nodes")
          for f in (_hist_drop_half, _hist_alter)] + \
    [("epsilon_tree_allreduce_4chip", *AR, f)
     for f in (_ar_no_exchange, _ar_drop_half, _ar_alter)]


@pytest.mark.parametrize("workload,target,attr,wrap", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, _, _, f in FAULTS])
def test_fault_fails_check(monkeypatch, workload, target, attr, wrap):
    out = _inproc(monkeypatch, workload, target, attr, wrap)
    assert out["correct"] is False


# -- work counts, peaks, reference --------------------------------------------

def test_hist_bytes_hand_checked():
    # higgs_level, entries: 36,750,000 entries x (4 + 4 + 4) B
    # + 7,168 bins x 2 x 4 B
    assert work.hist_bytes(36_750_000 * 12, 7168) == 441_000_000 + 57_344
    # one rank of epsilon, dense: 100,000 x 2,000 entries x 4 B
    # + 100,000 rows x (4 + 4) B + 512,000 bins x 2 x 4 B,
    # 0.983 ms at the v5e's 819 GB/s
    nbytes = work.hist_bytes(100_000 * 2_000 * 4 + 100_000 * 8, 512_000)
    assert nbytes == 804_896_000
    hbm = peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"]
    assert round(nbytes / hbm * 1e3, 3) == 0.983


def test_allreduce_bus_bytes_hand_checked():
    # one epsilon tree, 131,072,000 B per rank over 4 ranks: x 6/4
    assert work.allreduce_bus_bytes(131_072_000, 4) == 196_608_000
    assert work.allreduce_bus_bytes(1000, 1) == 0


def test_tree_nodes():
    """Smaller siblings of fixed trees: arbitrary row counts, the same
    for every run seed, each at most half its parent."""
    from bench_harness.entries.histogram import tree_nodes
    tree = {"level": 5, "trees": 2, "split_seed": 22, "split_bin": [1, 255]}
    got = tree_nodes(1_312_500, tree, 256)
    assert got == tree_nodes(1_312_500, tree, 256)
    assert [len(t) for t in got] == [16, 16]
    assert all(1 <= n <= 1_312_500 // 2 for t in got for n in t)
    assert any(n * 28 % 16384 for t in got for n in t)
    assert len({n for t in got for n in t}) == 32
    assert tree_nodes(1000, {"level": 0, "trees": 1}, 256) == [[1000]]
    # one split at bin 64 of 256: a quarter left, the smaller sibling
    assert tree_nodes(1000, {"level": 1, "trees": 1, "split_bin": [64, 64]},
                      256) == [[250]]


def test_peaks_refuse_unknown_kind():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v99")


def test_reference_histogram_and_rel_err():
    bins = np.array([0, 2, 2, 1])
    got = reference.histogram(bins, np.array([1., 2., 3., 4.]),
                              np.ones(4), 3)
    assert got.tolist() == [[1, 1], [4, 1], [5, 2]]
    # dense: two rows of two entries, each row's weight on both
    dense = reference.histogram(np.array([[[0, 2], [2, 1]]]),
                                np.array([[1., 3.]]), np.ones((1, 2)), 3)
    assert dense.tolist() == [[1, 1], [3, 1], [4, 2]]
    assert reference.rel_err(got, got) == 0
    assert reference.rel_err(got[:2], got) == float("inf")
    assert reference.rel_err(got * np.nan, got) == float("inf")


# -- trace reduction ----------------------------------------------------------

def test_merge_union():
    iv = np.array([[0, 10], [5, 12], [20, 30], [21, 22]], float)
    assert T.merge(iv).tolist() == [[0, 12], [20, 30]]
    assert T.union_ns(iv) == 22
    assert T.is_collective("%psum.1 = f32[8]{0} all-reduce(f32[8]{0} %x)")
    assert T.is_collective(
        "%collective-permute-done.3 = f32[8]{0:T(256)} collective-permute-"
        "done((f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) %collective-permute-start)")
    assert T.is_collective("%cps = (f32[8]{0}, f32[8]{0}, u32[]{:S(2)}) "
                           "collective-permute-start(f32[8]{0} %x)")
    assert not T.is_collective(
        "%_histogram_tpu_impl.1 = f32[4,56,128]{2,1,0} custom-call(s32[9] "
        "%b), custom_call_target=\"tpu_custom_call\"")
    assert T.short_name("%fusion.3 = f32[8]{0} fusion(f32[8] %a)") == \
        "fusion.3"


TRACES = sorted(f for f in os.listdir(HERE) if f.endswith(".xplane.pb"))


@pytest.mark.parametrize("name", TRACES)
def test_recorded_trace(name):
    """A trace recorded on the chip by record_trace.py: every device the
    mesh held has ops inside the window, busy under the window, and on
    several chips the allreduce's collectives are found."""
    chips = int(re.search(r"_(\d+)chip", name).group(1))
    tr = T.load(os.path.join(HERE, name))
    assert len(tr.devices) == chips
    win = tr.window_ns()
    assert win is not None and win[1] > win[0]
    for d in tr.devices:
        busy = tr.busy_ns(d)
        assert 0 < busy < win[1] - win[0]
        assert tr.busy_ns(d, "compute") > 0
        if chips > 1:
            assert tr.busy_ns(d, "collective") > 0
    times = T.device_times(tr, 0.0)
    assert 0 < times["busy_s"] < times["window_s"]
    bd = T.breakdown(tr)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(g[1] > 0 for g in bd["idle_gaps"])


# -- BENCHMARK.json against the contract's rules ------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_names_and_keys():
    assert set(BENCH) == KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(_line_ok(w) for w in BENCH["command"])
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        f = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        assert f["source"] == c["source"]
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert _line_ok(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(HARNESS, "traffic",
                                           w["traffic"] + ".json"))
        lim = json.load(open(os.path.join(HARNESS, "limits",
                                          w["name"] + ".json")))
        assert all(v["limit"] is not None for v in lim.values())
        reported = _reported(w["name"], "end_to_end")
        assert "setup_s" in reported and len(reported) >= 2
        assert _reported(w["name"], "per_layer")
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line_ok(m["layer"]) and m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(HARNESS, "metrics",
                                           m["name"] + ".py"))
        assert all(w in CELLS for w in m.get("workloads", []))
    for c in cfgs:
        assert any(w["config"] == c for w in BENCH["workloads"])
