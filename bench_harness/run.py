"""The benchmark's one command.

    python3 bench_harness/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything about a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic mix in ``traffic/<traffic>.json`` (whose ``entry`` names the
round driver in ``entries/<entry>.py``), its correctness limits in
``limits/<workload>.json`` and each metric's reader in
``metrics/<metric>.py``. Adding a cell, a configuration, a mix or a
metric adds files and entries; no file here changes.

A run is one process on the chip: it places JAX's compile cache in the
checkout, refuses anything but a TPU with the chips the cell asks for,
makes its data on the device from ``--seed``, warms the cell's shapes
(all of that is ``setup_s``), runs closed-loop rounds for ``--seconds``,
checks a seeded sample of the window's results against the plain
reference, and prints one JSON line. ``--trace 1`` profiles a window of
at most ``TRACE_WINDOW_S`` seconds and reports the per-layer metrics.
``--rehearse`` (tests only) runs on the CPU at the configuration's and
mix's ``rehearsal`` sizes; ``--control`` runs the program's own
lower-precision path, which the check must refuse.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_WINDOW_S = 4.0
SAMPLE_ROUNDS = 2          # rounds of the window whose results are checked
WARM_ROUNDS = 2            # whole rounds after every shape is warm


class RunError(RuntimeError):
    """The run cannot measure: no result line is printed."""


def _load_json(*parts):
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, rehearse: bool = False) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration and
    traffic mix, rehearsal sizes applied where asked."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = _load_json(HERE, "configs", w["config"] + ".json")
    traffic = _load_json(HERE, "traffic", w["traffic"] + ".json")
    if rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return {"bench": bench, "workload": w, "config": cfg,
            "traffic": traffic}


def seed_key(seed: int):
    """A threefry key from any whole number (the driver's seeds pass 32
    signed bits), through numpy's SeedSequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


class Reservoir:
    """A uniform sample of ``k`` rounds of the window, drawn from the seed
    as the rounds pass (the outputs of rounds not kept are freed)."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.items, self.seen = k, rng, [], 0

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _report_gc(gcs, longest) -> None:
    """Python's collections inside the window, on stderr: how many, the
    longest, and how much of the longest round they took."""
    if not gcs:
        print("# gc: no collection in the window", file=sys.stderr)
        return
    s, e, g = max(gcs, key=lambda x: x[1] - x[0])
    a, b = longest["start"], longest["start"] + longest["t"]
    inside = sum(max(0.0, min(b, e2) - max(a, s2)) for s2, e2, _ in gcs)
    print(f"# gc: {len(gcs)} collections, "
          f"{sum(e2 - s2 for s2, e2, _ in gcs) * 1e3:.3f} ms in all, longest "
          f"{(e - s) * 1e3:.3f} ms (generation {g}); "
          f"{inside * 1e3:.3f} ms inside the longest round", file=sys.stderr)


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, control: bool = False,
        t_start: float | None = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import numpy as np

    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(workload, rehearse)
    w, cfg, traffic = cell["workload"], cell["config"], cell["traffic"]
    if rehearse:
        os.environ["RABIT_PALLAS_INTERPRET"] = "1"
    # libtpu would log to a fixed /tmp path; a run writes only its
    # checkout and the temp, home and cache directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if not rehearse:
        # a fixed directory inside the checkout: the path is part of the
        # cache key, and nothing is shared with another checkout
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    t_devices = time.perf_counter()
    platform = devs[0].platform
    if platform != "tpu" and not rehearse:
        raise RunError(f"no TPU: JAX found {platform!r}; refusing to "
                       "measure anywhere else")
    if len(devs) < w["chips"]:
        raise RunError(f"{workload} needs {w['chips']} chips, JAX found "
                       f"{len(devs)}")
    sys.path.insert(0, ROOT)
    from rabit_tpu.parallel import make_mesh

    mesh = make_mesh(w["chips"])
    entry = _load_module(os.path.join(HERE, "entries",
                                      traffic["entry"] + ".py"),
                         "bench_entry_" + traffic["entry"])
    limits = _load_json(HERE, "limits", workload + ".json")
    rng = np.random.default_rng([seed, 0])
    c = entry.build(cfg, traffic, seed_key(seed), rng, mesh, control)
    t_built = time.perf_counter()
    c.warm()
    for r in range(WARM_ROUNDS):
        c.round(r, {}, _annotator(False))
    setup_s = time.perf_counter() - t_start
    print(f"# setup {setup_s:.3f} s: to devices {t_devices - t_start:.3f}, "
          f"data {t_built - t_devices:.3f}, warm-up {t_start + setup_s - t_built:.3f}",
          file=sys.stderr)

    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    annotate = _annotator(trace)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    records = []
    sample = Reservoir(SAMPLE_ROUNDS, np.random.default_rng([seed, 1]))
    compiles = []      # JAX trace/compile events inside the window: none due

    def on_event(name, secs, **kw):
        if name.startswith("/jax/core/compile/"):
            compiles.append(name)

    gcs = []           # (start, end, generation) of every collection

    def on_gc(phase, info):
        if phase == "start":
            on_gc.t = time.perf_counter()
        else:
            gcs.append((on_gc.t, time.perf_counter(), info["generation"]))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc.callbacks.append(on_gc)
    try:
        if trace:
            jax.profiler.start_trace(tdir)
        t0 = time.perf_counter()
        deadline = t0 + window
        with annotate("bench.window"):
            r = 0
            while True:
                rec = {}
                ts = time.perf_counter()
                with annotate("bench.round"):
                    items = c.round(r, rec, annotate)
                te = time.perf_counter()
                rec["t"], rec["start"] = te - ts, ts
                records.append(rec)
                sample.offer(items)
                r += 1
                if te >= deadline:
                    break
        window_s = time.perf_counter() - t0
        gc.callbacks.remove(on_gc)
        jax.monitoring.unregister_event_duration_listener(on_event)
        if trace:
            jax.profiler.stop_trace()
        longest = max(range(len(records)), key=lambda i: records[i]["t"])
        print(f"# window {window_s:.3f} s: {len(records)} rounds, longest "
              f"{records[longest]['t'] * 1e3:.3f} ms (round {longest}), "
              f"{len(compiles)} compile events", file=sys.stderr)
        _report_gc(gcs, records[longest])
        mem = _memory_peak(mesh.devices.flat)
        t_check = time.perf_counter()
        values = c.check([i for items in sample.items for i in items])
        del c, sample
        print(f"# check {time.perf_counter() - t_check:.3f} s",
              file=sys.stderr)
        tr = None
        if trace:
            from bench_harness import trace as T
            files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise RunError(f"expected one trace file, found {files}")
            tr = T.load(files[0])
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    ctx = types.SimpleNamespace(
        workload=w["name"], entry=traffic["entry"], chips=w["chips"],
        records=records, window_s=window_s, setup_s=setup_s, trace=tr,
        device_kind=devs[0].device_kind)
    from bench_harness import peaks
    ctx.peak = lambda key: peaks.lookup(ctx.device_kind)[key]
    bench = cell["bench"]
    specs = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = {}
    for m in specs:
        if "workloads" in m and w["name"] not in m["workloads"]:
            continue
        reader = _load_module(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"),
                              "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks, failed = {}, set()
    for name, vals in values.items():
        lim = float(limits[name]["limit"])
        checks[name] = {"value": max(vals), "limit": lim}
        failed |= {i for i, v in enumerate(vals) if not v <= lim}
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": not failed and bool(values),
           "attempted": len(records), "failed": len(failed),
           "metrics": metrics, "device": device}
    if tr is not None:
        device.update(T.device_times(tr, window_s))
        out["breakdown"] = T.breakdown(tr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU allowed, rehearsal sizes")
    ap.add_argument("--control", action="store_true",
                    help="the configuration's lower-precision path")
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  args.rehearse, args.control, t_start=_T_START)
    except RunError as e:
        print(f"bench_harness: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
