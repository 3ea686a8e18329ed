"""What the program itself puts into a traced run, for the per-layer
metrics that read it: its host phases (``rabit_tpu.telemetry.phase``),
recorded while the profiler records and read from the process's own
recorder after the window, and the stable names of its kernels on the
device trace.

Phases are stamped on ``time.perf_counter``, the clock of the harness's
round records. Each round's ``bench.round`` span on the trace and its
record's ``start`` give the offset between the two clocks; their median
maps a phase onto the device ops. A program that records no phases, or
names no kernel so, gives None here and never an error.
"""

from __future__ import annotations

import re

import numpy as np

from bench_harness import trace as T


def phases(run):
    """The phases recorded inside the window, or None where the program
    recorded none or the recorder dropped any."""
    try:
        from rabit_tpu import telemetry
    except ImportError:
        return None
    snap = telemetry.snapshot()
    if "phases" not in snap or snap["phases_dropped"] or not run.records:
        return None
    lo = run.records[0]["start"]
    hi = run.records[-1]["start"] + run.records[-1]["t"]
    got = [p for p in snap["phases"]
           if lo <= p["start"] and p["start"] + p["dur"] <= hi]
    return got or None


def median_us(run, entry: str, name: str):
    """Median duration of the phase ``name`` over the window's calls."""
    if run.entry != entry:
        return None
    durs = [p["dur"] for p in phases(run) or () if p["name"] == name]
    return float(np.median(durs)) * 1e6 if durs else None


def offset_ns(run):
    """Trace clock minus ``perf_counter`` in ns: the median over the
    window's rounds of each ``bench.round`` span's start less its
    record's, or None where spans and records do not pair one to one."""
    if run.trace is None:
        return None
    starts = [s for s, _, n in run.trace.host if n == "bench.round"]
    if not starts or len(starts) != len(run.records):
        return None
    return float(np.median([s - r["start"] * 1e9
                            for s, r in zip(starts, run.records)]))


def idle_inside(run, entry: str, name: str):
    """% of the traced window in which the busiest device runs no op
    while the host is inside the phase ``name``."""
    if run.entry != entry or run.trace is None or not run.trace.devices:
        return None
    ph, off, win = phases(run), offset_ns(run), run.trace.window_ns()
    if ph is None or off is None or win is None:
        return None
    host = np.array([(p["start"] * 1e9 + off,
                      (p["start"] + p["dur"]) * 1e9 + off)
                     for p in ph if p["name"] == name]).reshape(-1, 2)
    host = np.clip(host, win[0], win[1])
    if not len(host):
        return None
    busiest = max(run.trace.devices, key=run.trace.busy_ns)
    ops = run.trace.intervals(busiest)
    idle = T.union_ns(np.concatenate([host, ops])) - T.union_ns(ops)
    return 100.0 * idle / (win[1] - win[0])


def kernel_ms(run, entry: str, kernel: str):
    """Device time per round of the ops named ``kernel`` (HLO number
    aside), as the union of their intervals in the window on the device
    where it is largest."""
    if run.entry != entry or run.trace is None:
        return None
    tr, win = run.trace, run.trace.window_ns()
    busy = 0.0
    for d in tr.devices:
        keep = np.array([re.sub(r"\.\d+$", "", n) == kernel
                         for n in tr.names[d]], bool)
        iv = tr.ops[d][keep]
        if win is not None and len(iv):
            iv = np.clip(iv, win[0], win[1])
        busy = max(busy, T.union_ns(iv))
    return busy / len(run.records) / 1e6 if busy > 0 else None
