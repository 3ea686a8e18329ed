"""What several metric readers share: each metric's file is one call
here, with the entry whose cells it reads (a quantity that moves a
different end-to-end metric per entry has one file per entry)."""

import numpy as np


def busy_ns(run, entry: str, kind: str = "all"):
    """Union of the op intervals (``all``, ``compute`` or ``collective``)
    of the busiest device in the traced window, or None."""
    if run.entry != entry or run.trace is None or not run.trace.devices:
        return None
    busy = max(run.trace.busy_ns(d, kind) for d in run.trace.devices)
    return busy if busy > 0 else None


def per_round_ms(run, entry: str, kind: str = "all"):
    busy = busy_ns(run, entry, kind)
    return None if busy is None else busy / len(run.records) / 1e6


def idle_share(run, entry: str):
    """1 - busiest device's busy time / traced window, in %."""
    busy = busy_ns(run, entry)
    if busy is None:
        return None
    win = run.trace.window_ns()
    win_ns = (win[1] - win[0]) if win else run.window_s * 1e9
    return 100.0 * (1.0 - busy / win_ns)


def dispatch_us(run, entry: str):
    """Median host time of one entry call until it returns."""
    if run.entry != entry:
        return None
    return float(np.median([d for r in run.records
                            for d in r["dispatch_s"]])) * 1e6


def round_p95_ms(run):
    """95th percentile over every round of the window of one round's time
    on the caller's clock, from its first call until its last result is
    ready."""
    return float(np.percentile([r["t"] for r in run.records], 95)) * 1e3
