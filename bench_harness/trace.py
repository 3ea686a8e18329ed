"""Reduce a JAX profiler trace (``.xplane.pb``) to per-device op
intervals and the harness's own host spans.

Device ops are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, each named by its whole HLO instruction. An
op is a collective where the instruction's opcode says so (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, send/recv
and their -start/-done halves); its name does not (a ``psum`` is an
all-reduce). Host spans are the
``bench.*`` annotations the harness writes around its own calls
(``jax.profiler.TraceAnnotation``); they name what the host was doing in
a device gap. Every number the per-layer readers take from a trace comes
through here.
"""

from __future__ import annotations

import collections
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|send|recv)(-start|-done)?$")
HOST_PREFIX = "bench."


def short_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def opcode(hlo: str) -> str:
    """``%psum.1 = f32[8]{0} all-reduce(...)`` -> ``all-reduce``; the
    result shape may be a parenthesised tuple."""
    rest = hlo.split(" = ", 1)[-1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    return rest.strip().split("(", 1)[0]


def is_collective(hlo: str) -> bool:
    return COLLECTIVE.match(opcode(hlo)) is not None


class Trace:
    """``ops[dev]``: float64 [k, 2] (start_ns, end_ns) of every device op,
    sorted by start, with the instruction names ``names[dev]`` and the
    bool ``coll[dev]`` beside it. ``host``: (start_ns, end_ns, name) of
    the harness spans."""

    def __init__(self, ops: dict, names: dict, coll: dict, host: list):
        self.ops = {d: np.asarray(v, np.float64).reshape(-1, 2)
                    for d, v in ops.items()}
        self.names = names
        self.coll = {d: np.asarray(v, bool) for d, v in coll.items()}
        self.host = sorted(host)

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    def window_ns(self) -> tuple | None:
        """The ``bench.window`` span, the traced window on the trace's
        clock, or None where the host span is missing."""
        spans = [(s, e) for s, e, n in self.host if n == "bench.window"]
        return spans[0] if spans else None

    def intervals(self, dev: int, kind: str = "all") -> np.ndarray:
        """Op intervals of one device inside the traced window: ``all``,
        ``collective`` or ``compute`` (every op that is no collective)."""
        iv, coll = self.ops[dev], self.coll[dev]
        if kind == "collective":
            iv = iv[coll]
        elif kind == "compute":
            iv = iv[~coll]
        win = self.window_ns()
        if win is not None and len(iv):
            iv = np.clip(iv, win[0], win[1])
            iv = iv[iv[:, 1] > iv[:, 0]]
        return iv

    def busy_ns(self, dev: int, kind: str = "all") -> float:
        """Length of the union of the device's op intervals."""
        return union_ns(self.intervals(dev, kind))

    def op_totals(self) -> list:
        """[(op name without its HLO number, ns summed over devices)],
        longest first."""
        tot = collections.Counter()
        for d in self.devices:
            for (s, e), n in zip(self.ops[d], self.names[d]):
                tot[re.sub(r"\.\d+$", "", n)] += e - s
        return tot.most_common()

    def idle_gaps(self, dev: int, top: int = 10) -> list:
        """[(what the host was doing, gap ns)] for the ``top`` longest
        gaps between the device's merged op intervals inside the window.
        The host's doing is the innermost ``bench.*`` span covering the
        gap's middle, ``host`` where none does."""
        win = self.window_ns()
        merged = merge(self.intervals(dev))
        if win is None or not len(merged):
            return []
        gaps = np.concatenate([[win[0]], merged.reshape(-1),
                               [win[1]]]).reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:top]
        return [(self.host_doing((s + e) / 2), float(e - s))
                for s, e in gaps]

    def host_doing(self, t: float) -> str:
        best, width = "host", float("inf")
        for s, e, n in self.host:
            if s <= t <= e and e - s < width and n != "bench.window":
                best, width = n[len(HOST_PREFIX):], e - s
        return best


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of intervals as disjoint sorted [m, 2]."""
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > ends[:-1]])
    last = np.r_[first[1:] - 1, len(iv) - 1]
    return np.stack([iv[first, 0], ends[last]], axis=1)


def union_ns(iv: np.ndarray) -> float:
    m = merge(iv)
    return float((m[:, 1] - m[:, 0]).sum()) if len(m) else 0.0


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, names, coll, host = {}, {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            iv, nm = [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    nm.append(ev.name)
            order = sorted(range(len(iv)), key=lambda i: iv[i][0])
            ops[dev] = [iv[i] for i in order]
            names[dev] = [short_name(nm[i]) for i in order]
            coll[dev] = [is_collective(nm[i]) for i in order]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
    return Trace(ops, names, coll, host)


def device_times(tr: Trace, window_s: float) -> dict:
    """The result line's ``busy_s`` (union of op intervals, averaged over
    the devices) and ``window_s`` (the traced window)."""
    devs = tr.devices
    busy = sum(tr.busy_ns(d) for d in devs) / max(len(devs), 1) / 1e9
    win = tr.window_ns()
    return {"busy_s": busy,
            "window_s": (win[1] - win[0]) / 1e9 if win else window_s}


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ops that took most device time (seconds, averaged over the
    devices) and the longest idle gaps of the busiest device, each named
    by what the host was doing."""
    devs = tr.devices
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    ops = [[n, float(ns) / len(devs) / 1e9]
           for n, ns in tr.op_totals()[:top]]
    busiest = max(devs, key=tr.busy_ns)
    gaps = [[n, ns / 1e9] for n, ns in tr.idle_gaps(busiest, top)]
    return {"device_ops": ops, "idle_gaps": gaps}
