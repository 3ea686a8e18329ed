"""Measured dispatch for ``device_allreduce(method="auto")``.

The reference picks its allreduce algorithm from one hard-coded
constant (``reduce_ring_mincount = 32768``, allreduce_base.cc:35).
``tools/collective_sweep.py`` replaces the constant with data: it times
{tree, ring, bidir, swing} x {wire none/bf16/int8} x payload sizes on
the mesh and emits a schema-versioned ``COLLECTIVE_SWEEP_*.json`` whose
``table`` section this module loads. With no table committed (or an
unreadable/foreign-schema file) dispatch falls back to the conservative
constants below — exactly the pre-table behavior.

Wire quantization is LOSSY, so it is never auto-enabled: the table (or,
without a table, the ``rabit_dataplane_wire_mincount`` size gate) only
decides *when* a wire the user explicitly requested (per-call ``wire=``
beats the gate; ``rabit_dataplane_wire`` config/env is gated) actually
engages — ``WIRE_BENCH_20260730T233920Z.json`` measured quantized wire
LOSING below ~65k floats and winning at 4.2M, so an ungated wire makes
small reductions both slower and less accurate.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Optional, Tuple

from ..utils import log
from ..utils.config import parse_size
from . import wire as _wirespec

# Fallback crossover: ring pays off above 32K elements (reference
# allreduce_base.cc:35, doc/parameters.md).
RING_MINCOUNT_DEFAULT = 32 << 10

# Fallback wire gate: quantized wire measured losing at 65k and winning
# at 4.2M floats on the host fabric (WIRE_BENCH_20260730T233920Z.json);
# 256K elements sits conservatively inside that band.
WIRE_MINCOUNT_DEFAULT = 256 << 10

METHODS = ("tree", "ring", "bidir", "swing", "hier")

# "preagg" is a valid EXPLICIT method (and what skew adaptation elects)
# but never a table row: sweeps measure steady-state schedules, and
# pre-aggregation only exists relative to a measured laggard.
EXPLICIT_METHODS = METHODS + ("preagg",)

SCHEMA_PREFIX = "rabit_tpu.collective_sweep/"
# v3 adds block-quantized wire-spec columns ("int8:bf16", "@block") and
# the per-row wire_block field; v2 added the skew/lag columns
# (tools/collective_sweep.py --lag-rank); v1/v2 artifacts are committed
# history and must keep loading.
SCHEMA = SCHEMA_PREFIX + "v3"
ACCEPTED_SCHEMAS = (SCHEMA, SCHEMA_PREFIX + "v2", SCHEMA_PREFIX + "v1")

_TABLE_ENV = "RABIT_DISPATCH_TABLE"
_WIRE_ENV = "RABIT_DATAPLANE_WIRE"
_WIRE_MINCOUNT_ENV = "RABIT_DATAPLANE_WIRE_MINCOUNT"
_WIRE_ADAPT_ENV = "RABIT_WIRE_ADAPTIVE"
_METHOD_ENV = "RABIT_REDUCE_METHOD"

# Table wire columns may hold any canonical wire spec
# ("<rs>[:<ag>][@<block>]", parallel/wire.py grammar).
_WIRE_SPEC_RE = re.compile(
    r"^(bf16|int8|none)(:(bf16|int8|none))?(@[1-9][0-9]*)?$")

# Adaptive-election cost model (rabit_wire_adaptive): predicted wire
# seconds saved must beat the quantize/dequantize cost, modelled as a
# fixed per-collective overhead (the scale-computation dispatches) plus
# a codec throughput term. The constants are deliberately conservative
# — on-device block quantization streams at memcpy-like rates.
ADAPT_CODEC_GBPS = 2.0
ADAPT_OVERHEAD_S = 100e-6
_ADAPT_MIN_SAMPLES = 4
_ADAPT_RING_METHODS = ("ring", "bidir", "swing", "hier")


def wire_adaptive() -> bool:
    """Adaptive wire election on/off (``rabit_wire_adaptive``): learn
    the live link bandwidth from telemetry's per-op counters and engage
    the env-requested wire only where predicted savings beat the codec
    cost, instead of the static table/mincount gate."""
    return os.environ.get(_WIRE_ADAPT_ENV, "").lower() in (
        "1", "true", "yes", "on")


def _measured_bandwidth() -> Optional[float]:
    """Live bytes/second of the UNQUANTIZED ring-family dataplane,
    learned from telemetry's allreduce counters (recorder.py keys rows
    by (name, op, method, wire, bucket)). None until enough samples
    have durations — dispatch must fall back to the static gate, never
    guess from thin data."""
    from .. import telemetry
    if not telemetry.enabled():
        return None
    total_b, total_s, count = 0, 0.0, 0
    for row in telemetry.counter_rows("allreduce"):
        if row["wire"] or row["method"] not in _ADAPT_RING_METHODS:
            continue
        total_b += row["bytes"]
        total_s += row["total_s"]
        count += row["count"]
    if count < _ADAPT_MIN_SAMPLES or total_s <= 0 or total_b <= 0:
        return None
    return total_b / total_s


def _adaptive_elect(n: int, itemsize: int,
                    spec: str) -> Optional[bool]:
    """Should the requested wire ``spec`` engage for an ``n``-element
    payload? True/False when telemetry supports a decision, None when
    it can't (no data, disabled, or a multi-controller world — a
    per-process election is a divergent static jit arg, so agreement
    there stays with the static gate until it rides the skew digest
    plane)."""
    try:
        import jax
        if jax.process_count() > 1:
            return None
    except Exception:  # pragma: no cover - jax always importable here
        return None
    bw = _measured_bandwidth()
    if bw is None:
        return None
    nbytes = n * itemsize
    wire_b = _wirespec.wire_itemsize(spec, itemsize)
    saved_s = nbytes * (1.0 - wire_b / itemsize) / bw
    codec_s = ADAPT_OVERHEAD_S + nbytes / (ADAPT_CODEC_GBPS * 1e9)
    return saved_s > codec_s


# Last wire actually applied by resolve() on this thread of control —
# the dataplane stamps it as the span's ``wire_applied`` so traces show
# request vs outcome (mirrors telemetry.skew's note_applied pattern).
_last_wire: Optional[str] = None
_last_wire_provenance: str = ""


def note_wire(wire: Optional[str], provenance: str = "") -> None:
    global _last_wire, _last_wire_provenance
    _last_wire = wire
    _last_wire_provenance = provenance


def last_wire() -> Optional[str]:
    return _last_wire


def last_wire_provenance() -> str:
    return _last_wire_provenance


def wire_mincount() -> int:
    """Element-count floor below which a config/env-requested wire stays
    off (``rabit_dataplane_wire_mincount``; size suffixes accepted)."""
    v = os.environ.get(_WIRE_MINCOUNT_ENV)
    return parse_size(v) if v else WIRE_MINCOUNT_DEFAULT


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def artifacts_dir() -> str:
    """Where committed perf artifacts live (``benchmarks/artifacts/``);
    every capture tool writes here and history ingestion reads here."""
    return os.path.join(_repo_root(), "benchmarks", "artifacts")


def _newest_sweep() -> Optional[str]:
    """Newest committed sweep artifact (timestamped names sort).
    Scans ``benchmarks/artifacts/`` plus the repo root (pre-move
    layouts and user-dropped tables keep working)."""
    found = sorted(
        glob.glob(os.path.join(artifacts_dir(), "COLLECTIVE_SWEEP_*.json"))
        + glob.glob(os.path.join(_repo_root(), "COLLECTIVE_SWEEP_*.json")),
        key=os.path.basename)
    return found[-1] if found else None


def _valid_rows(rows) -> bool:
    if not isinstance(rows, list) or not rows:
        return False
    for r in rows:
        if not isinstance(r, dict) or r.get("method") not in METHODS:
            return False
        if not (r.get("max_n") is None or isinstance(r["max_n"], int)):
            return False
        w = r.get("wire")
        if w is not None and (not isinstance(w, str)
                              or not _WIRE_SPEC_RE.match(w)):
            return False
        # "flat": the schedule a hier row degrades to on worlds without
        # a usable host grouping (optional; hier rows only)
        if r.get("flat") not in (None, "tree", "ring", "bidir", "swing"):
            return False
    return rows[-1].get("max_n") is None  # must cover every size


# What load_table() remembers, as (stamp, table-or-None, source):
# under key None the discovered table, stamped with the value of
# RABIT_DISPATCH_TABLE it was found under, so the steady path makes no
# filesystem call; under an explicit path that file's parse, stamped
# with its mtime, so a changed file re-parses. A bad table is
# remembered as bad until its stamp changes or the cache is cleared.
_cache: dict = {}
_source = "documented constants (no table loaded yet)"


def clear_cache() -> None:
    """Forget every remembered table: the next :func:`load_table`
    discovers again (a newly dropped ``COLLECTIVE_SWEEP_*.json`` takes
    effect from then on)."""
    _cache.clear()


def epoch_reset(world: int) -> None:
    """Elastic-membership epoch hook (lint rule R002): the discovered
    table is found once per epoch, and its rows steer method choice per
    axis size — after a resize the relevant axis size changed, so a
    stale discovery must not outlive the world that made it (one
    directory scan and one JSON parse per epoch transition is free)."""
    del world  # resolve() receives the new axis size per call
    clear_cache()


def _parse(path: str) -> Tuple[Optional[dict], str]:
    """``(table-or-None, source)`` of one sweep file."""
    table = None
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("schema") in ACCEPTED_SCHEMAS:
            cand = data.get("table")
            if (isinstance(cand, dict)
                    and _valid_rows(cand.get("float_sum"))
                    and _valid_rows(cand.get("other"))):
                table = cand
    except FileNotFoundError:
        return None, f"documented constants ({path} missing)"
    except (OSError, ValueError):
        pass
    if table is None:
        return None, f"documented constants ({path} unreadable or invalid)"
    import jax
    running, measured = jax.default_backend(), data.get("backend")
    if measured != running:
        # a table timed on another fabric (every committed sweep ran on
        # the 8-device CPU mesh) must not steer this one
        return None, (f"documented constants ({path} was measured on "
                      f"backend {measured!r}, running {running!r})")
    return table, f"table {path} (backend {running!r})"


def _discover(env: Optional[str]) -> Tuple[Optional[dict], str]:
    """``(table-or-None, source)`` for ``RABIT_DISPATCH_TABLE`` = ``env``:
    disabled, the file it names, or the newest committed sweep."""
    if env in ("none", "off", "0"):
        return None, f"documented constants ({_TABLE_ENV}={env})"
    path = env or _newest_sweep()
    if not path:
        return None, "documented constants (no table found)"
    return _parse(path)


def load_table(path: Optional[str] = None) -> Optional[dict]:
    """The committed dispatch table, or None (→ fallback constants).

    Resolution order: explicit ``path`` arg, ``RABIT_DISPATCH_TABLE``
    env (``none``/``off``/``0`` disables), newest
    ``COLLECTIVE_SWEEP_*.json`` under ``benchmarks/artifacts/`` (repo
    root also scanned for compatibility). A missing file, a schema
    outside ``ACCEPTED_SCHEMAS`` (v3, v2 and the legacy v1 — future
    majors must not be misread), malformed rows, or a table whose
    recorded ``backend`` is not the running one all yield None —
    dispatch then uses the documented defaults, and
    :func:`table_source` says why.

    Without ``path`` the table is discovered once and remembered: the
    first call in the process, the first after :func:`clear_cache` or
    :func:`epoch_reset`, and the first after ``RABIT_DISPATCH_TABLE``
    changes value scan and parse; every other call is one environment
    read. An explicit ``path`` is re-parsed when its mtime changes.
    The ``dispatch_table`` cache counter's misses count discoveries
    and parses, its hits the calls that made neither.
    """
    global _source
    from ..telemetry import profile
    if path is None:
        stamp = os.environ.get(_TABLE_ENV)
    else:
        try:
            stamp = os.path.getmtime(path)
        except OSError:
            _source = f"documented constants ({path} missing)"
            return None
    hit = _cache.get(path)
    if hit is not None and hit[0] == stamp:
        profile.cache_event("dispatch_table", hit=True)
        _source = hit[2]
        return hit[1]
    profile.cache_event("dispatch_table", hit=False)
    table, _source = _discover(stamp) if path is None else _parse(path)
    log.log_info("dispatch: %s", _source)
    _cache[path] = (stamp, table, _source)
    return table


def table_source() -> str:
    """Which source the last :func:`load_table` decision used: a table
    measured on the running backend, or the documented constants and
    why."""
    return _source


def _bucket(rows, n: int) -> dict:
    for r in rows:
        if r["max_n"] is None or n <= r["max_n"]:
            return r
    return rows[-1]  # unreachable for valid tables (last max_n is None)


def resolve(n: int, dtype, op: int, axis_size: int,
            method: str = "auto",
            wire: Optional[str] = "auto",
            groups=None) -> Tuple[str, Optional[str]]:
    """Resolve ``(method, wire)`` for an ``n``-element payload.

    ``method="auto"``: per-size-bucket choice from the committed table,
    else tree below ``RING_MINCOUNT_DEFAULT`` and ring above (with the
    big-BitOR override — the tree BitOR path all-gathers).

    ``groups`` is the resolved host grouping (``parallel/topology.py``).
    A table row saying ``hier`` only engages when the grouping is
    genuinely two-level (>1 host, >1 rank/host, uniform); otherwise the
    row's ``flat`` column (else the fallback constants) applies — auto
    never runs a hierarchical schedule on a world with no hierarchy.
    An EXPLICIT ``method="hier"`` on such a world degrades to ``ring``,
    the same degradation contract as swing on a non-power-of-two world.

    With ``rabit_skew_adapt`` on and the fleet-agreed digest (adopted
    at the last agreement boundary; never a per-process candidate)
    naming a laggard inside this world, auto additionally prefers
    skew-tolerant shapes (swing/bidir → tree/ring by size) and stamps
    provenance ``skew_adapted`` plus the ``dispatch.skew_adapted``
    counter; the concrete re-root / rotation / pre-aggregation plan is
    applied by ``device_allreduce`` (``telemetry/skew.py``).

    ``wire="auto"``: engages the env-requested wire — the
    ``RABIT_DATAPLANE_WIRE`` base codec composed with the
    ``rabit_wire_rs``/``rabit_wire_ag`` phase overrides and the
    ``rabit_wire_block`` block size (parallel/wire.py spec grammar) —
    only where measurement says it pays. Precedence: with
    ``rabit_wire_adaptive`` on and telemetry carrying enough
    unquantized ring-family samples, a live bandwidth-learned
    crossover decides (:func:`_adaptive_elect`; single-controller
    worlds only — a per-process election would be a divergent static
    jit arg); else the table bucket's wire field; else ``n >=
    wire_mincount()``. An EXPLICITLY configured mincount (the env var
    is set) beats the table's wire column: a user who pins the gate —
    e.g. ``rabit_dataplane_wire_mincount=0`` to force quantization at
    demo sizes — must win over recorded policy, the same precedence
    rule as the per-call override. No env wire (or a tree method) →
    None. An explicit wire spec (``"bf16"``, ``"int8:bf16@512"``, …)
    passes through, canonicalized (per-call override);
    ``wire="none"``/None force it off. The applied wire and its
    provenance are noted (:func:`note_wire`) so dataplane spans can
    stamp request vs outcome.
    """
    import jax.numpy as jnp

    from .. import telemetry
    from ..ops.reducers import BITOR, SUM, OP_NAMES
    from ..telemetry import skew
    from . import topology
    requested = method
    with telemetry.phase("rabit.dispatch.load_table"):
        table = load_table()
    wire_eligible = op == SUM and jnp.issubdtype(jnp.dtype(dtype),
                                                 jnp.floating)
    hier_ok = (topology.hier_enabled()
               and topology.is_hierarchical(groups, axis_size))
    if method == "auto":
        if table is not None:
            rows = table["float_sum"] if wire_eligible else table["other"]
            row = _bucket(rows, n)
            method = row["method"]
            if method == "hier" and not hier_ok:
                method = row.get("flat") or (
                    "ring" if n >= RING_MINCOUNT_DEFAULT else "tree")
        else:
            method = "ring" if n >= RING_MINCOUNT_DEFAULT else "tree"
        if op == BITOR and n >= 1024 and method == "tree":
            method = "ring"  # tree BitOR all-gathers: tiny buffers only
    if method not in EXPLICIT_METHODS:
        raise ValueError(
            f"method must be one of {('auto',) + EXPLICIT_METHODS}, "
            f"got {method!r}")
    if method == "hier" and not hier_ok:
        method = "ring"  # no usable host grouping: flat ring IS the
        #                  inter-host path (degradation contract)
    if method == "swing" and axis_size & (axis_size - 1):
        method = "ring"  # swing needs a power-of-two world
    adapted = False
    if requested == "auto" and skew.adapt_enabled():
        # live skew consult: with the fleet-AGREED digest (the applied
        # digest from the last sync boundary — never a per-process
        # candidate, which would make the elected method a divergent
        # static jit arg) naming a persistent laggard, prefer
        # skew-tolerant shapes — the fixed-topology involutions
        # (swing, bidir) have no good place to park a laggard, while
        # tree re-roots and ring rotates (collectives apply the actual
        # plan; here only the method family is elected). The laggard
        # must be a rank of THIS world: a stale digest naming a rank
        # outside it yields no plan downstream, and provenance must
        # not report adaptation for rounds that ran flat.
        lag = skew.laggard_of(skew.monitor().applied())
        if lag is not None and 0 <= lag < axis_size and axis_size >= 2:
            adapted = True
            if method in ("swing", "bidir"):
                method = ("tree" if n < RING_MINCOUNT_DEFAULT else "ring")
    itemsize = jnp.dtype(dtype).itemsize
    requested_wire = wire
    wire_prov = ""
    if wire == "auto":
        # env request: base codec (rabit_dataplane_wire) with per-phase
        # overrides (rabit_wire_rs/rabit_wire_ag) and the env block
        # folded in — already canonical
        env_wire = _wirespec.phase_request(
            os.environ.get(_WIRE_ENV) or None)
        if (env_wire is None or method in ("tree", "preagg")
                or not wire_eligible):
            wire = None
        else:
            elected = (_adaptive_elect(n, itemsize, env_wire)
                       if wire_adaptive() else None)
            if elected is not None:
                # bandwidth-learned crossover (rabit_wire_adaptive)
                # beats the static gate; still only ever engages the
                # wire the user REQUESTED — lossy modes stay opt-in
                wire = env_wire if elected else None
                wire_prov = "adaptive"
            elif (table is not None
                    and not os.environ.get(_WIRE_MINCOUNT_ENV)):
                wire = env_wire \
                    if _bucket(table["float_sum"], n).get("wire") else None
            else:
                wire = env_wire if n >= wire_mincount() else None
    elif wire in ("none", "off"):
        wire = None
    else:
        # explicit per-call spec: canonicalize (folds the env block into
        # specs that don't pin one) so it is a stable jit cache key
        wire = _wirespec.canonical_wire(wire)
    provenance = ("explicit" if requested != "auto"
                  else "skew_adapted" if adapted
                  else "table" if table is not None else "fallback")
    if not wire_prov:
        wire_prov = ("explicit" if requested_wire not in ("auto",)
                     else provenance)
    if telemetry.enabled():
        if adapted:
            telemetry.count("dispatch.skew_adapted")
        if wire_prov == "adaptive":
            # adaptive election made the call (either way); the row's
            # wire field says whether it engaged ("" = declined)
            telemetry.count("dispatch.wire_adapted",
                            nbytes=n * itemsize, wire=wire)
        if wire is not None:
            # bytes entering the quantized dataplane, by spec — served
            # as rabit_wire_quantized_bytes_total (telemetry/prom.py)
            telemetry.count("wire.quantized", nbytes=n * itemsize,
                            op=OP_NAMES.get(op, str(op)), method=method,
                            wire=wire, provenance=wire_prov)
        telemetry.record_dispatch(
            n, itemsize, OP_NAMES.get(op, str(op)),
            method, wire, provenance)
    note_wire(wire, wire_prov)
    return method, wire
