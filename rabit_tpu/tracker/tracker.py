"""Rendezvous tracker.

The reference outsources this to dmlc-core's tracker (invoked as
``dmlc-submit``, test/test.mk:16); only the worker-side protocol lives in
its repo (allreduce_base.cc:222-441). This is our own tracker: it assigns
stable ranks (task_id -> rank survives restarts, the basis of
fail-restart-and-catch-up recovery), computes the tree + ring topology,
barriers each (re)registration epoch so every worker is listening before
link wiring starts, and relays ``print``/``shutdown`` commands.

Multi-job control plane (ISSUE 15): one tracker serves many
fault-isolated jobs. Job addressing rides the EXISTING wire protocol —
a worker whose task_id is ``<job>/<task>`` addresses job ``<job>``
(tracker/jobs.py), and a task_id without a separator addresses the
implicit ``default`` job. Task ids are only ever split when
``rabit_multi_job`` is set; unset, every byte on the wire and in the
WAL is identical to the single-job tracker. Per-world state lives on a
per-job ``JobState`` (lint R007 keeps it there); exceptions handling
one job's commands are quarantined at the job boundary, never raised
into the accept loop; ``rabit_max_jobs``/``rabit_max_fleet_ranks``
caps + a bounded FIFO admission queue make overload a degraded mode
(submitters are shed with a retry-after hint, never stalled).

Wire protocol (binary, little-endian, length-prefixed strings):
  worker -> tracker: magic u32 0x52425401, cmd str, task_id str,
                     num_attempt u32
    start/recover: + host str, listen_port u32, flags u32
                   (flags bit 0: worker will register an accelerator
                   data plane — the tracker hosts a device-world
                   coordinator on demand), uds_token str (random name
                   of the worker's abstract-UDS listener twin; "" =
                   TCP-only)
    print:         + msg str
    metrics:       + payload str (a rabit_tpu.telemetry_summary/v1 JSON
                   document; the tracker stores the latest per task_id
                   and prints the merged fleet table at end of run)
    endpoint:      + payload str (JSON {"host","port","rank"}: where
                   that worker's live /metrics endpoint listens; the
                   tracker's poller scrapes it on an interval while the
                   run is live — see telemetry/live.py)
    topo:          (no extra fields) tracker -> worker: payload str, a
                   JSON {"epoch","groups","delegates","single_host"}
                   document of the host topology observed at the last
                   completed assignment — ranks grouped by the host
                   fingerprint of the endpoint announce path (observed
                   registration source IP, falling back to the reported
                   hostname), plus the elected min-rank delegate per
                   host. "{}" before the first assignment. Feeds the
                   hierarchical collectives (parallel/topology.py).
    repl:          + last_seq u32 after the tracker's 1-ack (hot-standby
                   replication, ISSUE 12): the follower subscribes with
                   the newest WAL seq it holds durably and the leader
                   streams every later record as a raw ``append`` frame
                   — the exact CRC'd canonical-JSON bytes tracker/wal.py
                   journals — waiting for a u32 seq ack (bounded by
                   rabit_repl_ack_timeout_ms) after each before sending
                   the next. A torn stream resyncs by resubscribing
                   from the follower's last durable seq. Interleaved
                   with the journaled records the leader also ships
                   ephemeral ``seq 0`` lease-heartbeat frames (same
                   framing, never journaled on either side, never
                   acked): idempotent lease renewals ride these so the
                   journal stays bounded by real transitions while the
                   follower's promotion countdown still restarts every
                   ``lease_ms/3``.
    skew:          (no extra fields) tracker -> worker: payload str, a
                   JSON {"epoch","offsets_ms","laggard"} fleet skew
                   digest — the tracker-side FleetElection's smoothed,
                   hysteretic verdict over the poll loop's straggler
                   snapshots (telemetry/skew.py): per-rank EWMA arrival
                   offsets in ms plus the elected laggard (null while
                   no rank crosses the signal threshold); epoch bumps
                   exactly when the election changes. "{}" until a
                   poll sweep has per-rank busy times. Workers cache it
                   verbatim as their candidate and adopt it fleet-wide
                   at agreement boundaries (rabit_skew_adapt).
    submit:        + payload str (JSON {"job","nworkers","elastic"}:
                   an admission request for a new job, ISSUE 15).
                   tracker -> worker: payload str, a JSON verdict
                   answered IMMEDIATELY: {"ok": 1} admitted (or
                   already open — idempotent), {"ok": 0, "queued": 1,
                   "position": p, "retry_after_ms": n} parked in the
                   bounded FIFO admission queue, {"ok": 0, "shed": 1,
                   "retry_after_ms": n} shed past the queue depth, or
                   {"ok": 0, "error": ...} never admissible. The
                   tracker never stalls a submitter — overload is a
                   backoff hint, not a hang.
  tracker -> worker (start/recover): rank u32, world u32, epoch u32,
    coord_host str, coord_port u32 (this epoch's tracker-hosted device
    -world coordination service; empty/0 when coordinator hosting is
    off), single_host u32 (1 when every registered worker reported the
    same host — drives the world-consistent ring/tree crossover
    default), parent u32 (0xFFFFFFFF = none), ntree u32 + tree neighbor
    ranks, ring_prev u32, ring_next u32,
    nconnect u32 + (peer_rank u32, host str, port u32, uds_token
    str)..., naccept u32; worker replies ready u32 after wiring its
    links. A peer's uds_token resolves only on that peer's own host
    and network namespace, so the UDS fast path needs no same-host
    inference: resolving the name IS the proof, and failure falls back
    to TCP per-pair.
With the causal incident plane on (``rabit_events``, ISSUE 20) every
JSON-str tracker reply above (topo/skew/world/submit) piggybacks one
extra ``"hlc"`` field — the tracker's hybrid logical clock stamp, which
workers merge so fleet events order causally across hosts. u32 replies
never change, and with the knob unset no reply grows a byte.
Workers connect to lower-ranked neighbors and accept from higher ranks.
The epoch counts completed registration batches: every live worker
re-registers in the same batch during recovery, so all members of a
batch observe the same epoch — the agreement the accelerator data plane
needs to tear down/re-form its fixed-membership device world without an
extra consensus round. Epochs are per-job: job B forming never bumps
job A's epoch.
"""

from __future__ import annotations

import json
import os
import socket
import struct
import sys
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from ..telemetry import clock as _clock
from ..telemetry import events as _events
from ..telemetry import incident as _incident
from ..telemetry.aggregate import format_fleet_table, merge_summaries
from . import evloop as _evloop
from . import jobs as _jobs_mod
from . import membership as _membership
from . import wal as _wal_mod

MAGIC = 0x52425401
NO_RANK = 0xFFFFFFFF

# a wire string longer than this is a protocol violation, not a
# payload: the cap keeps a flipped length bit from growing one
# connection's input buffer without bound (same figure as the WAL's
# MAX_RECORD_BYTES — no tracker payload serializes to megabytes)
_MAX_WIRE_STR = 16 << 20


def _recv_all(conn: socket.socket, n: int) -> bytes:
    out = b""
    while len(out) < n:
        chunk = conn.recv(n - len(out))
        if not chunk:
            raise ConnectionError("worker closed connection")
        out += chunk
    return out


def _recv_u32(conn) -> int:
    return struct.unpack("<I", _recv_all(conn, 4))[0]


def _send_u32(conn, v: int) -> None:
    conn.sendall(struct.pack("<I", v))


def _recv_str(conn) -> str:
    n = _recv_u32(conn)
    return _recv_all(conn, n).decode()


def _send_str(conn, s: str) -> None:
    b = s.encode()
    _send_u32(conn, len(b))
    conn.sendall(b)


# -- incremental wire parsing (ISSUE 19) --------------------------------
# The event loop feeds these generators bytes as they arrive: a
# generator yields how many bytes it needs next and returns the parsed
# value. Same grammar as the blocking helpers above (which the CLIENT
# side — jobs.py, autoscaler, launch — still uses); the tracker's
# accept path no longer blocks a thread per in-flight command.


def _p_u32():
    return struct.unpack("<I", (yield 4))[0]


def _p_str():
    n = struct.unpack("<I", (yield 4))[0]
    if n == 0:
        return ""
    if n > _MAX_WIRE_STR:
        raise ConnectionError(f"wire string claims {n} bytes")
    return (yield n).decode()


def _parse_command():
    """One full worker->tracker request: preamble (magic, cmd, task_id,
    num_attempt) plus the command's own fields. Returns ``(cmd,
    task_id, args)`` — ``args`` is the per-command field tuple — or
    ``None`` on a bad magic (the connection is hung up on, exactly as
    the blocking path did)."""
    magic = yield from _p_u32()
    if magic != MAGIC:
        return None
    cmd = yield from _p_str()
    task_id = yield from _p_str()
    yield from _p_u32()   # num_attempt (informational)
    if cmd in ("start", "recover", "join"):
        host = yield from _p_str()
        port = yield from _p_u32()
        flags = yield from _p_u32()
        token = yield from _p_str()
        return cmd, task_id, (host, port, flags, token)
    if cmd in ("print", "metrics", "endpoint", "resume", "evict",
               "submit"):
        payload = yield from _p_str()
        return cmd, task_id, (payload,)
    # topo / skew / world / shutdown / repl (and unknown commands)
    # carry no extra request fields
    return cmd, task_id, ()


def tree_neighbors(rank: int, world: int) -> Tuple[Optional[int], List[int]]:
    """Complete binary tree: parent + children of ``rank``."""
    parent = (rank - 1) // 2 if rank > 0 else None
    children = [c for c in (2 * rank + 1, 2 * rank + 2) if c < world]
    return parent, children


FLAG_DATAPLANE = 1  # registration flags bit 0


def _require_coordinator_api():
    """The coordinator service rides jaxlib private APIs, kept in
    ``utils/jaxcompat.py``. Fail loudly
    at setup — not mid-recovery — when a jax upgrade removed them
    (VERDICT r2 weak #7)."""
    from ..utils import jaxcompat
    jaxcompat.distributed_runtime_module()
    return jaxcompat


def _default_ready_timeout() -> float:
    """``rabit_tracker_ready_timeout`` knob (doc/parameters.md): how
    long ``_assign`` waits for each worker's ready ack before declaring
    the epoch partially failed."""
    try:
        return float(os.environ.get("RABIT_TRACKER_READY_TIMEOUT", 60.0))
    except ValueError:
        return 60.0


RESUME_GRACE_MS_DEFAULT = 15_000

FORMING_TIMEOUT_MS_DEFAULT = 0

LEASE_MS_DEFAULT = 2_000
REPL_ACK_TIMEOUT_MS_DEFAULT = 1_000


def default_lease_ms() -> int:
    """``rabit_lease_ms`` (doc/parameters.md): leadership-lease length.
    The leader journals a renewal every third of this; a hot standby may
    only promote itself after the last replicated lease expired, so this
    bounds failover time from above and split-brain risk to zero."""
    v = os.environ.get("RABIT_LEASE_MS")
    if not v:
        return LEASE_MS_DEFAULT
    try:
        return max(100, int(v))
    except ValueError:
        raise ValueError(
            f"RABIT_LEASE_MS must be an integer (ms), got {v!r}")


def repl_ack_timeout_ms() -> int:
    """``rabit_repl_ack_timeout_ms`` (doc/parameters.md): how long the
    leader waits for a follower's per-record ack before dropping that
    subscriber (it resyncs by resubscribing from its last durable
    seq)."""
    v = os.environ.get("RABIT_REPL_ACK_TIMEOUT_MS")
    if not v:
        return REPL_ACK_TIMEOUT_MS_DEFAULT
    try:
        return max(50, int(v))
    except ValueError:
        raise ValueError(
            f"RABIT_REPL_ACK_TIMEOUT_MS must be an integer (ms), "
            f"got {v!r}")


def resume_grace_ms() -> int:
    """``rabit_tracker_resume_grace_ms`` (doc/parameters.md): how long
    a resumed tracker waives poll-miss eviction evidence while worker
    pollers reconnect — a brief tracker outage must never evict
    healthy ranks."""
    v = os.environ.get("RABIT_TRACKER_RESUME_GRACE_MS")
    if not v:
        return RESUME_GRACE_MS_DEFAULT
    try:
        return max(0, int(v))
    except ValueError:
        raise ValueError(
            f"RABIT_TRACKER_RESUME_GRACE_MS must be an integer (ms), "
            f"got {v!r}")


# -- WAL replay fold (ISSUEs 10/19) --------------------------------------
# The per-record replay application lives at module level so it has
# exactly TWO consumers sharing ONE implementation: Tracker._replay
# (resume / standby promotion) and fold_records (snapshot compaction,
# live and `wal.py --compact`). Compacted state that drifted from
# replay semantics would be a forged history, so they are the same
# code by construction. Lint R003 exempts the ``_replay*`` family —
# these functions ARE the journal's read side.


class _ReplayWorld:
    """Duck-typed minimal tracker for offline replay folds: exactly
    the attributes ``_replay_apply`` / ``snapshot_state`` touch, none
    of the sockets or threads a real Tracker binds."""

    def __init__(self, nworkers: int, elastic: bool):
        self.nworkers = int(nworkers)
        self.elastic = bool(elastic)
        self.multi_job = False
        self._jobs: Dict[str, _jobs_mod.JobState] = {
            _jobs_mod.DEFAULT_JOB: _jobs_mod.JobState(
                _jobs_mod.DEFAULT_JOB, nworkers, elastic=elastic)}
        self._orphan_jobs: set = set()
        self.restarts = 0
        self.promoted_wall = 0.0
        self.promoted_mono = 0.0
        self.failover_duration_ms = 0.0
        self._lease: Optional[dict] = None
        self._journaled_lease: Optional[dict] = None


def snapshot_state(world) -> dict:
    """Serialize ``world``'s replay-reachable control-plane state as a
    ``wal_snapshot/v1`` doc: exactly the state a full journal replay
    reconstructs (job table, ranks, epochs, membership sets, quota,
    topo/skew/endpoint docs, shutdown ranks, restarts, failover
    stamps, the journaled lease) — deliberately nothing ephemeral
    (pending registrations, sockets, services die with the process
    either way). The caller holds the tracker lock for a live
    ``world``."""
    jobs: Dict[str, dict] = {}
    for jid, jb in world._jobs.items():
        jd: Dict[str, object] = {
            "nworkers": jb.nworkers, "elastic": jb.elastic,
            "sched_class": jb.sched_class, "weight": jb.sched_weight,
            "quota": jb.quota, "preempted": jb.preempted,
            "closed": not jb.open, "closed_reason": jb.closed_reason,
            "ranks": dict(jb._ranks), "epoch": jb._epoch,
            "topo": dict(jb._topo), "skew": dict(jb._skew),
            "endpoints": {t: dict(d)
                          for t, d in jb._endpoints.items()},
            "down": sorted(jb._shutdown_ranks)}
        if jb.elastic and jb._member is not None:
            mv = jb._member
            jd["member"] = {
                "target": mv.target, "live": sorted(mv.live),
                "evicted": sorted(mv.evicted),
                "joining": sorted(mv.joining),
                "generation": mv.generation,
                "evictions": mv.evictions,
                "admissions": mv.admissions}
        jobs[jid] = jd
    doc: Dict[str, object] = {"multi_job": bool(world.multi_job),
                              "restarts": int(world.restarts),
                              "jobs": jobs}
    if world.promoted_wall or world.failover_duration_ms:
        doc["promoted"] = {
            "wall": world.promoted_wall, "mono": world.promoted_mono,
            "failover_ms": world.failover_duration_ms}
    if world._journaled_lease is not None:
        doc["lease"] = dict(world._journaled_lease)
    return doc


def _replay_adopt_into(world, state: dict) -> None:
    """Adopt one ``wal_snapshot/v1`` state doc: REPLACES the job table
    and journaled misc state; the journal's tail records then replay
    on top. The implicit default job is mutated in place — its shape
    (nworkers/elastic) comes from the launch, exactly as a full replay
    never changes it — while every other job is rebuilt from its
    snapshotted open-time shape."""
    from ..telemetry import skew as _skew_mod
    if state.get("multi_job"):
        world.multi_job = True
    world.restarts = int(state.get("restarts", world.restarts))
    prom = state.get("promoted") or {}
    if prom:
        world.promoted_wall = float(prom.get("wall", 0.0))
        world.promoted_mono = float(prom.get("mono", 0.0))
        world.failover_duration_ms = float(prom.get("failover_ms", 0.0))
    lease = state.get("lease")
    if lease is not None:
        world._lease = dict(lease)
        world._journaled_lease = dict(lease)
    keep = {_jobs_mod.DEFAULT_JOB:
            world._jobs[_jobs_mod.DEFAULT_JOB]}
    world._orphan_jobs.clear()
    for jid, jd in (state.get("jobs") or {}).items():
        jid = str(jid)
        job = keep.get(jid)
        if job is None:
            job = _jobs_mod.JobState(
                jid, int(jd.get("nworkers", world.nworkers)),
                elastic=bool(jd.get("elastic", False)),
                sched_class=int(jd.get("sched_class", 0)),
                sched_weight=float(jd.get("weight", 1.0)))
            keep[jid] = job
        job.quota = int(jd.get("quota", job.nworkers))
        job.preempted = int(jd.get("preempted", 0))
        job._ranks = {str(t): int(r)
                      for t, r in (jd.get("ranks") or {}).items()}
        job._epoch = int(jd.get("epoch", 0))
        job._topo = dict(jd.get("topo") or {})
        digest = dict(jd.get("skew") or {})
        if digest:
            job._skew = digest
            job._skew_election = _skew_mod.FleetElection.seeded(digest)
        job._endpoints = {str(t): dict(d) for t, d in
                          (jd.get("endpoints") or {}).items()}
        job._shutdown_ranks = {int(r) for r in jd.get("down") or []}
        m = jd.get("member")
        if job.elastic and job._member is not None and m:
            mv = job._member
            mv.target = int(m.get("target", job.nworkers))
            mv.live = {int(r) for r in m.get("live") or []}
            mv.evicted = {int(r) for r in m.get("evicted") or []}
            mv.joining = {int(r) for r in m.get("joining") or []}
            mv.generation = int(m.get("generation", 0))
            mv.evictions = int(m.get("evictions", 0))
            mv.admissions = int(m.get("admissions", 0))
        if jd.get("closed"):
            job.close(str(jd.get("closed_reason", "")))
        if jid != _jobs_mod.DEFAULT_JOB and job.open:
            world._orphan_jobs.add(jid)
    world._jobs = keep


def _replay_apply(world, kind: str, data: dict) -> None:
    """Apply ONE journaled ``(kind, data)`` record to ``world`` — a
    Tracker mid-construction or a :class:`_ReplayWorld`. Raw mutations
    are deliberate: this IS the WAL API's read side."""
    from ..telemetry import skew as _skew_mod
    if kind == _wal_mod.SNAPSHOT_KIND:
        _replay_adopt_into(world, data.get("state") or {})
        return
    jid = str(data.get("job", _jobs_mod.DEFAULT_JOB))
    if kind == "job_open":
        # a journaled open proves multi-job was on when written
        world.multi_job = True
        prev = world._jobs.get(jid)
        if prev is None or not prev.open:
            world._jobs[jid] = _jobs_mod.JobState(
                jid, int(data.get("nworkers", world.nworkers)),
                elastic=bool(data.get("elastic", False)),
                sched_class=int(data.get("sched_class", 0)),
                sched_weight=float(data.get("weight", 1.0)))
            if jid != _jobs_mod.DEFAULT_JOB:
                world._orphan_jobs.add(jid)
        return
    if kind == "job_close":
        closing = world._jobs.get(jid)
        if closing is not None:
            closing.close(str(data.get("reason", "")))
        world._orphan_jobs.discard(jid)
        return
    job = world._jobs.get(jid)
    if job is None:
        # tagged records outlived a torn job_open: the tags
        # themselves prove the job existed — adopt it
        world.multi_job = True
        job = _jobs_mod.JobState(jid, world.nworkers,
                                 elastic=world.elastic)
        world._jobs[jid] = job
        if jid != _jobs_mod.DEFAULT_JOB:
            world._orphan_jobs.add(jid)
    if kind == "assign":
        job._ranks[str(data["task"])] = int(data["rank"])
    elif kind == "epoch":
        job._epoch = int(data["epoch"])
        if job.elastic and job._member is not None:
            job._member.formed(data.get("members", []))
    elif kind == "park":
        if job.elastic and job._member is not None:
            job._member.park(int(data["rank"]))
    elif kind == "evict":
        if job.elastic and job._member is not None:
            job._member.evict(int(data["rank"]))
    elif kind == "quota":
        # a preemption's capacity transfer survives a resume:
        # without this the victim would re-claim its full
        # nworkers and over-commit the fleet cap
        job.quota = int(data.get("quota", job.quota))
        job.preempted = int(data.get("preempted", job.preempted))
    elif kind == "topo":
        job._topo = dict(data.get("doc") or {})
    elif kind == "skew":
        digest = dict(data.get("digest") or {})
        job._skew = digest
        job._skew_election = _skew_mod.FleetElection.seeded(digest)
    elif kind == "endpoint":
        job._endpoints[str(data["task"])] = dict(data["doc"])
    elif kind == "down":
        job._shutdown_ranks.add(int(data["rank"]))
    elif kind == "resume":
        world.restarts = int(data.get("restarts", world.restarts))
    elif kind == "promoted":
        # a journaled failover outlives the promoted process: a
        # later resume keeps reporting the measured duration
        world.promoted_wall = float(data.get("wall", 0.0))
        world.promoted_mono = float(data.get("mono", 0.0))
        world.failover_duration_ms = float(data.get("failover_ms", 0.0))
    elif kind == _wal_mod.LEASE_KIND:
        world._lease = dict(data)
        world._journaled_lease = dict(data)


def fold_records(records, nworkers: int = 1,
                 elastic: bool = False) -> dict:
    """Fold a replayed ``(kind, data)`` list into one
    ``wal_snapshot/v1`` state doc — the offline half of snapshot
    compaction (``wal.py --compact``). ``nworkers``/``elastic`` must
    match the tracker launch shape, the same requirement ``--resume``
    itself has."""
    world = _ReplayWorld(nworkers, elastic)
    for kind, data in records:
        _replay_apply(world, kind, data)
    return snapshot_state(world)


def forming_timeout_ms() -> int:
    """``rabit_job_forming_timeout_ms`` (doc/parameters.md): close an
    open multi-job that has held an admission slot this long with no
    registered rank, no pending registration, and no wire contact
    (0 disables, the default). Guards a serving fleet against ghost
    jobs — admitted from the FIFO queue after their submitter gave up
    waiting, or flooded in by a submit storm — that would otherwise
    jam admission capacity forever."""
    v = os.environ.get("RABIT_JOB_FORMING_TIMEOUT_MS")
    if not v:
        return FORMING_TIMEOUT_MS_DEFAULT
    try:
        return max(0, int(v))
    except ValueError:
        raise ValueError(
            f"RABIT_JOB_FORMING_TIMEOUT_MS must be an integer (ms), "
            f"got {v!r}")


class Tracker:
    def __init__(self, nworkers: int, host: str = "127.0.0.1", port: int = 0,
                 coordinator: bool = False,
                 ready_timeout: Optional[float] = None,
                 link_rewrite=None,
                 metrics_port: Optional[int] = None,
                 elastic: Optional[bool] = None,
                 wal_dir: Optional[str] = None,
                 resume: bool = False,
                 lease_ms: Optional[int] = None,
                 node_id: str = "leader",
                 multi_job: Optional[bool] = None):
        self.nworkers = nworkers            # fleet-global: default-job target
        # elastic world membership (ISSUE 9): when on, the tracker is
        # the membership authority for the live job — dead ranks are
        # EVICTED (``evict`` command, or poll evidence of a silent
        # endpoint) so survivors re-form at world N-1 instead of
        # stalling for the exact replacement, and late joiners are
        # parked (``join`` command) until the next epoch boundary
        # re-admits them back toward the target world. Off by default:
        # with ``rabit_elastic`` unset every registration batch waits
        # for the full fixed world exactly as before.
        if elastic is None:
            elastic = _membership.elastic_enabled()
        self.elastic = bool(elastic)        # fleet-global: job default
        # multi-job control plane (ISSUE 15): per-world state lives on
        # JobState objects (tracker/jobs.py); the tracker itself keeps
        # only fleet-global machinery (every attribute assigned on
        # ``self`` below is annotated so — lint R007 enforces the
        # split). The implicit ``default`` job always exists: with
        # ``rabit_multi_job`` unset it IS the tracker's one world and
        # task ids are never split, so wire and WAL bytes are identical
        # to the single-job control plane.
        if multi_job is None:
            multi_job = _jobs_mod.multi_job_enabled()
        self.multi_job = bool(multi_job)    # fleet-global: mode flag
        self._default = _jobs_mod.JobState(     # fleet-global: implicit job
            _jobs_mod.DEFAULT_JOB, nworkers, elastic=self.elastic)
        self._jobs: Dict[str, _jobs_mod.JobState] = {  # fleet-global: job table
            _jobs_mod.DEFAULT_JOB: self._default}
        # admission control (ISSUE 15): caps snapshot at construction,
        # a bounded FIFO for submissions that do not fit right now
        self._admission = _jobs_mod.AdmissionQueue()   # fleet-global
        self._max_jobs = _jobs_mod.max_jobs()          # fleet-global: cap
        self._max_fleet_ranks = _jobs_mod.max_fleet_ranks()  # fleet-global
        # admitted-verdict tally: with queued_total/shed_total it is
        # the shed-rate SLO's denominator (telemetry/slo.py, ISSUE 17)
        self.submit_admitted_total = 0                 # fleet-global
        # jobs re-adopted from the WAL whose membership has not yet
        # re-presented: if none of a job's tasks makes wire contact
        # within the resume grace window, the job is dead weight from
        # before the crash — the reaper closes it ("orphaned") so it
        # stops eating admission capacity forever
        self._orphan_jobs: set = set()                 # fleet-global
        # last wire contact per job (monotonic, stamped at open):
        # feeds the forming-timeout ghost-job reaper
        self._job_contact: Dict[str, float] = {}       # fleet-global
        # fleet scheduler (ISSUE 19): recent job-close timestamps feed
        # the measured-drain-rate retry_after_ms hint; preemptions are
        # tallied per VICTIM class for the prom exposition
        self._drain_t: Deque[float] = deque(maxlen=16)  # fleet-global
        self.sched_preemptions: Dict[int, int] = {}    # fleet-global
        self.sock = socket.socket(socket.AF_INET,  # fleet-global: listener
                                  socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind((host, port))
        self.sock.listen(256)
        self.host, self.port = self.sock.getsockname()  # fleet-global: addr
        # C10k connection plane (ISSUE 19): ONE selectors event loop
        # owns accept + read + write readiness for every worker
        # connection (tracker/evloop.py); parsed commands flow through
        # per-job FIFO queues into a FIXED service-thread pool. Idle
        # connections cost a file descriptor, not a thread — resident
        # thread count is bounded regardless of connection count.
        self._loop = _evloop.EventLoop()    # fleet-global: readiness loop
        self._svc = _evloop.ServicePool(    # fleet-global: command pool
            name="rabit-tracker-svc")
        self._lock = threading.Lock()       # fleet-global: the tracker lock
        self._cv = threading.Condition(self._lock)  # fleet-global: batch cv
        self._done = threading.Event()      # fleet-global: lifecycle
        self._thread: Optional[threading.Thread] = None  # fleet-global
        self.messages: List[str] = []       # fleet-global: print relay log
        # device-world coordinator hosting (accelerator data plane): one
        # JAX coordination service per registration epoch, living HERE —
        # a service that vanishes under a live client fatally terminates
        # that client's process (jaxlib error-poll thread), so services
        # must be hosted by the one process guaranteed to outlive every
        # worker: the tracker (the reference's tracker daemon plays the
        # same always-alive role, SURVEY §2 #16). Failure detection is
        # the socket control plane's job, so the services' own heartbeat
        # policing is disabled (huge timeout) — a dead worker must not
        # poison the survivors' agents. The services themselves live on
        # each job (an epoch is a job-scoped notion).
        self._coordinator = coordinator     # fleet-global: config flag
        self._ready_timeout = (             # fleet-global: config
            ready_timeout if ready_timeout is not None
            else _default_ready_timeout())
        # chaos hook: ``link_rewrite(peer_rank, host, port) -> (host,
        # port)`` rewrites the peer addresses advertised in _assign so
        # worker->worker links route through fault-injection proxies.
        # Rewritten peers get an EMPTY uds_token: the UDS fast path
        # would bypass a TCP proxy entirely (the token resolves on the
        # peer's host, not at the proxy).
        self._link_rewrite = link_rewrite   # fleet-global: chaos hook
        # live observability plane (off unless rabit_metrics_port /
        # RABIT_METRICS_PORT is configured): workers announce their
        # /metrics endpoints via the ``endpoint`` command; a poller
        # thread scrapes each rank's /summary on an interval and feeds
        # the SAME per-task metrics dict the end-of-run merge uses, so
        # the tracker's own /metrics serves a mid-run fleet view
        if metrics_port is None:
            raw = os.environ.get("RABIT_METRICS_PORT")
            metrics_port = int(raw) if raw not in (None, "") else None
        self._metrics_port = metrics_port   # fleet-global: config
        self._metrics_server = None         # fleet-global: one server
        self._poll_thread: Optional[threading.Thread] = None  # fleet-global
        self._poll_stop = threading.Event()  # fleet-global
        self._poll_count = 0                # fleet-global: sweep counter
        # causal incident plane (ISSUE 20): HLC-stamped fleet events +
        # automated root-cause attribution, all off unless the
        # ``rabit_events``/RABIT_EVENTS master knob is set. With the
        # knob unset none of this grows a wire byte, a route, or a
        # gauge — the control plane is byte-identical to before. With
        # it set: worker summaries carry their event rings, the tracker
        # folds them (dedup by per-task seq) into one fleet event log
        # served at /events, JSON-str wire replies piggyback the
        # tracker's HLC so worker clocks causally follow the control
        # plane, and the poll loop runs an incident sweep correlating
        # SLO burns and watchdog aborts against the event log
        # (served at /incidents, dumped alongside flight records).
        self._events_on = _events.enabled()  # fleet-global: plane flag
        self._fleet_events: Deque[dict] = deque(  # fleet-global: event log
            maxlen=4 * _events.stats()["capacity"])
        self._event_seen: Dict[str, int] = {}   # fleet-global: dedup seqs
        self._event_drops: Dict[str, int] = {}  # fleet-global: per-task
        # leaf lock serializing fold cursors only — never held while
        # acquiring any other lock (C002)
        self._events_fold_lock = threading.Lock()  # fleet-global
        self._incidents = _incident.IncidentBook()  # fleet-global
        self._incident_log: Deque[dict] = deque(maxlen=64)  # fleet-global
        self._slo_prev: Dict[str, str] = {}  # fleet-global: slo edges
        if self._events_on:
            _clock.set_node(f"tracker:{node_id}")
        # crash-recoverable control plane (ISSUE 10): when a WAL dir is
        # configured (``rabit_tracker_wal_dir``), every control-plane
        # transition below is journaled through tracker/wal.py BEFORE
        # it takes effect, and ``resume=True`` replays the journal to
        # re-adopt a live world after a tracker crash — same ranks,
        # same epoch, no worker restart. With the knob unset every
        # ``_wal`` call below is a no-op and behavior is byte-identical
        # to a WAL-less tracker. The root journal is THE authority (and
        # the replicated one); non-default jobs additionally mirror
        # their records into ``<wal_dir>/<job_id>/`` so one job's
        # journal can be inspected (wal.py --inspect) in isolation.
        if wal_dir is None:
            wal_dir = os.environ.get(_wal_mod.WAL_DIR_ENV) or None
        self.wal_dir = wal_dir              # fleet-global: journal home
        self._wal_log: Optional[_wal_mod.WriteAheadLog] = None  # fleet-global
        self._job_wals: Dict[str, _wal_mod.WriteAheadLog] = {}  # fleet-global
        self.restarts = 0                   # fleet-global: resume counter
        self.crashed = False                # fleet-global: crash() flag
        self._grace_until = 0.0             # fleet-global: resume grace
        # hot-standby leadership + WAL streaming replication (ISSUE 12):
        # only engaged when ``lease_ms`` is set (the launcher passes it
        # through ``rabit_tracker_standby``). The leader journals a
        # lease renewal every lease_ms/3 and streams every WAL record
        # to ``repl`` subscribers; with lease_ms unset none of this
        # exists — no lease records, no extra threads, no new gauges —
        # so a PR 10 configuration is byte-identical.
        self.lease_ms = int(lease_ms) if lease_ms else None  # fleet-global
        self.node_id = str(node_id)         # fleet-global: identity
        self.promoted = False               # fleet-global: standby flag
        # failover measurement (ISSUE 17): the standby stamps both
        # clocks at promotion — wall for humans and cross-host logs,
        # monotonic for the duration arithmetic — plus the measured
        # leader-kill -> promoted duration, journaled as a "promoted"
        # record so a later resume keeps serving the same gauge
        self.promoted_wall = 0.0            # fleet-global: failover stamp
        self.promoted_mono = 0.0            # fleet-global: failover stamp
        self.failover_duration_ms = 0.0     # fleet-global: failover span
        self._lease: Optional[dict] = None  # fleet-global: leadership
        self._lease_thread: Optional[threading.Thread] = None  # fleet-global
        # the replication side never touches self._lock (``_wal`` runs
        # under it in several paths): frames live under their own
        # condition, appended by ``_wal`` and drained per-subscriber
        self._repl_cv = threading.Condition()   # fleet-global: repl plane
        # frame i carries seq _repl_base + i + 1. The base is CONSTANT
        # per process: a live compaction APPENDS its snapshot frame to
        # this in-memory log (contiguous seq), it never truncates it —
        # only a journal that was ALREADY compacted when this process
        # opened it starts the log past seq 1.
        self._repl_log: List[bytes] = []    # fleet-global: stream frames
        self._repl_base = 0                 # fleet-global: stream offset
        self._repl_subs: List[dict] = []    # fleet-global: subscribers
        # newest ephemeral lease heartbeat (a seq-0 frame) + a counter
        # so each subscriber can tell "a fresher one arrived"; only the
        # newest matters, so heartbeats are a slot, not a log
        self._repl_hb: Optional[bytes] = None   # fleet-global
        self._repl_hb_n = 0                 # fleet-global
        # the lease doc last actually journaled (vs merely heartbeat):
        # a renewal that matches it except for until_ms is idempotent
        # and stays out of the journal entirely
        self._journaled_lease: Optional[dict] = None  # fleet-global
        # WAL snapshot compaction (ISSUE 19): fold the live state into
        # a snapshot-root every N journaled records (off by default);
        # the pending flag keeps at most one compaction in flight
        self._snap_every = _wal_mod.snapshot_every()   # fleet-global
        self._snap_pending = False          # fleet-global: one in flight
        if wal_dir is not None:
            self._wal_log = _wal_mod.WriteAheadLog(wal_dir)  # fleet-global
            records = self._wal_log.open(resume=resume)
            base = self._wal_log.base
            self._repl_base = base          # fleet-global: stream offset
            self._repl_log = [              # fleet-global: repl backfill
                _wal_mod.encode_record(base + i + 1, kind, data)
                for i, (kind, data) in enumerate(records)]
            if resume:
                self._replay(records)
                self.restarts += 1
                self._wal("resume", restarts=self.restarts,
                          epoch=self._epoch)
                self._grace_until = (time.monotonic()
                                     + resume_grace_ms() / 1e3)
                self._note_resume(len(records))

    # -- per-world delegation (ISSUE 15) ----------------------------------
    # The single-job read surface: every per-world attribute the rest
    # of the repo historically read off the tracker (wal/standby
    # smokes, launch, tests) resolves to the implicit default job.
    # Tracker code itself mutates JobState fields through an explicit
    # ``job`` reference, never through these shims.
    @property
    def _ranks(self) -> Dict[str, int]:
        return self._default._ranks

    @property
    def _pending(self) -> Dict[int, tuple]:
        return self._default._pending

    @property
    def _epoch(self) -> int:
        return self._default._epoch

    @property
    def _shutdown_ranks(self) -> set:
        return self._default._shutdown_ranks

    @property
    def _metrics(self) -> Dict[str, dict]:
        return self._default._metrics

    @property
    def _endpoints(self) -> Dict[str, dict]:
        return self._default._endpoints

    @property
    def _topo(self) -> dict:
        return self._default._topo

    @property
    def _skew(self) -> dict:
        return self._default._skew

    @_skew.setter
    def _skew(self, digest: dict) -> None:
        # test seam (test_skew forces served digests); production
        # writers go through the poll loop's job reference + _wal
        self._default._skew = digest

    @property
    def _member(self):
        return self._default._member

    @property
    def _resumed_ranks(self) -> set:
        return self._default._resumed_ranks

    @property
    def _last_straggler(self) -> Optional[dict]:
        return self._default._last_straggler

    def job(self, job_id: str) -> Optional[_jobs_mod.JobState]:
        """The JobState for ``job_id`` (None = never opened)."""
        with self._lock:
            return self._jobs.get(str(job_id))

    def _replay(self, records) -> None:
        """Restore journaled control-plane state (constructor only,
        before the serve thread exists — no locking needed). The
        per-record application is the module-level ``_replay_apply``,
        shared byte-for-byte with snapshot compaction's fold (lint
        R003 exempts the ``_replay*`` family — they ARE the WAL API's
        read side). Records tagged ``job`` replay into that job's
        state; ``job_open``/``job_close`` rebuild the job table; a
        ``snapshot`` record (ISSUE 19) replaces the whole table with
        its folded state and the tail replays on top — so a resume (or
        a standby promotion) re-adopts EVERY live job with its own
        epoch in time bounded by live state, not history."""
        for kind, data in records:
            _replay_apply(self, kind, data)
        for job in self._jobs.values():
            if job.open and job._epoch > 0:
                job.mark_live()

    def _wal(self, kind: str, _job=None, **data) -> None:
        """Journal one control-plane transition (no-op when the WAL is
        off). Callers invoke this BEFORE acting on the transition —
        the journal is write-ahead, so a crash between journal and
        action replays the intent, never loses it. Every journaled
        record is also published to ``repl`` subscribers as the exact
        frame bytes that hit the disk (re-encoding is byte-identical:
        canonical JSON).

        Multi-job (ISSUE 15): pass the owning JobState as ``_job``.
        Non-default jobs get a ``job`` key stamped into the record (the
        replay side routes on it) and the record mirrored — best-effort
        — into the job's own ``<wal_dir>/<job_id>/`` journal for
        isolated inspection. Default-job records stay untagged, so a
        multi-job-off WAL is byte-identical to the single-job one."""
        if self._wal_log is None:
            return
        jid = None
        if self.multi_job:
            if _job is not None and \
                    _job.job_id != _jobs_mod.DEFAULT_JOB:
                jid = _job.job_id
                data = dict(data)
                data["job"] = jid
            elif str(data.get("job", _jobs_mod.DEFAULT_JOB)) \
                    != _jobs_mod.DEFAULT_JOB:
                jid = str(data["job"])      # job_open / job_close
        with self._repl_cv:
            if kind == _wal_mod.LEASE_KIND and \
                    _wal_mod.lease_renewal_only(self._journaled_lease,
                                                data):
                # idempotent renewal (same owner, same width, only
                # until_ms advanced): keep it OUT of the journal — at
                # one beat per lease_ms/3 a multi-day job would grow
                # the WAL, this replication log, and every future
                # replay without bound — and ship it to subscribers as
                # an ephemeral seq-0 heartbeat frame instead. The
                # follower restarts its promotion countdown on receipt
                # and never journals or acks it.
                self._repl_hb = _wal_mod.encode_record(0, kind, data)
                self._repl_hb_n += 1
                self._repl_cv.notify_all()
                return
            # seq assignment and positional publication must be ONE
            # atomic step: journal writers run concurrently (the lease
            # thread beats while connection handlers journal endpoint/
            # join/shutdown transitions), so recording outside this
            # lock would let seq N+1 land in _repl_log before seq N —
            # permanently misindexing the stream ``_serve_repl`` reads
            # positionally. record() takes only the WAL's own
            # leaf-level lock, so nesting it here cannot deadlock.
            seq = self._wal_log.record(kind, **data)
            if kind == _wal_mod.LEASE_KIND:
                self._journaled_lease = dict(data)
            self._repl_log.append(_wal_mod.encode_record(seq, kind, data))
            self._repl_cv.notify_all()
            if jid is not None:
                self._mirror_job_record_locked(jid, kind, data)
            if self._snap_every and not self._snap_pending and \
                    seq - self._wal_log.snapshot_seq >= self._snap_every:
                # compact OFF the journaling path: a service-pool task
                # folds the state under _lock -> _repl_cv (the
                # established order; this frame is already durable)
                self._snap_pending = True
                self._svc.submit("__wal_snapshot__",
                                 self._take_snapshot)

    def _take_snapshot(self) -> None:
        """One live WAL compaction (service-pool task, never the wire
        path): serialize the replay-reachable state under the tracker
        lock, atomically rewrite the journal as snapshot-root + future
        tail, and publish the exact snapshot frame to the replication
        stream (followers adopt it as an append or a seq jump). Open
        per-job mirrors compact best-effort with their own slice."""
        try:
            with self._lock:
                if self._wal_log is None or self.crashed:
                    return
                state = snapshot_state(self)
                with self._repl_cv:
                    _seq, frame = self._wal_log.snapshot(state)
                    self._repl_log.append(frame)
                    self._repl_cv.notify_all()
                    for jid, w in list(self._job_wals.items()):
                        jd = state["jobs"].get(jid)
                        if jd is None:
                            continue
                        try:
                            w.snapshot({"multi_job": True,
                                        "jobs": {jid: jd}})
                        except Exception:  # pragma: no cover - mirror
                            pass
        finally:
            # sole clearing site; worst case a duplicate compaction is
            # scheduled, which folds to the same snapshot
            self._snap_pending = False  # noqa: C003 - advisory flag

    def snapshot_seq(self) -> int:
        """Seq of the newest journaled snapshot (0 = none / WAL off) —
        the ``rabit_wal_snapshot_seq`` gauge."""
        return 0 if self._wal_log is None else self._wal_log.snapshot_seq

    def _mirror_job_record_locked(self, jid: str, kind: str,
                           data: dict) -> None:
        """Best-effort per-job journal mirror under ``_repl_cv`` (the
        caller, ``_wal``). The root journal is authoritative — a mirror
        that cannot open or append is dropped silently rather than ever
        failing a control-plane transition."""
        try:
            w = self._job_wals.get(jid)
            if w is None and kind != "job_close":
                w = _wal_mod.WriteAheadLog(
                    os.path.join(self.wal_dir, jid))
                w.open(resume=os.path.exists(w.path))
                self._job_wals[jid] = w
            if w is not None:
                w.record(kind,
                         **{k: v for k, v in data.items() if k != "job"})
                if kind == "job_close":
                    w.close()
                    self._job_wals.pop(jid, None)
        except Exception:  # pragma: no cover - mirror is best-effort
            pass

    def _note_resume(self, nrecords: int) -> None:
        """Make a tracker resume observable: span + counter + flight
        note, mirroring ``_note_transition``."""
        from .. import telemetry
        from ..telemetry import flight
        telemetry.count("tracker.resume", provenance="tracker")
        telemetry.record_span("tracker.resume", 0.0, op="resume",
                              provenance="tracker",
                              records=nrecords, restarts=self.restarts)
        live_jobs = [j.job_id for j in self._jobs.values() if j.open]
        jobs_note = (f", jobs {sorted(live_jobs)}" if self.multi_job
                     else "")
        flight.note("tracker_resume",
                    f"replayed {nrecords} WAL records, restart "
                    f"#{self.restarts}, epoch {self._epoch}{jobs_note}")
        self._fleet_emit("tracker.resume",
                         f"replayed {nrecords} WAL records, restart "
                         f"#{self.restarts}, epoch {self._epoch}")
        print(f"[tracker] resumed from WAL ({nrecords} records, "
              f"restart #{self.restarts}, epoch {self._epoch}, "
              f"{len(self._ranks)} known ranks{jobs_note})",
              file=sys.stderr, flush=True)

    def wal_records(self) -> int:
        """Journaled transitions so far (0 when the WAL is off)."""
        return 0 if self._wal_log is None else self._wal_log.records_total

    def in_resume_grace(self) -> bool:
        """True while poll-miss eviction evidence is waived after a
        resume (workers are still reconnecting their pollers)."""
        return time.monotonic() < self._grace_until

    # -- leadership lease + WAL replication (ISSUE 12) --------------------
    def _renew_lease(self) -> None:
        """Renew the leadership lease. The CLAIM (first lease, or an
        owner change) is a journaled record in the replicated log;
        renewals that merely advance ``until_ms`` are idempotent and
        ride the stream as ephemeral heartbeats (``_wal`` compacts
        them), so the journal stays bounded by real transitions. The
        standby may only promote after a full lease of silence from
        this stream — its countdown is LOCAL monotonic time restarted
        on every received frame, so the gate needs no clock agreement
        between hosts."""
        lease = _wal_mod.lease_doc(self.node_id, self.lease_ms)
        with self._lock:
            # journal + publish under ONE lock hold so a live snapshot
            # (ISSUE 19) can never capture the state from between them
            self._wal(_wal_mod.LEASE_KIND, **lease)
            self._lease = lease

    def _lease_loop(self) -> None:
        """Heartbeat renewals at a third of the lease, so two missed
        beats still leave the lease live; it lapses only when the
        leader is genuinely gone (crash) or unreachable (partition)."""
        period = max(0.05, self.lease_ms / 3000.0)
        while not self._done.wait(period):
            if self.crashed:
                return
            try:
                self._renew_lease()
            except _wal_mod.WalError:  # pragma: no cover - disk death
                return

    def lease(self) -> Optional[dict]:
        """The newest lease this tracker journaled (None when the
        lease machinery is off)."""
        with self._lock:
            return None if self._lease is None else dict(self._lease)

    def repl_stats(self) -> dict:
        """Replication-plane snapshot: journal seq, live subscribers,
        newest acked seq, and the record lag behind the journal."""
        seq = 0 if self._wal_log is None else self._wal_log.seq
        with self._repl_cv:
            subs = [dict(s) for s in self._repl_subs]
        acked = max((s["acked"] for s in subs), default=0)
        return {"seq": seq, "subscribers": len(subs), "acked_seq": acked,
                "lag_records": max(0, seq - acked)}

    def _serve_repl(self, conn: socket.socket, peer: str) -> None:
        """One ``repl`` subscriber: stream every WAL record at or past
        its resync point, one ack per record. Runs on the connection's
        own ``_handle`` thread for as long as the follower keeps
        acking; a slow or torn follower is dropped (it resubscribes
        from its last durable seq — replication must never be able to
        stall the control plane itself)."""
        if self._wal_log is None:
            _send_u32(conn, 0)   # replication requires a journal
            conn.close()
            return
        _send_u32(conn, 1)
        last = _recv_u32(conn)
        conn.settimeout(repl_ack_timeout_ms() / 1e3)
        sub = {"peer": peer, "acked": last}
        with self._repl_cv:
            self._repl_subs.append(sub)
            hb_seen = self._repl_hb_n
        try:
            # positional cursor into _repl_log: frame idx carries seq
            # _repl_base + idx + 1 (the base is constant per process).
            # A follower acked BELOW the base resynced into a compacted
            # history — it gets the snapshot root first (idx 0) and its
            # journal adopts the seq jump (wal.append_encoded).
            idx = max(0, last - self._repl_base)
            while not self._done.is_set():
                hb = None
                with self._repl_cv:
                    while (len(self._repl_log) <= idx
                           and self._repl_hb_n <= hb_seen
                           and not self._done.is_set()):
                        self._repl_cv.wait(0.2)
                    if self._done.is_set():
                        break
                    if len(self._repl_log) > idx:
                        frame = self._repl_log[idx]
                    else:
                        hb = self._repl_hb
                        hb_seen = self._repl_hb_n
                if hb is not None:
                    # ephemeral lease heartbeat (seq 0): fire and
                    # forget — the follower restarts its promotion
                    # countdown on receipt, never journals or acks it
                    conn.sendall(hb)
                    continue
                conn.sendall(frame)
                ack = _recv_u32(conn)
                if ack != self._repl_base + idx + 1:
                    break   # confused follower: drop it, it resyncs
                with self._repl_cv:
                    sub["acked"] = ack
                idx += 1
        except (OSError, ConnectionError, struct.error):
            pass
        finally:
            with self._repl_cv:
                if sub in self._repl_subs:
                    self._repl_subs.remove(sub)
            try:
                conn.close()
            except OSError:
                pass

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "Tracker":
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()
        self._start_live_plane()
        if self.lease_ms and self._wal_log is not None:
            self._renew_lease()
            self._lease_thread = threading.Thread(
                target=self._lease_loop, name="rabit-tracker-lease",
                daemon=True)
            self._lease_thread.start()
        return self

    def join(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def stop(self) -> None:
        self._done.set()
        self._poll_stop.set()
        with self._repl_cv:
            self._repl_cv.notify_all()  # unblock repl streamers
        if self._metrics_server is not None:
            self._metrics_server.stop()
            # main-thread lifecycle handoff; serving threads are gone
            self._metrics_server = None  # noqa: C003
        # stop the command pool before the loop: queued handlers may
        # still want to queue replies, and the loop's teardown drains
        # its op queue once more so those final acks actually flush
        self._svc.stop()
        self._loop.stop()
        try:
            self.sock.close()
        except OSError:
            pass
        # workers have exited (or been killed) by now, so no live client
        # can be poisoned by its service going away; snapshot under the
        # lock, shut down outside it (shutdown() can block on joins)
        with self._lock:
            services = []
            for jb in self._jobs.values():
                services.extend(jb._services)
                jb._services = []
        for _epoch, svc in services:
            try:
                svc.shutdown()
            except Exception:
                pass
        if self._wal_log is not None and not self.crashed:
            self._wal_log.close()
            with self._repl_cv:
                mirrors = list(self._job_wals.values())
                self._job_wals.clear()
            for w in mirrors:
                try:
                    w.close()
                except Exception:  # pragma: no cover - best-effort
                    pass

    def crash(self) -> None:
        """Simulate a tracker crash (tests, chaos ``tracker_kill``):
        the listening socket and background threads die but NOTHING is
        flushed, closed gracefully, or reaped — exactly the state a
        SIGKILL leaves behind, minus the process exit. The WAL stays
        as the dead incarnation left it (every record was already
        fsynced on append), ready for a ``resume=True`` successor on
        the same pinned port."""
        # happens-once flag flipped before the threads it gates are
        # torn down; readers tolerate either value during the flip
        self.crashed = True  # noqa: C003
        self._done.set()
        self._poll_stop.set()
        with self._repl_cv:
            self._repl_cv.notify_all()  # repl streamers die un-flushed
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None  # noqa: C003 - lifecycle teardown
        # closing the loop hard-drops every in-flight connection — the
        # closest a live process gets to SIGKILL's half-open sockets
        self._svc.stop()
        self._loop.stop()
        try:
            self.sock.close()
        except OSError:
            pass
        # deliberately NOT closed/reaped: the WAL file handles and any
        # coordination services — a real crash wouldn't either
        with self._cv:
            self._cv.notify_all()  # unblock parked joiners

    def service_count(self) -> int:
        """Live coordination services across every job (bounded: old
        epochs are reaped per job)."""
        with self._lock:
            return sum(len(jb._services) for jb in self._jobs.values())

    def _new_coordinator(self, job, epoch: int,
                         world: Optional[int] = None) -> Tuple[str, int]:
        """Start this epoch's coordination service on a fresh port,
        sized to the epoch's world (an elastic epoch may be smaller
        than the launch-time target).

        The free-port probe binds with the same family/wildcard the
        service will use (an IPv4-loopback probe says nothing about the
        IPv6 wildcard), with an IPv4 fallback for IPv6-disabled hosts;
        the bind-close-start race remains but is at least sampling the
        right namespace."""
        compat = _require_coordinator_api()
        last_err: Optional[Exception] = None
        for family, bind_host, fmt in (
                (socket.AF_INET6, "::", "[::]:{p}"),
                (socket.AF_INET, "0.0.0.0", "0.0.0.0:{p}")):
            try:
                probe = socket.socket(family, socket.SOCK_STREAM)
            except OSError as e:
                last_err = e
                continue
            try:
                probe.bind((bind_host, 0))
                port = probe.getsockname()[1]
            except OSError as e:
                last_err = e
                continue
            finally:
                probe.close()
            try:
                # liveness detection off in the service: failure
                # detection is the socket control plane's job
                svc = compat.start_service(
                    fmt.format(p=port),
                    job.nworkers if world is None else world)
            except Exception as e:  # noqa: BLE001 - retried on next family
                last_err = e
                continue
            with self._lock:
                job._services.append((epoch, svc))
            return (self.host, port)
        raise RuntimeError(
            f"could not start device-world coordination service: {last_err}")

    def _reap_old_services(self, job, acked_epoch: int) -> None:
        """Drop services older than the epoch whose members ALL acked:
        the teardown-before-ack contract guarantees no live client of an
        older epoch exists, so shutting their services down cannot poison
        anyone. Keeps service/port/thread count bounded regardless of
        failure count (VERDICT r2 weak #5). Per job: one job's reap
        never touches a neighbor's services."""
        with self._lock:
            keep = [(e, s) for e, s in job._services if e >= acked_epoch]
            dead = [(e, s) for e, s in job._services if e < acked_epoch]
            job._services = keep
        for _e, svc in dead:
            try:
                svc.shutdown()
            except Exception:  # pragma: no cover - best-effort
                pass

    def merged_metrics(self) -> Optional[dict]:
        """Fleet-merged ``telemetry_fleet`` doc from the per-rank
        summaries shipped so far, or None when no worker shipped any.
        Multi-job: the union across jobs, task keys re-qualified as
        ``<job>/<task>`` so same-named tasks in different jobs cannot
        collide."""
        with self._lock:
            if self.multi_job:
                snap = {}
                for jid, jb in self._jobs.items():
                    for t, d in jb._metrics.items():
                        snap[_jobs_mod.job_task(jid, t)] = d
            else:
                snap = dict(self._default._metrics)
        if not snap:
            return None
        return merge_summaries(snap)

    # -- live observability plane -----------------------------------------
    def _start_live_plane(self) -> None:
        """Fleet metrics endpoint + per-rank poller (off unless a
        metrics port was configured). Failure to bind is a warning, not
        a run killer — observability must never block rendezvous."""
        if self._metrics_port is None:
            return
        from ..telemetry import live
        identity = {"role": "tracker", "nworkers": self.nworkers}
        if self.multi_job:
            identity["multi_job"] = True
        if self.lease_ms:
            # the supervisor's pre-respawn probe and the worker-side
            # failover discovery both read this: a tracker that answers
            # /healthz with tracker_role "leader" IS the control plane
            # (a promoted standby says so too — that is the point)
            identity.update({"tracker_role": "leader",
                             "node": self.node_id,
                             "promoted": bool(self.promoted)})
        try:
            # poll thread starts only after this store completes
            self._metrics_server = live.MetricsServer(  # noqa: C003
                port=self._metrics_port,
                sources_fn=self._metric_sources,
                summary_fn=lambda: self.merged_metrics() or {},
                gauges_fn=self._live_gauges,
                identity=identity,
                routes=self._live_routes(),
            ).start()
        except OSError as e:
            print(f"[tracker] metrics server failed to bind port "
                  f"{self._metrics_port}: {e}", file=sys.stderr, flush=True)
            return
        # port 0 auto-assigns; without this line the endpoint would be
        # undiscoverable from the launch CLI
        print(f"[tracker] live metrics on "
              f"{self._metrics_server.host}:{self._metrics_server.port}",
              file=sys.stderr, flush=True)
        self._poll_stop.clear()
        self._poll_thread = threading.Thread(
            target=self._poll_loop, name="rabit-tracker-poll", daemon=True)
        self._poll_thread.start()

    def _live_routes(self) -> dict:
        """Extra JSON routes on the live endpoint. /events and
        /incidents exist only while the incident plane is on — an
        unconfigured tracker's route table is unchanged."""
        routes = {"/straggler": self._straggler_doc,
                  "/jobs": self._jobs_doc,
                  "/slo": self._slo_doc}
        if self._events_on:
            routes["/events"] = self._events_doc
            routes["/incidents"] = self._incidents_doc
        return routes

    def _jl(self, jid: str, **labels) -> Dict[str, str]:
        """Gauge labels for one job's row: a ``job`` label only when
        multi-job is on, so a single-job /metrics page is byte-identical
        to the pre-ISSUE-15 exposition."""
        if self.multi_job:
            labels["job"] = jid
        return labels

    def _metric_sources(self) -> list:
        """One Prometheus source per polled rank: the per-rank summary
        labelled with its rank (and its job when multi-job), so one
        tracker scrape shows every rank's collective counters side by
        side."""
        with self._lock:
            jobs_now = (list(self._jobs.values()) if self.multi_job
                        else [self._default])
            rows = []
            for jb in jobs_now:
                for doc in jb._metrics.values():
                    rows.append((self._jl(jb.job_id,
                                          rank=str(doc.get("rank", -1))),
                                 doc))
        return rows

    def _job_snapshots_locked(self) -> List[dict]:
        """Per-job gauge snapshot taken under the lock: one dict per
        job (exactly the default job when multi-job is off)."""
        jobs_now = (list(self._jobs.values()) if self.multi_job
                    else [self._default])
        snap = []
        for jb in jobs_now:
            m = jb._member
            snap.append({
                "id": jb.job_id, "status": jb.status,
                "elastic": jb.elastic,
                "nend": len(jb._endpoints),
                "topo": dict(jb._topo), "skew": dict(jb._skew),
                "strag": jb._last_straggler,
                "world": (m.world() if jb.elastic and m is not None
                          else jb.nworkers),
                "evictions": (m.evictions if jb.elastic and m is not None
                              else 0),
                "admissions": (m.admissions
                               if jb.elastic and m is not None else 0),
                "quarantined": jb.quarantined,
            })
        return snap

    def _live_gauges(self) -> list:
        with self._lock:
            snap = self._job_snapshots_locked()
            polls = self._poll_count
            queued_total = self._admission.queued_total
            shed_total = self._admission.shed_total
            preempt = dict(self.sched_preemptions)
        qdepth = len(self._admission)
        gauges = [
            ("rabit_tracker_endpoints",
             "Worker metrics endpoints known to the tracker.",
             "gauge", [(self._jl(s["id"]), s["nend"]) for s in snap]),
            ("rabit_tracker_polls_total",
             "Completed endpoint poll sweeps.", "counter", [({}, polls)]),
            ("rabit_tracker_open_conns",
             "Worker connections currently held by the selectors event "
             "loop (each costs a descriptor and a buffer, never a "
             "thread).", "gauge", [({}, self._loop.open_conns)]),
            ("rabit_tracker_loop_lag_ms",
             "EWMA of loop wakeup service time — the delay a newly "
             "ready connection waits behind the current batch.",
             "gauge", [({}, round(self._loop.lag_ms(), 4))]),
        ]
        if self._wal_log is not None:
            gauges.append((
                "rabit_tracker_restarts_total",
                "Tracker crash-resume cycles (WAL replay + live-world "
                "re-adoption).", "counter", [({}, self.restarts)]))
            gauges.append((
                "rabit_wal_records_total",
                "Control-plane transitions journaled to the tracker "
                "write-ahead log.", "counter",
                [({}, self._wal_log.records_total)]))
            gauges.append((
                "rabit_wal_snapshot_seq",
                "Seq of the journal's most recent snapshot record (0 "
                "until one exists) — replay cost is bounded by the "
                "tail past this point.", "gauge",
                [({}, self._wal_log.snapshot_seq)]))
        if self.lease_ms and self._wal_log is not None:
            repl = self.repl_stats()
            gauges.append((
                "rabit_tracker_role",
                "Control-plane role: 1 while this tracker holds the "
                "leadership lease and serves the world (a promoted "
                "standby reports 1 too — by then it IS the leader).",
                "gauge", [({"node": self.node_id}, 1)]))
            gauges.append((
                "rabit_repl_acked_seq",
                "Newest WAL seq a standby has durably acked (0 with "
                "no subscriber).", "gauge", [({}, repl["acked_seq"])]))
            gauges.append((
                "rabit_repl_lag_records",
                "Journaled records not yet acked by the standby — the "
                "bounded data loss of a failover right now.",
                "gauge", [({}, repl["lag_records"])]))
        elastic_rows = [s for s in snap if s["elastic"]]
        if elastic_rows:
            gauges.append((
                "rabit_world_size",
                "Live world size of the current membership epoch "
                "(elastic jobs shrink below the launch target and "
                "grow back on re-admission).", "gauge",
                [(self._jl(s["id"]), s["world"]) for s in elastic_rows]))
            gauges.append((
                "rabit_member_evictions_total",
                "Ranks evicted from the live job (watchdog/poll "
                "evidence or the evict command).", "counter",
                [(self._jl(s["id"]), s["evictions"])
                 for s in elastic_rows]))
            gauges.append((
                "rabit_member_admissions_total",
                "Parked joiners admitted at an epoch boundary.",
                "counter", [(self._jl(s["id"]), s["admissions"])
                            for s in elastic_rows]))
        topo_rows = [s for s in snap if s["topo"].get("groups")]
        if topo_rows:
            gauges.append((
                "rabit_tracker_topology_hosts",
                "Distinct hosts in the current link-registration epoch.",
                "gauge",
                [(self._jl(s["id"]), len(s["topo"]["groups"]))
                 for s in topo_rows]))
            rows = []
            for s in topo_rows:
                sizes = [len(g) for g in s["topo"]["groups"]]
                rows.append((self._jl(s["id"], stat="min"), min(sizes)))
                rows.append((self._jl(s["id"], stat="max"), max(sizes)))
            gauges.append((
                "rabit_tracker_topology_ranks_per_host",
                "Ranks per host (max label distinguishes ragged "
                "groupings, which disable the hierarchical schedule).",
                "gauge", rows))
        strag_rows = [s for s in snap
                      if s["strag"] is not None
                      and s["strag"].get("lagging_rank") is not None]
        if strag_rows:
            gauges.append((
                "rabit_straggler_lag_collectives",
                "Collectives the laggard is behind the leader.", "gauge",
                [(self._jl(s["id"],
                           rank=str(s["strag"]["lagging_rank"])),
                  s["strag"]["lag_collectives"]) for s in strag_rows]))
            gauges.append((
                "rabit_straggler_busy_skew_seconds",
                "Spread of per-rank collective busy time.", "gauge",
                [(self._jl(s["id"]), s["strag"]["busy_skew_s"])
                 for s in strag_rows]))
        skew_rows = [s for s in snap if s["skew"].get("offsets_ms")]
        if skew_rows:
            offset_rows = []
            for s in skew_rows:
                offset_rows.extend(
                    (self._jl(s["id"], rank=str(r)), v)
                    for r, v in sorted(s["skew"]["offsets_ms"].items(),
                                       key=lambda kv: int(kv[0])))
            gauges.append((
                "rabit_skew_offset_ms",
                "Per-rank mean arrival offset behind the earliest rank "
                "(the skew digest served to workers).", "gauge",
                offset_rows))
            gauges.append((
                "rabit_skew_epoch",
                "Fleet skew election epoch (bumps when the served "
                "laggard verdict changes).",
                "gauge", [(self._jl(s["id"]), s["skew"].get("epoch", 0))
                          for s in skew_rows]))
        if self.multi_job:
            by_status: Dict[str, int] = {}
            for s in snap:
                by_status[s["status"]] = by_status.get(s["status"], 0) + 1
            gauges.append((
                "rabit_tracker_jobs",
                "Jobs known to this tracker by lifecycle status "
                "(forming/live/failed/closed).", "gauge",
                [({"status": st}, n)
                 for st, n in sorted(by_status.items())]))
            gauges.append((
                "rabit_admission_queue_depth",
                "Submitted jobs parked in the bounded FIFO admission "
                "queue right now.", "gauge", [({}, qdepth)]))
            gauges.append((
                "rabit_admission_queued_total",
                "Job submissions parked (they retry after the hinted "
                "backoff).", "counter", [({}, queued_total)]))
            gauges.append((
                "rabit_admission_shed_total",
                "Job submissions shed past the queue depth — overload "
                "answered with backoff, never a stall.", "counter",
                [({}, shed_total)]))
            gauges.append((
                "rabit_job_quarantined_total",
                "Commands quarantined at this job's fault boundary "
                "(exceptions that never reached the accept loop).",
                "counter", [(self._jl(s["id"]), s["quarantined"])
                            for s in snap]))
            gauges.append((
                "rabit_sched_preemptions_total",
                "Ranks preempted from running jobs by priority-class "
                "admission, labeled by the VICTIM's class.", "counter",
                [({"sched_class": str(c)}, n)
                 for c, n in sorted(preempt.items())] or [
                     ({"sched_class": "0"}, 0)]))
        if self.promoted:
            gauges.append((
                "rabit_failover_duration_ms",
                "Leader-kill to standby-promoted duration, stamped by "
                "the control plane at promotion (tracker/standby.py).",
                "gauge", [({"node": self.node_id},
                           round(self.failover_duration_ms, 3))]))
        if self.multi_job or self.lease_ms:
            # SLO burn gauges ride along only where the SLO plane has
            # something to measure (admission or failover) — a plain
            # single-job tracker's exposition stays byte-identical
            from ..telemetry import slo as _slo
            gauges.extend(_slo.gauges(self._slo_verdicts()))
        if self._events_on:
            # incident plane gauges (ISSUE 20): only with the plane on
            # — an unconfigured exposition stays byte-identical
            gauges.extend(_incident.gauges(
                self._incidents.open_docs(),
                self._events_dropped_total()))
        return gauges

    def _slo_verdicts(self) -> list:
        """Tracker-side SLO measurements (telemetry/slo.py): the
        objectives the control plane can see on its own — failover
        time (once promoted) and admission shed rate. Availability
        and collective latency are fleet-side (per-rank histograms,
        the soak harness's round ledger)."""
        from ..telemetry import slo as _slo
        with self._lock:
            shed = self._admission.shed_total
            queued = self._admission.queued_total
            admitted = self.submit_admitted_total
        measured: Dict[str, float] = {}
        if self.promoted and self.failover_duration_ms > 0:
            measured["failover_ms"] = self.failover_duration_ms
        total = admitted + queued + shed
        if total:
            measured["shed_rate"] = shed / total
        slos = [s for s in _slo.default_slos()
                if s.name in ("failover_ms", "shed_rate")]
        return _slo.evaluate_all(slos, measured)

    def _slo_doc(self) -> dict:
        """The ``/slo`` route: per-objective burn state
        (capture_status.py --live folds ``worst`` into the status
        line)."""
        from ..telemetry import slo as _slo
        return _slo.burn_doc(self._slo_verdicts())

    # -- causal incident plane (ISSUE 20) ---------------------------------
    def _fleet_emit(self, kind: str, detail: str = "", job: str = "",
                    rank: int = -1, **attrs) -> None:
        """Tracker-side fleet event: recorded in the process ring,
        then the ring is folded into the fleet log — the tracker is
        its own consumer, control-plane events never need a scrape
        hop. Never takes self._lock (callers hold it in several paths
        and it is not reentrant); the fold's own leaf lock is safe
        under it."""
        if not self._events_on:
            return
        _events.emit(kind, detail=detail, job=job, rank=rank, **attrs)
        self._fold_local_ring()

    def _fold_local_ring(self) -> None:
        """Fold this process's OWN event ring into the fleet log.

        The ring is process-global, so in-process co-tenants — the
        launcher's chaos proxies, a hot standby stamping its
        promotion — share it with the tracker's `_fleet_emit`; folding
        the ring (dedup'd by seq like any worker fold) is what gets
        their events into `/events` and the incident sweep's causal
        window."""
        if not self._events_on:
            return
        with self._events_fold_lock:
            seen = self._event_seen.get("__local__", 0)
            newest = seen
            for rec in _events.snapshot()["records"]:
                seq = rec.get("seq", 0)
                if not isinstance(seq, int) or seq <= seen:
                    continue
                newest = max(newest, seq)
                rec = dict(rec)
                rec["source"] = "tracker"
                self._fleet_events.append(rec)
            self._event_seen["__local__"] = newest

    def _fold_events(self, task_id: str, doc: dict, job) -> None:
        """Fold one worker summary's event ring into the fleet log.

        Records arrive repeatedly (every scrape re-ships the ring);
        the per-task ``seq`` is the dedup cursor. The worker's HLC
        merges into the tracker's clock so every tracker-side stamp
        causally follows everything it has observed, and the worker's
        cumulative ring-drop count feeds the fleet-wide gauge."""
        if not self._events_on or not isinstance(doc, dict):
            return
        _clock.merge_from_doc(doc)
        ev = doc.get("events")
        if not isinstance(ev, dict):
            return
        with self._events_fold_lock:
            seen = self._event_seen.get(task_id, 0)
            newest = seen
            for rec in ev.get("records", ()):
                if not isinstance(rec, dict):
                    continue
                seq = rec.get("seq", 0)
                if not isinstance(seq, int) or seq <= seen:
                    continue
                newest = max(newest, seq)
                rec = dict(rec)
                rec["source"] = task_id
                if job is not None and not rec.get("job"):
                    rec["job"] = job.job_id
                self._fleet_events.append(rec)
            self._event_seen[task_id] = newest
            dropped = ev.get("dropped")
            if isinstance(dropped, int) and dropped >= 0:
                self._event_drops[task_id] = dropped

    def _events_dropped_total(self) -> int:
        """Fleet-wide dropped events: every task's cumulative ring
        drops plus the tracker's own ring."""
        return sum(self._event_drops.values()) \
            + _events.stats()["dropped"]

    def _events_doc(self) -> dict:
        """The ``/events`` route: the folded fleet event log in causal
        order (HLC when stamped, wall time otherwise)."""
        from ..telemetry.schema import make_header
        self._fold_local_ring()
        evs = sorted(self._fleet_events, key=_incident._event_key)
        doc = make_header(_events.EVENT_KIND)
        doc["events"] = evs
        doc["count"] = len(evs)
        doc["dropped"] = self._events_dropped_total()
        return doc

    def _incidents_doc(self) -> dict:
        """The ``/incidents`` route: open incidents plus the recent
        history (capture_status.py --live folds open count, worst
        severity, and the newest attribution line)."""
        open_docs = self._incidents.open_docs()
        return {"open": open_docs,
                "open_count": len(open_docs),
                "worst": self._incidents.worst(),
                "closed_total": self._incidents.closed_total,
                "recent": list(self._incident_log)}

    def _incident_sweep(self) -> None:
        """One poll-loop pass of the incident engine: emit slo.* state
        -change events on verdict edges, correlate each warn/violating
        verdict and each unseen watchdog abort against the fleet event
        log, dump newly opened incidents alongside the flight
        records."""
        from ..telemetry import flight
        self._fold_local_ring()
        verdicts = self._slo_verdicts()
        events_now = list(self._fleet_events)
        opened = []
        for v in verdicts:
            name = str(v.get("slo", "?"))
            state = str(v.get("state", ""))
            if self._slo_prev.get(name) != state:
                self._slo_prev[name] = state
                kind = f"slo.{state}"
                if kind in _events.EVENT_KINDS:
                    self._fleet_emit(
                        kind, f"{name} = {v.get('value')} "
                              f"{v.get('unit', '')} (burn "
                              f"{v.get('burn')})")
            inc = self._incidents.observe_slo(v, events_now)
            if inc is not None:
                opened.append(inc)
        opened.extend(self._incidents.observe_events(events_now))
        if not opened:
            return
        fr = flight.installed()
        out_dir = fr.out_dir if fr is not None \
            else os.environ.get("RABIT_FLIGHT_DIR")
        for inc in opened:
            self._incident_log.append(inc)
            if out_dir:
                _incident.dump(inc, out_dir)
            print(f"[tracker] incident {inc.get('id')} "
                  f"[{inc.get('severity')}]: {inc.get('summary')}",
                  file=sys.stderr, flush=True)

    def _straggler_doc(self) -> dict:
        """The ``/straggler`` route: the default job's snapshot (shape
        unchanged from the single-job tracker) plus — multi-job — a
        ``jobs`` map of every job's own snapshot."""
        with self._lock:
            strag = self._default._last_straggler
            per_job = ({jb.job_id: jb._last_straggler
                        for jb in self._jobs.values()
                        if jb._last_straggler is not None}
                       if self.multi_job else None)
        doc = (dict(strag) if strag is not None
               else {"ranks": [], "signal": False})
        if per_job is not None:
            doc["jobs"] = per_job
        return doc

    def _jobs_doc(self) -> dict:
        """The ``/jobs`` route: per-job health + the admission plane
        (capture_status.py --live renders this)."""
        with self._lock:
            docs = [jb.doc() for jb in self._jobs.values()]
            queued_total = self._admission.queued_total
            shed_total = self._admission.shed_total
        return {"multi_job": bool(self.multi_job), "jobs": docs,
                "queue": self._admission.snapshot(),
                "queued_total": queued_total, "shed_total": shed_total,
                "max_jobs": self._max_jobs,
                "max_fleet_ranks": self._max_fleet_ranks}

    def _poll_loop(self) -> None:
        from ..telemetry import crossrank, live, skew
        interval = live.poll_interval_s()
        since_snapshot = 0
        while not self._poll_stop.wait(interval):
            with self._lock:
                jobs_now = [jb for jb in self._jobs.values() if jb.open]
            polled = False
            since_snapshot += 1
            for job in jobs_now:
                with self._lock:
                    endpoints = dict(job._endpoints)
                if not endpoints:
                    continue
                polled = True
                for tid, ep in endpoints.items():
                    doc = live.scrape_json(ep["host"], ep["port"])
                    if doc is not None:
                        with self._lock:
                            job._metrics[tid] = doc
                            job._endpoint_misses[tid] = 0
                        self._fold_events(tid, doc, job)
                        continue
                    # post-resume grace (ISSUE 10): right after a
                    # tracker resume every poller in the fleet is still
                    # timing out against the OLD incarnation's cadence
                    # — silence here is evidence of the tracker's
                    # outage, not the worker's. Waive it until the
                    # grace window closes.
                    if self.in_resume_grace():
                        with self._lock:
                            job._endpoint_misses[tid] = 0
                        continue
                    # poll evidence of a partition: an endpoint that
                    # HAS answered before and now stays silent for
                    # several sweeps is indistinguishable from a dead
                    # rank to the fleet — in an elastic world that is
                    # grounds for eviction (the watchdog catches the
                    # same failure from the inside; this catches it
                    # when the process is unreachable rather than
                    # crashed). Scoped to the silent endpoint's OWN
                    # job: one job's dead fleet never evicts a
                    # neighbor's rank.
                    with self._lock:
                        seen_before = tid in job._metrics
                        misses = job._endpoint_misses.get(tid, 0) + 1
                        job._endpoint_misses[tid] = misses
                        rank = job._ranks.get(tid)
                        live_rank = (job.elastic and rank is not None
                                     and rank in job._member.live)
                    if (job.elastic and seen_before and live_rank
                            and misses >= _membership.EVICT_POLL_MISSES):
                        self.evict_rank(
                            rank, f"endpoint silent for {misses} polls",
                            job=job)
                with self._lock:
                    summaries = dict(job._metrics)
                    served_epoch = job._skew.get("epoch")
                strag = crossrank.straggler_snapshot(summaries)
                # raw per-sweep offsets fold through the job's OWN
                # election; the served digest is its smoothed,
                # hysteretic verdict with an epoch that bumps on
                # election change (one election per job — job B's
                # laggard must never skew job A's schedules)
                raw = skew.digest_from_snapshot(strag)
                if job._skew_election is None:
                    # poll thread is the sole writer after _replay
                    job._skew_election = skew.FleetElection()
                digest = job._skew_election.fold(raw)
                with self._lock:
                    if digest is not None and \
                            digest.get("epoch") != served_epoch:
                        # journal VERDICTS, not sweeps: the digest's
                        # epoch bumps exactly when the election
                        # changes, so the WAL grows with decisions
                        # rather than poll cadence (journal + act
                        # under one hold: snapshot consistency)
                        self._wal("skew", digest=digest, _job=job)
                    job._last_straggler = strag
                    if digest is not None:
                        job._skew = digest
                # periodic straggler snapshot: one line every ~5
                # sweeps, only while someone is actually behind — in
                # the round sequence, or >1s of in-collective wait
                behind = bool(strag.get("signal")) \
                    and strag.get("lagging_rank") is not None
                if since_snapshot >= 5 and behind:
                    since_snapshot = 0
                    jtag = (f" job {job.job_id}" if self.multi_job
                            else "")
                    print(f"[tracker] straggler:{jtag} rank "
                          f"{strag['lagging_rank']} is "
                          f"{strag['lag_collectives']} collectives "
                          f"behind (busy skew "
                          f"{strag['busy_skew_s']:.3f}s)",
                          file=sys.stderr, flush=True)
            if polled:
                with self._lock:
                    self._poll_count += 1
            if self._events_on:
                # incident sweep rides the poll cadence even when no
                # endpoint answered: tracker-side events (membership,
                # admission, SLO edges) still need correlating
                self._incident_sweep()

    def live_addr(self) -> Optional[Tuple[str, int]]:
        """The live /healthz endpoint's ``(host, port)``, or None when
        no metrics port is configured — what the supervisor probes
        before daring a cold respawn (ISSUE 12)."""
        srv = self._metrics_server
        return None if srv is None else (srv.host, srv.port)

    def live_stats(self) -> dict:
        """Snapshot of the live plane for launchers and tests."""
        with self._lock:
            stats = {
                "metrics_addr": (None if self._metrics_server is None
                                 else list(self._metrics_server.address)),
                "endpoints": {t: dict(e) for t, e in
                              self._default._endpoints.items()},
                "polls": self._poll_count,
                "straggler": self._default._last_straggler,
            }
            if self.multi_job:
                stats["jobs"] = {jb.job_id: jb.doc()
                                 for jb in self._jobs.values()}
        return stats

    def _print_fleet_metrics(self, job=None) -> None:
        """End-of-run fleet table — the production replacement for
        eyeballing per-rank TrackerPrint lines. Appended to
        ``messages`` like a print command so launchers/tests see it.
        Multi-job: scoped to the completing job's own ranks."""
        if job is None or not self.multi_job:
            fleet = self.merged_metrics()
        else:
            with self._lock:
                snap = dict(job._metrics)
            fleet = merge_summaries(snap) if snap else None
        if fleet is None or not fleet.get("counters"):
            return
        table = format_fleet_table(fleet)
        self.messages.append(table)
        print(table, flush=True)

    def env(self, task_id: str, num_attempt: int = 0) -> Dict[str, str]:
        """Environment for a worker process."""
        return {
            "RABIT_TRACKER_URI": self.host,
            "RABIT_TRACKER_PORT": str(self.port),
            "RABIT_TASK_ID": task_id,
            "RABIT_NUM_TRIAL": str(num_attempt),
            "RABIT_WORLD_SIZE": str(self.nworkers),
        }

    # -- serving ----------------------------------------------------------
    def _serve(self) -> None:
        """The serve thread's body: run the readiness loop (ISSUE 19).
        Accept, read and write readiness for every worker connection
        live on this ONE thread; parsed commands drain through the
        fixed service pool."""
        try:
            self._loop.add_listener(self.sock, self._on_accept)
        except (OSError, ValueError):
            return  # stop() closed the socket before we started
        self._svc.start()
        self._loop.run()

    def _on_accept(self, conn) -> None:
        """Loop-thread accept callback: arm the incremental wire
        parser. No blocking work here — the loop owns this thread."""
        self._loop.start_parse(conn, _parse_command(), self._on_command)

    def _on_command(self, conn, parsed) -> None:
        """One full request parsed (loop thread): resolve the job
        address and enqueue onto its command queue. The fixed service
        pool serves queues round-robin across jobs, so one job's storm
        cannot starve a neighbor's commands."""
        if parsed is None:        # bad magic: hang up, exactly as before
            self._loop.close_conn(conn)
            return
        cmd, task_id, args = parsed
        job_id = _jobs_mod.DEFAULT_JOB
        if self.multi_job:
            job_id, task_id = _jobs_mod.split_task(task_id)
        self._svc.submit(job_id, lambda: self._handle(
            conn, cmd, job_id, task_id, args))

    def _reply_u32(self, conn, v: int, close: bool = True) -> None:
        """Queue a u32 reply on the loop (the non-blocking twin of
        ``_send_u32``); ``close`` hangs up once it drains."""
        self._loop.send(conn, struct.pack("<I", v), close_after=close)

    def _reply_str(self, conn, s: str, close: bool = True) -> None:
        b = s.encode()
        self._loop.send(conn, struct.pack("<I", len(b)) + b,
                        close_after=close)

    def _reply_json(self, conn, doc: dict) -> None:
        """JSON-str reply with the tracker's HLC piggybacked when the
        incident plane is on (ISSUE 20) — workers fold the stamp so
        their clocks causally follow the control plane. Never added to
        u32 replies, and with ``rabit_events`` unset the wire bytes
        are identical to a plain ``_reply_str``."""
        if self._events_on:
            stamp = _clock.tick()
            if stamp is not None:
                doc["hlc"] = stamp
        self._reply_str(conn, json.dumps(doc))

    def _handle(self, conn, cmd: str, job_id: str, task_id: str,
                args: tuple) -> None:
        """Job-scoped command execution on a service-pool thread. Any
        exception ``_dispatch`` raises (a malformed payload, a
        poisoned JobState) is caught HERE at the job boundary and
        quarantined — it must never unwind into the service pool or
        take a neighbor job down with it."""
        try:
            try:
                self._dispatch(conn, cmd, job_id, task_id, args)
            except (ConnectionError, OSError, struct.error):
                raise   # wire-level failures are the peer's problem
            except Exception as e:  # noqa: BLE001 - job fault boundary
                self._quarantine(job_id, cmd, e)
                self._loop.close_conn(conn)
        except (ConnectionError, OSError, struct.error):
            self._loop.close_conn(conn)

    def _dispatch(self, conn, cmd: str, job_id: str, task_id: str,
                  args: tuple) -> None:
        if cmd == "print":
            msg = args[0]
            self.messages.append(msg)
            print(msg, flush=True)
            self._reply_u32(conn, 1)
        elif cmd == "metrics":
            try:
                doc = json.loads(args[0])
            except ValueError:
                doc = None
            job = self._job_for(job_id)
            ok = isinstance(doc, dict) and job is not None
            if ok:
                with self._lock:
                    job._metrics[task_id] = doc
                self._fold_events(task_id, doc, job)
            self._reply_u32(conn, 1 if ok else 0)
        elif cmd == "endpoint":
            try:
                doc = json.loads(args[0])
            except ValueError:
                doc = None
            job = self._job_for(job_id)
            ok = (isinstance(doc, dict) and "host" in doc
                  and "port" in doc and job is not None)
            if ok:
                ep = {"host": str(doc["host"]),
                      "port": int(doc["port"]),
                      "rank": int(doc.get("rank", -1))}
                with self._lock:
                    # journal + act under ONE lock hold so a live
                    # snapshot (ISSUE 19) can never capture the state
                    # from between them
                    self._wal("endpoint", task=task_id, doc=ep,
                              _job=job)
                    job._endpoints[task_id] = ep
                    # a re-announce is proof of life: a stale miss
                    # count from before a tracker outage must not
                    # carry over into fresh eviction evidence
                    job._endpoint_misses[task_id] = 0
            self._reply_u32(conn, 1 if ok else 0)
        elif cmd == "topo":
            job = self._job_for(job_id)
            with self._lock:
                doc = {} if job is None else dict(job._topo)
            self._reply_json(conn, doc)
        elif cmd == "skew":
            job = self._job_for(job_id)
            with self._lock:
                doc = {} if job is None else dict(job._skew)
            self._reply_json(conn, doc)
        elif cmd == "world":
            self._reply_json(conn,
                             self.membership_doc(self._job_for(job_id)))
        elif cmd == "resume":
            # post-restart handshake (ISSUE 10): a live worker
            # re-presents its (task_id, stable_rank, epoch) so the
            # resumed tracker can reconcile the replayed WAL
            # against the world that kept running through the
            # outage. Ack 1 = identities agree (or were adopted),
            # 0 = mismatch — the worker should fall back to a full
            # re-registration.
            try:
                doc = json.loads(args[0])
            except ValueError:
                doc = None
            job = self._job_for(job_id)
            ok = False
            if isinstance(doc, dict) and doc.get("rank") is not None \
                    and job is not None:
                ok = self._resume_present(
                    job, task_id, int(doc["rank"]),
                    int(doc.get("epoch", 0)))
            self._reply_u32(conn, 1 if ok else 0)
        elif cmd == "evict":
            try:
                doc = json.loads(args[0])
            except ValueError:
                doc = None
            job = self._job_for(job_id)
            ok = False
            if isinstance(doc, dict) and doc.get("rank") is not None \
                    and job is not None:
                ok = self.evict_rank(int(doc["rank"]),
                                     str(doc.get("reason", "")),
                                     job=job)
            self._reply_u32(conn, 1 if ok else 0)
        elif cmd == "repl":
            # replication subscribers live for the tracker's lifetime,
            # not a request's: detach the socket from the loop (loop
            # thread) and hand it to a dedicated streamer thread —
            # bounded by the number of standbys, never by connections
            self._loop.call(lambda: self._detach_repl(conn, task_id))
        elif cmd == "submit":
            # admission control: answer IMMEDIATELY with a verdict
            # (admitted / queued+retry_after / shed+retry_after) —
            # overload sheds, it never stalls a submitter's socket
            self._reply_json(conn, self._submit(args[0]))
        elif cmd == "join":
            host, port, flags, token = args
            job = self._job_for_register(job_id)
            if job is None:
                # admission refused: shed, never parked
                self._loop.close_conn(conn)
                return
            self._register(conn, job, task_id, host, port, flags,
                           token, join=True)
        elif cmd == "shutdown":
            job = self._job_for(job_id)
            all_down = False
            if job is not None:
                with self._lock:
                    rank = job._ranks.get(task_id)
                    if rank is not None:
                        # journaled so a tracker resumed mid-teardown
                        # still sees the job complete (a worker only
                        # ever sends shutdown once)
                        self._wal("down", rank=rank, _job=job)
                        job._shutdown_ranks.add(rank)
                    all_down = job.all_down_locked()
            self._reply_u32(conn, 1)
            if all_down:
                self._job_complete(job)
        elif cmd in ("start", "recover"):
            host, port, flags, token = args
            job = self._job_for_register(job_id)
            if job is None:
                # admission refused: shed, never parked
                self._loop.close_conn(conn)
                return
            self._register(conn, job, task_id, host, port, flags, token)
        else:
            self._loop.close_conn(conn)

    def _detach_repl(self, conn, peer: str) -> None:
        """Loop-thread half of the ``repl`` arm: pull the socket out of
        readiness-land (back to blocking) and start its streamer."""
        if conn.closed or conn.detached:
            return
        raw, leftover = self._loop.detach(conn)
        if leftover:
            # protocol violation: a follower must wait for the
            # tracker's ok before sending its resync seq
            try:
                raw.close()
            except OSError:
                pass
            return
        threading.Thread(target=self._serve_repl, args=(raw, peer),
                         name="rabit-tracker-repl", daemon=True).start()

    # -- multi-job admission + fault domains (ISSUE 15) -------------------
    def _quarantine(self, job_id: str, cmd: str, exc: Exception) -> None:
        """One job's command handler raised: count it against THAT
        job's fault domain and keep serving. The accept loop and every
        neighbor job never see the exception."""
        from .. import telemetry
        from ..telemetry import flight
        with self._lock:
            job = self._jobs.get(job_id)
            if job is not None:
                job.quarantined += 1
        telemetry.count("tracker.quarantine", provenance="tracker")
        flight.note("job_quarantine",
                    f"job {job_id}: {cmd} raised "
                    f"{type(exc).__name__}: {exc}")
        self._fleet_emit("tracker.quarantine",
                         f"{cmd} raised {type(exc).__name__}: {exc}",
                         job=job_id)
        print(f"[tracker] quarantined {cmd} for job {job_id}: "
              f"{type(exc).__name__}: {exc}", file=sys.stderr, flush=True)

    def _job_for(self, job_id: str):
        """The named job, or None when unknown (commands for a job
        that was never admitted answer not-ok rather than raising)."""
        with self._lock:
            job = self._jobs.get(job_id)
            if job is not None and job.open:
                self._orphan_jobs.discard(job_id)   # wire contact
                self._job_contact[job_id] = time.monotonic()
            return job

    def _job_for_register(self, job_id: str):
        """Resolve a registration's job: an existing open job, the
        always-present default job, or — multi-job — an implicit
        admission attempt sized at the launch-time target. None means
        admission refused (the connection is shed; the worker's
        launcher should ``submit`` and retry after the hinted
        backoff)."""
        with self._lock:
            job = self._jobs.get(job_id)
            if job is not None and job.open:
                self._orphan_jobs.discard(job_id)   # wire contact
                self._job_contact[job_id] = time.monotonic()
                return job
            if job_id == _jobs_mod.DEFAULT_JOB:
                return self._default   # closed default re-forms in place
            if job is not None:
                return None   # closed job: its task ids are retired
            if self._fits_locked(self.nworkers):
                return self._open_job_locked(job_id, self.nworkers,
                                             self.elastic)
        return None

    def _submit(self, payload: str) -> dict:
        """The ``submit`` wire command's verdict. Shapes:
        ``{"ok": 1}`` admitted (idempotent for an already-open job),
        ``{"ok": 0, "queued": 1, "position": p, "retry_after_ms": n}``
        parked in the FIFO queue, ``{"ok": 0, "shed": 1,
        "retry_after_ms": n}`` queue full, ``{"ok": 0, "error": ...}``
        never admissible."""
        try:
            doc = json.loads(payload)
        except ValueError:
            doc = None
        if not isinstance(doc, dict) or not doc.get("job"):
            return {"ok": 0, "error": "malformed submit payload"}
        if not self.multi_job:
            return {"ok": 0,
                    "error": "multi-job disabled (rabit_multi_job unset)"}
        job_id = str(doc["job"])
        try:
            n = int(doc.get("nworkers", self.nworkers))
        except (TypeError, ValueError):
            return {"ok": 0, "error": "nworkers must be an integer"}
        if n < 1:
            return {"ok": 0, "error": "nworkers must be >= 1"}
        elastic = bool(doc.get("elastic", self.elastic))
        try:
            cls = max(0, int(doc.get("sched_class", 0)))
        except (TypeError, ValueError):
            cls = 0
        try:
            weight = float(doc.get("weight", 1.0))
        except (TypeError, ValueError):
            weight = 1.0
        if weight <= 0:
            weight = 1.0
        self._reap_orphans()   # free capacity held by pre-crash jobs
        with self._lock:
            job = self._jobs.get(job_id)
            if job is not None and job.open:
                self.submit_admitted_total += 1
                self._fleet_emit("admission.admitted",
                                 f"{job_id} already open", job=job_id)
                return {"ok": 1, "job": job_id, "already": 1}
            if self._max_fleet_ranks and n > self._max_fleet_ranks:
                return {"ok": 0,
                        "error": f"nworkers {n} exceeds "
                                 f"rabit_max_fleet_ranks "
                                 f"{self._max_fleet_ranks}"}
            if self._fits_locked(n):
                self._open_job_locked(job_id, n, elastic, cls, weight)
                self.submit_admitted_total += 1
                self._fleet_emit("admission.admitted",
                                 f"{job_id} opened at {n} ranks",
                                 job=job_id)
                return {"ok": 1, "job": job_id}
            plan = self._plan_preemption_locked(n, cls) if cls else None
        if plan:
            verdict = self._preempt(job_id, n, elastic, cls, weight,
                                    plan)
            if verdict is not None:
                return verdict
        retry = self._retry_hint_ms()
        with self._lock:
            if self._fits_locked(n):   # capacity freed while unlocked
                self._open_job_locked(job_id, n, elastic, cls, weight)
                self.submit_admitted_total += 1
                self._fleet_emit("admission.admitted",
                                 f"{job_id} opened at {n} ranks",
                                 job=job_id)
                return {"ok": 1, "job": job_id}
            pos = self._admission.offer(
                {"job": job_id, "nworkers": n, "elastic": elastic,
                 "sched_class": cls, "weight": weight})
            if pos < 0:
                depth = len(self._admission)
                self._fleet_emit("admission.shed",
                                 f"{job_id} shed past queue depth "
                                 f"{depth}", job=job_id)
                return {"ok": 0, "shed": 1,
                        "retry_after_ms": retry * (depth + 1)}
            self._fleet_emit("admission.queued",
                             f"{job_id} parked at position {pos}",
                             job=job_id)
            return {"ok": 0, "queued": 1, "position": pos,
                    "retry_after_ms": retry * (pos + 1)}

    def _retry_hint_ms(self) -> int:
        """Backoff hint from the MEASURED drain rate: the mean gap
        between recent job closes says how long a queue slot takes to
        free. Falls back to the old constant until two closes have
        been observed (and under multi-job OFF, where no job ever
        closes while the tracker serves)."""
        with self._lock:
            ts = list(self._drain_t)
        if len(ts) >= 2:
            per_close_s = (ts[-1] - ts[0]) / (len(ts) - 1)
            if per_close_s > 0:
                return max(50, min(60_000, int(per_close_s * 1e3)))
        return _jobs_mod.RETRY_AFTER_MS_DEFAULT

    def _fits_locked(self, nworkers: int) -> bool:
        """Would a job of ``nworkers`` fit under the admission caps
        right now? Caller holds the lock. The pre-created default job
        does not count until it has registered anyone. Capacity sums
        ``quota`` (== nworkers until a preemption shrinks it), so
        preempted ranks are genuinely transferable."""
        open_jobs = [jb for jb in self._jobs.values()
                     if jb.open and (jb.job_id != _jobs_mod.DEFAULT_JOB
                                     or jb._ranks or jb._pending)]
        if len(open_jobs) >= self._max_jobs:
            return False
        if self._max_fleet_ranks:
            in_use = sum(jb.quota for jb in open_jobs)
            if in_use + nworkers > self._max_fleet_ranks:
                return False
        return True

    def _plan_preemption_locked(self, n: int, cls: int):
        """Victim ranks whose eviction would fit an ``n``-rank
        class-``cls`` job under ``rabit_max_fleet_ranks``. Caller
        holds the lock; execution happens OUTSIDE it (evict_rank
        re-takes the lock). Victims are elastic open jobs of strictly
        lower class, lowest class first, highest live rank first; each
        keeps at least one rank so its survivors re-form. None = the
        shortfall cannot be covered (or the blocker is the job-count
        cap, which preemption cannot fix)."""
        if not self._max_fleet_ranks:
            return None
        open_jobs = [jb for jb in self._jobs.values()
                     if jb.open and (jb.job_id != _jobs_mod.DEFAULT_JOB
                                     or jb._ranks or jb._pending)]
        if len(open_jobs) >= self._max_jobs:
            return None
        need = n - (self._max_fleet_ranks
                    - sum(jb.quota for jb in open_jobs))
        if need <= 0:
            return None
        victims = sorted(
            (jb for jb in open_jobs
             if jb.elastic and jb.sched_class < cls
             and jb._member is not None),
            key=lambda jb: (jb.sched_class, jb.job_id))
        plan = []
        for jb in victims:
            if need <= 0:
                break
            live = sorted(jb._member.live, reverse=True)
            take = min(need, jb.quota - 1, max(0, len(live) - 1))
            for r in live[:take]:
                plan.append((jb, r))
            need -= max(0, take)
        return plan if plan and need <= 0 else None

    def _preempt(self, job_id: str, n: int, elastic: bool, cls: int,
                 weight: float, plan) -> Optional[dict]:
        """Execute a preemption plan: evict the victim ranks (the
        existing elastic evict path — survivors re-form at the smaller
        world), transfer the freed quota, and admit the submitter.
        Returns the admitted verdict, or None if the plan raced stale
        (capacity moved between planning and execution — the caller
        falls back to the queue)."""
        evicted: Dict[object, int] = {}
        for jb, r in plan:
            if self.evict_rank(r, f"preempted by class {cls} job "
                               f"{job_id}", job=jb):
                evicted[jb] = evicted.get(jb, 0) + 1
        if not evicted:
            return None
        with self._lock:
            for jb, cnt in evicted.items():
                jb.quota = max(1, jb.quota - cnt)
                jb.preempted += cnt
                # journal + act under one hold (snapshot consistency)
                self._wal("quota", quota=jb.quota,
                          preempted=jb.preempted, _job=jb)
                by_class = self.sched_preemptions
                by_class[jb.sched_class] = \
                    by_class.get(jb.sched_class, 0) + cnt
            if not self._fits_locked(n):
                return None
            self._open_job_locked(job_id, n, elastic, cls, weight)
            self.submit_admitted_total += 1
        total = sum(evicted.values())
        print(f"[tracker] admitted class {cls} job {job_id} by "
              f"preempting {total} rank(s) from "
              f"{', '.join(sorted(jb.job_id for jb in evicted))}",
              file=sys.stderr, flush=True)
        return {"ok": 1, "job": job_id, "preempted": total}

    def _open_job_locked(self, job_id: str, nworkers: int, elastic: bool,
                         sched_class: int = 0, weight: float = 1.0):
        """Create + journal a job (caller holds the lock and has
        already verified it fits). Scheduler fields ride the
        ``job_open`` record only when non-default, so a scheduler-less
        WAL stays byte-identical."""
        job = _jobs_mod.JobState(job_id, nworkers, elastic=elastic,
                                 sched_class=sched_class,
                                 sched_weight=weight)
        extra = {}
        if sched_class:
            extra["sched_class"] = int(sched_class)
        if weight != 1.0:
            extra["weight"] = float(weight)
        self._wal("job_open", job=job_id, nworkers=int(nworkers),
                  elastic=bool(elastic), **extra)
        self._jobs[job_id] = job
        self._job_contact[job_id] = time.monotonic()
        return job

    def _close_job_locked(self, job, reason: str) -> None:
        if job.open:
            self._wal("job_close", job=job.job_id, reason=reason)
            job.close(reason)
            # one drain-rate sample per close: the admission plane's
            # retry_after_ms hint is measured, not guessed
            self._drain_t.append(time.monotonic())

    def _admit_queued_locked(self) -> List[str]:
        """Admit queued submissions in strict FIFO order while the
        head fits; a too-big head blocks the queue (FIFO fairness — a
        small job must not starve a big one forever). Caller holds the
        lock. Returns the admitted job ids."""
        admitted = []
        while True:
            head = self._admission.peek()
            if head is None or not self._fits_locked(head["nworkers"]):
                break
            self._admission.pop_front()
            self._open_job_locked(head["job"], head["nworkers"],
                                  head["elastic"],
                                  int(head.get("sched_class", 0)),
                                  float(head.get("weight", 1.0)))
            admitted.append(head["job"])
        return admitted

    def _reap_orphans(self) -> List[str]:
        """Close open jobs no live worker is behind, freeing their
        admission slots. Two kinds of dead weight:

        * **WAL orphans** — a crash-resume re-adopts every
          journaled-open job, but its workers may have died with the
          old leader. Any wire contact tagged with the job clears it
          from the orphan set; once the resume grace window has
          passed, whatever remains is closed (``"orphaned"``).
        * **Ghost jobs** — admitted from the FIFO queue after the
          submitter stopped waiting (or flooded in by a submit
          storm), so nobody ever registers. With
          ``rabit_job_forming_timeout_ms`` set, an open job with no
          registered rank, no pending registration, and no wire
          contact for that long is closed (``"forming timeout"``).

        Returns the reaped job ids; queued submissions are admitted
        into the freed capacity."""
        reaped: List[tuple] = []
        admitted: List[str] = []
        with self._lock:
            if self._orphan_jobs and not self.in_resume_grace():
                for jid in sorted(self._orphan_jobs):
                    jb = self._jobs.get(jid)
                    if jb is not None and jb.open:
                        self._close_job_locked(jb, "orphaned")
                        reaped.append((jid, "no contact since resume"))
                self._orphan_jobs.clear()
            t_ms = forming_timeout_ms()
            if t_ms:
                now = time.monotonic()
                for jid, jb in list(self._jobs.items()):
                    if (jid != _jobs_mod.DEFAULT_JOB and jb.open
                            and not jb._ranks and not jb._pending
                            and now - self._job_contact.get(jid, now)
                            > t_ms / 1e3):
                        self._close_job_locked(jb, "forming timeout")
                        reaped.append((jid, f"forming > {t_ms} ms"))
            if reaped:
                admitted = self._admit_queued_locked()
        for jid, why in reaped:
            print(f"[tracker] reaped orphaned job {jid} ({why})",
                  file=sys.stderr, flush=True)
        for jid in admitted:
            print(f"[tracker] admitted queued job {jid}",
                  file=sys.stderr, flush=True)
        return [jid for jid, _ in reaped]

    def _job_complete(self, job) -> None:
        """Every live rank of ``job`` sent shutdown: close its world,
        admit queued jobs into the freed capacity, and — only when
        nothing else is running or waiting — finish the tracker
        itself."""
        self._print_fleet_metrics(job)
        if not self.multi_job:
            self._done.set()
            return
        with self._lock:
            self._close_job_locked(job, "complete")
            admitted = self._admit_queued_locked()
            still_open = [jb for jb in self._jobs.values()
                          if jb.open and
                          (jb.job_id != _jobs_mod.DEFAULT_JOB
                           or jb._ranks or jb._pending)]
            queue_empty = self._admission.peek() is None
        for jid in admitted:
            print(f"[tracker] admitted queued job {jid}",
                  file=sys.stderr, flush=True)
        if job is self._default and not still_open and queue_empty:
            self._done.set()

    def _expected_ranks(self, job) -> set:
        """Ranks the job's current registration batch must contain
        before it forms (caller holds the lock): the fixed world, or —
        elastic — the live membership view's survivors plus parked
        joiners."""
        if job.elastic:
            return job._member.expected()
        return set(range(job.nworkers))

    def _try_complete_batch_locked(self, job):
        """(batch, epoch) when every expected rank is pending, else
        None. Caller holds the lock and, on success, must run
        ``_assign`` OUTSIDE it. Factored out of ``_register`` because
        an EVICTION can also complete a batch: survivors re-register
        and block waiting for a dead rank until the poll loop (or an
        ``evict`` command) removes it from the expected set."""
        expected = self._expected_ranks(job)
        if not expected or not expected <= set(job._pending):
            return None
        batch = {r: job._pending.pop(r) for r in expected}
        self._wal("epoch", epoch=job._epoch + 1,
                  members=sorted(batch), _job=job)
        job._epoch += 1
        job.mark_live()
        if job.elastic:
            admitted = job._member.formed(batch)
            for r in sorted(admitted):
                self._note_transition("admit", r, "joined at epoch "
                                      f"{job._epoch}", job)
        self._cv.notify_all()
        return batch, job._epoch

    def _resume_present(self, job, task_id: str, rank: int,
                        epoch: int) -> bool:
        """Reconcile one worker's post-restart ``resume`` handshake
        against the replayed WAL: a matching identity confirms the
        journal, an unknown task_id is adopted (a torn WAL tail can
        lose the final pre-crash assignment — the live worker IS the
        authority on its own rank), and a contradiction is refused so
        the worker falls back to full re-registration."""
        with self._lock:
            known = job._ranks.get(task_id)
            if known is None and 0 <= rank < job.nworkers \
                    and rank not in job._ranks.values():
                self._wal("assign", task=task_id, rank=rank, _job=job)
                job._ranks[task_id] = rank
                known = rank
            ok = known == rank and epoch <= job._epoch + 1
            if ok:
                job._endpoint_misses[task_id] = 0
                job._resumed_ranks.add(rank)
        return ok

    def _register(self, conn, job, task_id: str, host: str, port: int,
                  flags: int = 0, token: str = "",
                  join: bool = False) -> None:
        """Registration is non-blocking now (ISSUE 19): a worker whose
        batch is incomplete simply leaves its connection parked in
        ``job._pending`` — no thread waits on it. Whichever command
        completes the batch serves everyone via ``_assign``."""
        grace_s: Optional[float] = None
        prev = None
        with self._cv:
            if task_id not in job._ranks:
                rank = len(job._ranks)
                if job.elastic and rank >= job.nworkers \
                        and job._member.evicted:
                    # replacement hardware arrives under a NEW task_id:
                    # adopt the lowest vacated stable rank so the world
                    # can grow back to target (and the newcomer inherits
                    # that rank's durable checkpoint shard directory)
                    rank = min(job._member.evicted)
                self._wal("assign", task=task_id, rank=rank, _job=job)
                job._ranks[task_id] = rank
            rank = job._ranks[task_id]
            if rank >= job.nworkers:
                self._loop.close_conn(conn)
                return
            if job.elastic:
                m = job._member
                if join or rank in m.evicted or \
                        (m.live and rank not in m.live):
                    # (re-)admission: parked until the epoch boundary —
                    # a joiner must never perturb an in-flight world
                    self._wal("park", rank=rank, _job=job)
                    m.park(rank)
                    grace_s = _membership.join_grace_ms() / 1e3 or None
            job._shutdown_ranks.discard(rank)
            prev = job._pending.get(rank)
            job._pending[rank] = (conn, host, port, flags, token)
            got = self._try_complete_batch_locked(job)
        if prev is not None and prev[0] is not conn:
            # a re-registration superseded a still-parked connection
            self._loop.close_conn(prev[0])
        if got is None:
            if grace_s is not None:
                # parked joiner: bounce it (the joiner retries) after
                # rabit_join_grace_ms if no epoch boundary adopts it,
                # rather than hold its socket open forever
                self._arm_join_bounce(job, conn, rank, grace_s)
            return
        self._assign(job, *got)

    def _arm_join_bounce(self, job, conn, rank: int,
                         grace_s: float) -> None:
        def bounce() -> None:  # loop thread
            with self._cv:
                pend = job._pending.get(rank)
                if pend is None or pend[0] is not conn:
                    return  # adopted (or superseded) in time
                del job._pending[rank]
            self._loop.close_conn(conn)
        self._loop.call_later(grace_s, bounce)

    # -- elastic membership (ISSUE 9) -------------------------------------
    def membership_doc(self, job=None) -> dict:
        """The ``world`` wire command's payload: the live membership
        view, or a static fixed-world doc when elastic is off (so the
        command always answers — a worker probing an inelastic tracker
        learns membership is fixed rather than timing out)."""
        if job is None:
            job = self._default
        with self._lock:
            if job.elastic:
                return job._member.doc(job._epoch)
            return {"epoch": job._epoch, "world": job.nworkers,
                    "target": job.nworkers,
                    "live": list(range(job.nworkers)), "evicted": [],
                    "joining": [], "generation": 0, "elastic": False}

    def _note_transition(self, kind: str, rank: int, detail: str,
                         job=None) -> None:
        """Make a membership transition observable: a counter + a
        zero-duration ``membership.transition`` span (trace_report
        renders these on the timeline) + a flight-recorder note naming
        the rank, so a post-mortem bundle shows WHY the world
        resized."""
        from .. import telemetry
        from ..telemetry import flight
        jtag = ""
        if job is not None and self.multi_job \
                and job.job_id != _jobs_mod.DEFAULT_JOB:
            jtag = f" job {job.job_id}"
        telemetry.count(f"membership.{kind}", provenance="membership")
        telemetry.record_span("membership.transition", 0.0,
                              op=kind, provenance="membership",
                              rank=rank, detail=detail)
        flight.note(f"member_{kind}", f"rank {rank}:{jtag} {detail}")
        self._fleet_emit(f"membership.{kind}", detail,
                         job="" if job is None else job.job_id,
                         rank=rank)
        print(f"[tracker] membership:{jtag} {kind} rank {rank} "
              f"({detail})", file=sys.stderr, flush=True)

    def evict_rank(self, rank: int, reason: str = "", job=None) -> bool:
        """Evict ``rank`` from the live job (the ``evict`` wire
        command, or the poll loop's silent-endpoint evidence). The
        rank leaves the expected set immediately, so survivors already
        blocked in re-registration form their N-1 batch NOW instead of
        waiting out the ready timeout on a dead peer. No-op unless the
        job is elastic. Scoped: evicting from one job never touches a
        neighbor's membership."""
        if job is None:
            job = self._default
        if not job.elastic or not 0 <= int(rank) < job.nworkers:
            return False
        rank = int(rank)
        with self._cv:
            if rank in job._member.evicted:
                return False
            self._wal("evict", rank=rank, reason=reason, _job=job)
            if not job._member.evict(rank):
                return False
            pend = job._pending.pop(rank, None)
            got = self._try_complete_batch_locked(job)
            if job.open and not job._member.live:
                # every member is gone: the job FAILED inside its own
                # fault domain (it re-forms if replacements arrive) —
                # observable in /jobs, invisible to its neighbors
                job.mark_failed()
        self._note_transition("evict", rank, reason or "evicted", job)
        if pend is not None:
            self._loop.close_conn(pend[0])
        if got is not None:
            self._assign(job, *got)
        return True

    def _assign(self, job,
                batch: Dict[int, Tuple[socket.socket, str, int, int,
                                       str]],
                epoch: int) -> None:
        # Elastic worlds may be holey in STABLE rank space (rank 1 of
        # {0, 2, 3} is gone): schedules are built over dense collective
        # SLOTS, and the wire `rank` field carries the slot. With a
        # fixed world the batch is always the full contiguous range, so
        # the mapping is the identity and nothing changes byte-wise.
        world = len(batch) if job.elastic else job.nworkers
        slot_of = _membership.dense_slots(batch)
        addr = {slot_of[r]: (h, p, tok)
                for r, (c, h, p, f, tok) in batch.items()}
        conns = {slot_of[r]: c for r, (c, h, p, f, tok) in batch.items()}
        # host a coordinator when configured OR when any worker advertised
        # data-plane need in its registration flags (the Python engine API
        # path is invisible to the launcher's argv/env autodetect)
        want_coord = self._coordinator or any(
            f & FLAG_DATAPLANE for (c, h, p, f, tok) in batch.values())
        try:
            coord_host, coord_port = (
                self._new_coordinator(job, epoch, world)
                if want_coord else ("", 0))
        except Exception as e:  # noqa: BLE001 - reject batch loudly
            # a silent failure here would hang every worker in this
            # batch; closing their connections surfaces a clean
            # registration error on each instead
            print(f"[tracker] coordinator start failed, rejecting epoch "
                  f"{epoch}: {e}", file=sys.stderr, flush=True)
            for c in conns.values():
                self._loop.close_conn(c)
            return
        # Single-host worlds get a flag so every rank makes the SAME
        # collective-algorithm choice (the ring/tree crossover default
        # prefers tree on a shared medium; a per-rank local-links guess
        # could diverge in mixed-host worlds and deadlock a collective).
        # Judged by the OBSERVED registration source address, not the
        # self-reported hostname: cloned VMs/containers can share a
        # hostname across machines. The flag only steers that algorithm
        # default — the UDS fast path does NOT trust it (source IPs
        # collapse behind SNAT); it rides the per-peer random uds_token,
        # which resolves only on the owning host.
        def _src_ip(c):
            try:
                return c.getpeername()[0]
            except OSError:
                return None  # died pre-assignment; be conservative
        single_host = len({_src_ip(c) for (c, h, p, f, tok) in
                           batch.values()}) <= 1
        # Host grouping for hierarchical collectives (the ``topo``
        # command): ranks sharing a fingerprint share a host. Same
        # src-ip-first rule as single_host (hostnames lie across cloned
        # VMs); the reported hostname only breaks ties when the source
        # address is unknown. Like single_host this steers SCHEDULE
        # choice only — data never rides an inferred-same-host path
        # (UDS still proves locality per-pair via uds_token).
        by_host: Dict[str, List[int]] = {}
        for rank in sorted(batch):
            c, h, p, f, tok = batch[rank]
            by_host.setdefault(_src_ip(c) or h, []).append(slot_of[rank])
        groups = list(by_host.values())
        topo = {
            "epoch": epoch,
            "groups": groups,
            "delegates": [min(g) for g in groups],
            "single_host": single_host,
        }
        with self._lock:
            # journal + act under ONE lock hold so a live snapshot
            # (ISSUE 19) can never capture the state from between them
            self._wal("topo", doc=topo, _job=job)
            job._topo = topo

        def _pack_u32(buf: bytearray, v: int) -> None:
            buf += struct.pack("<I", v)

        def _pack_str(buf: bytearray, s: str) -> None:
            b = s.encode()
            buf += struct.pack("<I", len(b))
            buf += b

        # ready-ack barrier: each worker's 4-byte ack arrives via the
        # loop (no blocking reads); the counters below are mutated ONLY
        # by loop-thread callbacks, so they need no lock. A worker dying
        # pre-ack is logged, not swallowed: the epoch still completes
        # (the dead worker re-registers into the NEXT epoch after
        # respawn) but the operator can see why a recovery round
        # happened. teardown-before-ack contract: once EVERY member
        # acked epoch N, no client of an epoch < N exists anywhere ->
        # reap old services (on the service pool: reaping takes the
        # tracker lock and can block on service joins).
        state = {"left": len(conns), "all_acked": True}

        def _settle() -> None:  # loop thread
            state["left"] -= 1
            if state["left"] == 0 and state["all_acked"]:
                self._svc.submit(
                    job.job_id,
                    lambda: self._reap_old_services(job, epoch))

        def _on_ack(c, _data) -> None:  # loop thread
            self._loop.close_conn(c)
            _settle()

        def _make_on_fail(rank):
            def _on_fail(c, exc) -> None:  # loop thread
                state["all_acked"] = False
                print(f"[tracker] rank {rank} did not ack epoch "
                      f"{epoch} ({type(exc).__name__}: {exc})",
                      file=sys.stderr, flush=True)
                self._loop.close_conn(c)
                _settle()
            return _on_fail

        for rank in sorted(slot_of.values()):
            conn = conns[rank]
            parent, children = tree_neighbors(rank, world)
            tree_nbrs = ([] if parent is None else [parent]) + children
            ring_prev = (rank - 1) % world
            ring_next = (rank + 1) % world
            neighbors = sorted(set(tree_nbrs) |
                               ({ring_prev, ring_next} if world > 1
                                else set()))
            connect_to = [r for r in neighbors if r < rank]
            naccept = len([r for r in neighbors if r > rank])
            blob = bytearray()
            _pack_u32(blob, rank)
            _pack_u32(blob, world)
            _pack_u32(blob, epoch)
            _pack_str(blob, coord_host)
            _pack_u32(blob, coord_port)
            _pack_u32(blob, 1 if single_host else 0)
            _pack_u32(blob, NO_RANK if parent is None else parent)
            _pack_u32(blob, len(tree_nbrs))
            for r in tree_nbrs:
                _pack_u32(blob, r)
            _pack_u32(blob, ring_prev)
            _pack_u32(blob, ring_next)
            _pack_u32(blob, len(connect_to))
            for r in connect_to:
                peer_host, peer_port, peer_tok = addr[r]
                if self._link_rewrite is not None:
                    peer_host, peer_port = self._link_rewrite(
                        r, peer_host, peer_port)
                    peer_tok = ""  # UDS would bypass the proxy
                _pack_u32(blob, r)
                _pack_str(blob, peer_host)
                _pack_u32(blob, int(peer_port))
                _pack_str(blob, peer_tok)
            _pack_u32(blob, naccept)
            self._loop.send(conn, bytes(blob))
            self._loop.expect(conn, 4, _on_ack,
                              timeout=self._ready_timeout,
                              on_fail=_make_on_fail(rank))


def _main(argv: Optional[List[str]] = None) -> int:
    """Standalone tracker CLI. ``--wal-dir`` journals every
    control-plane transition; ``--resume <wal_dir>`` replays it and
    re-adopts a live world after a crash — pin ``--host``/``--port``
    to the dead incarnation's address so the env the workers were
    launched with stays valid (ISSUE 10). ``--multi-job`` turns the
    tracker into a long-lived multiplexing service: workers address a
    job by prefixing their task id (``<job>/<task>``) and launchers
    park via the ``submit`` command (ISSUE 15)."""
    import argparse
    ap = argparse.ArgumentParser(description=_main.__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--wal-dir", default=None,
                    help="journal control-plane transitions here "
                         "(also RABIT_TRACKER_WAL_DIR)")
    ap.add_argument("--resume", metavar="WAL_DIR", default=None,
                    help="replay WAL_DIR and re-adopt the live world")
    ap.add_argument("--multi-job", action="store_true",
                    help="serve many fault-isolated jobs on this one "
                         "tracker (also RABIT_MULTI_JOB=1)")
    args = ap.parse_args(argv)
    tr = Tracker(args.num_workers, host=args.host, port=args.port,
                 wal_dir=args.resume or args.wal_dir,
                 resume=args.resume is not None,
                 multi_job=True if args.multi_job else None).start()
    print(f"[tracker] listening on {tr.host}:{tr.port}",
          file=sys.stderr, flush=True)
    try:
        tr.join()
    finally:
        tr.stop()
    return 0


if __name__ == "__main__":
    sys.exit(_main())
