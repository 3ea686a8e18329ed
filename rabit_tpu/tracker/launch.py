"""Local cluster launcher — the ``dmlc-submit --cluster local
--num-workers N --local-num-attempt M`` equivalent (reference
test/test.mk:13-37): starts a tracker, spawns N worker processes, and
respawns any worker that dies (up to ``max_attempts`` times per worker,
with the attempt counter exported so mock kill schedules advance).

Usage:
    python -m rabit_tpu.tracker.launch -n 4 [--max-attempts 20] \
        prog arg1 key=value ...

``--submit HOST:PORT`` targets a RUNNING multi-job tracker instead of
starting one: the launcher submits ``--job`` through the admission
plane (backing off on queued/shed verdicts per the tracker's
``retry_after_ms`` hints — overload sheds, it never stalls), then
spawns its workers with job-scoped task ids (``<job>/<i>``) against
the shared control plane. Many such launchers share one tracker, each
inside its own fault domain (doc/fault_tolerance.md).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from . import standby as _standby_mod
from .tracker import Tracker, default_lease_ms


class _ChaosFarm:
    """Per-run proxy fleet for ``launch(chaos=...)``: one proxy fronts
    the tracker, plus one per distinct worker link listener, created
    lazily from the tracker's ``link_rewrite`` hook (listen ports are
    only known at registration, and change across respawns). Every
    proxy runs the schedule filtered to its target class (``tracker``
    vs ``link``, unscoped rules run on both) and reseeded per proxy,
    so faults stay deterministic per-link without sharing
    ``max_times`` budgets."""

    def __init__(self, schedule):
        from ..chaos.schedule import Schedule
        self.schedule = Schedule.from_spec(schedule)
        self._lock = threading.Lock()
        self._by_target: Dict[Tuple[str, int], object] = {}
        self.tracker_proxy = None

    def front_tracker(self, tracker: Tracker, kill_hook=None):
        from ..chaos.proxy import ChaosProxy
        self.tracker_proxy = ChaosProxy(
            tracker.host, tracker.port,
            self.schedule.for_target("tracker").reseed(0),
            name="chaos-tracker", kill_hook=kill_hook).start()
        return self.tracker_proxy

    def link_rewrite(self, peer_rank: int, host: str,
                     port: int) -> Tuple[str, int]:
        from ..chaos.proxy import ChaosProxy
        with self._lock:
            proxy = self._by_target.get((host, port))
            if proxy is None:
                proxy = ChaosProxy(
                    host, port,
                    self.schedule.for_target("link").reseed(1 + peer_rank),
                    name=f"chaos-link-r{peer_rank}").start()
                self._by_target[(host, port)] = proxy
        return proxy.host, proxy.port

    def stop(self) -> Dict[str, int]:
        with self._lock:
            proxies = list(self._by_target.values())
            self._by_target.clear()
        if self.tracker_proxy is not None:
            proxies.append(self.tracker_proxy)
            self.tracker_proxy = None
        events = 0
        storm_submits = 0
        storm_shed = 0
        for p in proxies:
            # a short-lived world must not race a storm it survived:
            # let in-flight firings land their tallies (bounded)
            p.join_storms()
            events += len(p.events)
            for tally in getattr(p, "storm_results", []):
                storm_submits += tally.get("submits", 0)
                storm_shed += sum(1 for v in tally.get("verdicts", [])
                                  if isinstance(v, dict)
                                  and not v.get("ok"))
            p.stop()
        return {"proxies": len(proxies), "events": events,
                "storm_submits": storm_submits,
                "storm_shed": storm_shed}


class _TrackerSupervisor:
    """Supervise the in-process tracker the way the launcher already
    supervises workers (ISSUE 10): a crash — injected by the chaos
    ``tracker_kill`` rule or scripted by a test — is followed by a
    ``resume=True`` respawn on the SAME pinned host:port once the
    scheduled outage elapses, so the env every worker was launched
    with stays valid and the replayed WAL re-adopts the live world.
    Without a WAL dir a killed tracker stays dead (exactly today's
    failure mode — supervision never invents durability)."""

    def __init__(self, tracker: Tracker, wal_dir: Optional[str],
                 factory, quiet: bool = False):
        self.tracker = tracker
        self.wal_dir = wal_dir
        self._factory = factory  # (host, port) -> resumed Tracker
        self.quiet = quiet
        self.restarts = 0
        # hot standby (ISSUE 12): when a StandbyTracker shadows this
        # leader, failover replaces cold respawn — the supervisor's job
        # flips from "fork a successor" to "adopt the promoted standby
        # and never fork a second tracker into a healthy world"
        self.standby: Optional[_standby_mod.StandbyTracker] = None
        self.proxy = None            # chaos front proxy, for retarget
        self.failovers = 0
        self._lock = threading.Lock()
        self._respawn_at: Optional[float] = None

    def kill(self, delay_ms: float = 0.0) -> None:
        """Chaos kill hook: crash the live tracker NOW; schedule the
        ``--resume`` respawn ``delay_ms`` later (the outage the fleet
        must ride out) when a WAL makes resume possible."""
        with self._lock:
            if self.tracker.crashed:
                return
            self.tracker.crash()
            if not self.quiet:
                print(f"[launch] tracker killed (outage "
                      f"{delay_ms / 1e3:.1f}s"
                      + (", will resume from WAL)" if self.wal_dir
                         else ", no WAL: stays dead)"),
                      file=sys.stderr, flush=True)
            if self.wal_dir is not None:
                self._respawn_at = time.monotonic() + delay_ms / 1e3

    def _leader_alive(self) -> bool:
        """Probe for a live leader OTHER than the one we supervise
        before cold-respawning: a promoted standby legitimately owns
        the tracker role now. Prefer the ``/healthz`` identity probe
        (it works for an out-of-process standby too); fall back to
        in-process promotion state."""
        sb = self.standby
        if sb is None:
            return False
        tr = sb.tracker
        if tr is not None and tr.live_addr() is not None:
            from ..telemetry import live as _live
            doc = _live.scrape_json(*tr.live_addr(), path="/healthz")
            return bool(doc and doc.get("ok")
                        and doc.get("tracker_role") == "leader")
        return tr is not None and not tr.crashed

    def _adopt_locked(self) -> None:
        """A standby promoted itself: it IS the tracker now. Fence the
        deposed incarnation (it may still be listening after a mere
        partition), repoint the chaos front proxy so addresses baked
        into live workers — including the native engine's shutdown
        path — keep resolving, and cancel any scheduled respawn."""
        fresh = self.standby.tracker
        old, self.tracker = self.tracker, fresh
        self.failovers += 1
        self._respawn_at = None
        if not old.crashed:
            old.crash()
        if self.proxy is not None:
            self.proxy.retarget(fresh.host, fresh.port)
        if not self.quiet:
            print(f"[launch] standby promoted: tracker now "
                  f"{fresh.host}:{fresh.port} (failover "
                  f"{self.failovers}, seq {self.standby.acked_seq})",
                  file=sys.stderr, flush=True)

    def poll(self) -> None:
        """Called from the launcher's supervision loop, like the
        per-worker ``Popen.poll``s."""
        with self._lock:
            if (self.standby is not None and self.standby.promoted()
                    and self.tracker is not self.standby.tracker):
                self._adopt_locked()
                return
            if self._respawn_at is None or \
                    time.monotonic() < self._respawn_at:
                return
            if self._leader_alive():
                # a promoted leader already serves this world — never
                # fork a second tracker into it (adopted next poll)
                self._respawn_at = None
                return
            if self.standby is not None and self.standby.alive():
                # promotion is bounded by the lease: hold the cold
                # respawn while the standby is still working toward it
                self._respawn_at = time.monotonic() + 0.05
                return
            # double failure (standby dead too, or none): the PR 10
            # cold resume on the pinned port is the fallback
            self._respawn_at = None
            host, port = self.tracker.host, self.tracker.port
        # the dead incarnation's listen socket can linger a beat past
        # crash(); the pinned port must win before workers notice
        deadline = time.monotonic() + 10
        while True:
            try:
                fresh = self._factory(host, port)
                break
            except OSError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.05)
        fresh.start()
        with self._lock:
            self.tracker = fresh
            self.restarts += 1
        if not self.quiet:
            print(f"[launch] tracker resumed on {host}:{port} "
                  f"(restart {self.restarts})", file=sys.stderr,
                  flush=True)


def _xla_dataplane_requested(cmd: List[str]) -> bool:
    return (os.environ.get("RABIT_DATAPLANE") == "xla"
            or any(a == "rabit_dataplane=xla" for a in cmd))


def _refuse_shared_chip(nworkers: int, xla_plane: bool) -> None:
    """Refuse to start xla-data-plane workers that would share a chip.
    The launcher pins no chip per worker, so unless the workers are
    held off the TPU (``JAX_PLATFORMS=cpu``) every one of them would
    claim every chip of the host. A multi-process device world on
    several chips has never run (ROADMAP)."""
    platforms = [p for p in os.environ.get("JAX_PLATFORMS", "").split(",")
                 if p]
    if xla_plane and nworkers > 1 and (not platforms
                                       or "tpu" in platforms):
        raise RuntimeError(
            f"refusing to launch {nworkers} rabit_dataplane=xla workers "
            "that would share the host's chips: this launcher pins no "
            "chip per worker. Set JAX_PLATFORMS=cpu to run the workers "
            "on the CPU backend.")


def launch(nworkers: int, cmd: List[str], max_attempts: int = 20,
           timeout: float = 300.0, quiet: bool = False,
           coordinator: Optional[bool] = None,
           stats: Optional[Dict] = None, chaos=None,
           elastic: Optional[bool] = None) -> int:
    """Run ``cmd`` as ``nworkers`` local processes under a tracker.
    Returns 0 on success. Workers exiting nonzero are respawned with an
    incremented attempt counter until ``max_attempts``. ``coordinator``
    makes the tracker host a per-epoch device-world coordination service
    (required by the XLA data plane); default: auto-detect from the
    worker command / environment. Workers additionally advertise
    data-plane need in their tracker-registration flags, so the
    coordinator is hosted on demand even when the data plane was
    selected through the Python engine API (invisible here).

    ``chaos`` (a :class:`rabit_tpu.chaos.Schedule` spec: dict, JSON
    string, ``@file.json``, or the ``rabit_chaos``/``RABIT_CHAOS`` env
    default) interposes fault-injection proxies on every socket path:
    workers rendezvous with the tracker through one proxy, and the
    tracker rewrites advertised peer addresses through per-link proxies
    — so scheduled delays/resets/partitions/blackouts hit live
    registration and collective traffic (doc/fault_tolerance.md)."""
    from . import membership as _membership
    if coordinator is None:
        coordinator = _xla_dataplane_requested(cmd)
    _refuse_shared_chip(nworkers, coordinator)
    if chaos is None:
        chaos = os.environ.get("RABIT_CHAOS") or None
    if elastic is None:
        elastic = (_membership.elastic_enabled()
                   or any(a == "rabit_elastic=1" for a in cmd))
    farm = _ChaosFarm(chaos) if chaos is not None else None
    wal_dir = os.environ.get("RABIT_TRACKER_WAL_DIR") or None
    # hot standby (ISSUE 12): engaged only when BOTH knobs are set —
    # an advertised standby address (``rabit_tracker_standby``) and a
    # WAL dir (replication streams the journal; no journal, nothing to
    # stream). With either unset, lease_ms stays None and the tracker
    # is byte-identical to the PR 10 configuration.
    standby_spec = os.environ.get(_standby_mod.STANDBY_ENV) or None
    lease_ms = default_lease_ms() if (standby_spec and wal_dir) else None
    tracker = Tracker(
        nworkers, coordinator=coordinator,
        link_rewrite=farm.link_rewrite if farm else None,
        elastic=elastic, wal_dir=wal_dir, lease_ms=lease_ms).start()

    def _resumed_tracker(host: str, port: int) -> Tracker:
        return Tracker(
            nworkers, host=host, port=port, coordinator=coordinator,
            link_rewrite=farm.link_rewrite if farm else None,
            elastic=elastic, wal_dir=wal_dir, resume=True,
            lease_ms=lease_ms)

    sup = _TrackerSupervisor(tracker, wal_dir, _resumed_tracker,
                             quiet=quiet)
    tracker_addr = (tracker.host, tracker.port)
    if farm is not None:
        proxy = farm.front_tracker(tracker, kill_hook=sup.kill)
        tracker_addr = (proxy.host, proxy.port)
        sup.proxy = proxy
    standby = None
    if lease_ms:
        sb_host, sb_port = "127.0.0.1", 0
        if ":" in standby_spec:     # else truthy "1"/"auto": ephemeral
            h, _, p = standby_spec.rpartition(":")
            sb_host, sb_port = (h or "127.0.0.1"), int(p)
        # the standby follows the leader THROUGH the chaos front proxy:
        # a ``tracker_partition`` severs replication exactly like it
        # severs the workers, which is what makes partition failover
        # honest rather than simulated
        standby = _standby_mod.StandbyTracker(
            tracker_addr[0], tracker_addr[1], nworkers,
            wal_dir=os.path.join(wal_dir, "standby"),
            host=sb_host, port=sb_port, lease_ms=lease_ms,
            elastic=elastic,
            link_rewrite=farm.link_rewrite if farm else None,
            quiet=quiet).start()
        sup.standby = standby
    procs: Dict[int, subprocess.Popen] = {}
    # respawn accounting is PER RANK: `attempts[i]` counts every spawn
    # of worker i (exported as RABIT_NUM_TRIAL so mock kill schedules
    # advance), while `faults[i]` counts only the deaths that consume
    # the `max_attempts` budget — one flapping rank can exhaust its OWN
    # budget but never a healthy neighbour's. Elastic re-admissions are
    # exempt from the budget entirely: an evicted-then-readmitted rank
    # is the mechanism working, not a failure to police (the launch
    # `timeout` still bounds a flapping loop).
    attempts: Dict[int, int] = {i: 0 for i in range(nworkers)}
    faults: Dict[int, int] = {i: 0 for i in range(nworkers)}
    readmissions = 0
    finished: Dict[int, bool] = {i: False for i in range(nworkers)}

    def spawn(i: int) -> None:
        env = dict(os.environ)
        env.update(tracker.env(task_id=str(i), num_attempt=attempts[i]))
        # rendezvous at the CURRENT control plane, read at spawn time:
        # the chaos front proxy when one is configured (retarget()
        # keeps it valid across a failover), else the supervisor's
        # live tracker. The launch-time address must not be baked in —
        # after a failover the deposed leader is fenced and nothing
        # ever listens there again, so a worker respawned later would
        # burn its whole attempts budget connecting to a dead address.
        if farm is not None:
            uri, tracker_port = tracker_addr
        else:
            live = sup.tracker
            uri, tracker_port = live.host, live.port
        env["RABIT_TRACKER_URI"] = uri
        env["RABIT_TRACKER_PORT"] = str(tracker_port)
        if standby is not None:
            # the pre-advertised failover address: worker-side breakers
            # probe it when the leader goes quiet (telemetry/skew.py)
            env[_standby_mod.STANDBY_ENV] = \
                f"{standby.host}:{standby.port}"
        if elastic:
            env["RABIT_ELASTIC"] = "1"
        procs[i] = subprocess.Popen(cmd, env=env)

    try:
        for i in range(nworkers):
            spawn(i)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            # the tracker is supervised like the workers below: a
            # chaos-killed tracker respawns with resume=True once its
            # scheduled outage elapses
            sup.poll()
            alive = False
            for i in range(nworkers):
                p = procs.get(i)
                if p is None or finished[i]:
                    continue
                rc = p.poll()
                if rc is None:
                    alive = True
                    continue
                if rc == 0:
                    finished[i] = True
                    continue
                attempts[i] += 1
                if elastic:
                    # re-admit, not respawn-against-budget: the tracker
                    # evicts the dead rank (poll evidence or the worker
                    # side's evict call) so survivors re-form at N-1;
                    # this relaunch rejoins toward the target world
                    readmissions += 1
                else:
                    faults[i] += 1
                    if faults[i] > max_attempts:
                        raise RuntimeError(
                            f"worker {i} failed rc={rc} after "
                            f"{max_attempts} attempts (per-rank "
                            "budget)")
                if not quiet:
                    verb = "re-admit" if elastic else "respawn"
                    print(f"[launch] worker {i} died rc={rc}; {verb} "
                          f"attempt {attempts[i]}", file=sys.stderr,
                          flush=True)
                spawn(i)
                alive = True
            if all(finished.values()):
                return 0
            if not alive:
                break
            time.sleep(0.05)
        raise RuntimeError(
            f"timeout/stall: finished={sum(finished.values())}/{nworkers}")
    finally:
        # a respawn may have replaced the tracker object mid-run: all
        # end-of-run reads and the teardown go to the LIVE incarnation
        tracker = sup.tracker
        if stats is not None:
            # observability for tests: retained coordination services
            # must stay bounded no matter how many recovery epochs ran
            stats["services_retained"] = tracker.service_count()
            stats["total_attempts"] = sum(attempts.values())
            stats["attempts_by_rank"] = dict(attempts)
            stats["readmissions"] = readmissions
            stats["membership"] = tracker.membership_doc()
            # fleet-merged telemetry (per-rank summaries shipped via the
            # metrics command) — how cluster tests assert that recovery
            # spans/counters actually fired on the workers
            stats["fleet_metrics"] = tracker.merged_metrics()
            # causal incident plane (ISSUE 20): the folded fleet event
            # log + incident book, when ``rabit_events`` armed them
            if tracker._events_on:
                stats["fleet_events"] = tracker._events_doc()
                stats["incidents"] = tracker._incidents_doc()
            # live observability plane: endpoints announced, poll
            # sweeps completed, and the last straggler snapshot —
            # captured BEFORE tracker.stop() tears the poller down
            stats["live"] = tracker.live_stats()
            # crash-recovery accounting (ISSUE 10): tracker respawns
            # counted like worker respawns, plus the journal's size
            stats["tracker_restarts"] = sup.restarts
            stats["tracker_wal"] = {"dir": wal_dir,
                                    "records": tracker.wal_records(),
                                    "restarts": tracker.restarts}
            # hot-standby accounting (ISSUE 12): failovers are NOT
            # restarts — a promotion never re-forked anything
            stats["failover"] = {
                "standby": standby is not None,
                "failovers": sup.failovers,
                "promoted": (standby.promoted()
                             if standby is not None else False),
                "acked_seq": (standby.acked_seq
                              if standby is not None else 0),
                "resyncs": (standby.resyncs
                            if standby is not None else 0),
            }
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        if farm is not None:
            chaos_stats = farm.stop()
            if stats is not None:
                stats["chaos"] = chaos_stats
            if not quiet and chaos_stats["events"]:
                print(f"[launch] chaos injected {chaos_stats['events']} "
                      f"fault(s) across {chaos_stats['proxies']} proxies",
                      file=sys.stderr, flush=True)
        if standby is not None:
            standby.stop()  # also stops an adopted (promoted) tracker
        if standby is None or standby.tracker is not tracker:
            tracker.stop()


def submit_launch(addr: str, job_id: str, nworkers: int, cmd: List[str],
                  max_attempts: int = 20, timeout: float = 300.0,
                  elastic: bool = False, max_wait_s: float = 60.0,
                  quiet: bool = False) -> int:
    """``launch --submit``: run ``cmd`` as one JOB on an already-running
    multi-job tracker at ``addr`` (``HOST:PORT``). Admission first —
    :func:`jobs.submit_blocking` honors queued/shed backoff hints until
    admitted or ``max_wait_s`` lapses — then the same spawn/respawn
    discipline as :func:`launch`, with every worker addressing its own
    fault domain via the ``<job>/<i>`` task id. The tracker is NOT
    owned here: its lifecycle (and any chaos/standby fronting) belongs
    to whoever started it."""
    from . import jobs as _jobs_mod
    _refuse_shared_chip(nworkers, _xla_dataplane_requested(cmd))
    host, _, port_s = addr.rpartition(":")
    if not host or not port_s.isdigit():
        print(f"[submit] bad --submit address {addr!r} "
              f"(want HOST:PORT)", file=sys.stderr, flush=True)
        return 2
    port = int(port_s)
    try:
        verdict = _jobs_mod.submit_blocking(
            host, port, job_id, nworkers, elastic=elastic,
            max_wait_s=max_wait_s)
    except (TimeoutError, OSError) as e:
        print(f"[submit] job {job_id!r} not admitted: {e}",
              file=sys.stderr, flush=True)
        return 1
    if not quiet:
        print(f"[submit] job {job_id!r} admitted at {host}:{port} "
              f"({verdict})", file=sys.stderr, flush=True)
    procs: Dict[int, subprocess.Popen] = {}
    attempts: Dict[int, int] = {i: 0 for i in range(nworkers)}
    finished: Dict[int, bool] = {i: False for i in range(nworkers)}

    def spawn(i: int) -> None:
        env = dict(os.environ)
        env["RABIT_TRACKER_URI"] = host
        env["RABIT_TRACKER_PORT"] = str(port)
        env["RABIT_TASK_ID"] = f"{job_id}{_jobs_mod.JOB_SEP}{i}"
        env["RABIT_NUM_TRIAL"] = str(attempts[i])
        env["RABIT_WORLD_SIZE"] = str(nworkers)
        env["RABIT_MULTI_JOB"] = "1"
        if elastic:
            env["RABIT_ELASTIC"] = "1"
        procs[i] = subprocess.Popen(cmd, env=env)
        attempts[i] += 1

    for i in range(nworkers):
        spawn(i)
    deadline = time.monotonic() + timeout
    rc = 0
    try:
        while not all(finished.values()):
            if time.monotonic() > deadline:
                print(f"[submit] job {job_id!r} timed out after "
                      f"{timeout:.0f}s", file=sys.stderr, flush=True)
                rc = 1
                break
            time.sleep(0.1)
            for i, p in list(procs.items()):
                code = p.poll()
                if code is None or finished[i]:
                    continue
                if code == 0:
                    finished[i] = True
                elif attempts[i] >= max_attempts:
                    print(f"[submit] worker {job_id}/{i} exhausted "
                          f"{max_attempts} attempts", file=sys.stderr,
                          flush=True)
                    rc = 1
                    finished[i] = True
                else:
                    spawn(i)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--max-attempts", type=int, default=20)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="fault-injection schedule: JSON, @file.json "
                         "(default: RABIT_CHAOS env)")
    ap.add_argument("--elastic", action="store_true", default=None,
                    help="elastic world membership: evict dead ranks "
                         "so survivors continue at N-1, re-admit them "
                         "on relaunch (default: RABIT_ELASTIC env)")
    ap.add_argument("--submit", default=None, metavar="HOST:PORT",
                    help="submit --job to an already-running multi-job "
                         "tracker instead of starting one; backs off "
                         "and retries on queued/shed verdicts")
    ap.add_argument("--job", default=None, metavar="NAME",
                    help="job id for --submit (default: job-<pid>); "
                         "workers get task ids NAME/<i>")
    ap.add_argument("--submit-wait", type=float, default=60.0,
                    metavar="S", help="admission budget for --submit "
                                      "before giving up (default 60)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.cmd and args.cmd[0] == "--":
        args.cmd = args.cmd[1:]
    if not args.cmd:
        ap.error("missing worker command")
    if args.submit:
        return submit_launch(args.submit,
                             args.job or f"job-{os.getpid()}",
                             args.num_workers, args.cmd,
                             args.max_attempts, args.timeout,
                             elastic=bool(args.elastic),
                             max_wait_s=args.submit_wait)
    if args.job:
        ap.error("--job requires --submit")
    return launch(args.num_workers, args.cmd, args.max_attempts,
                  args.timeout, chaos=args.chaos, elastic=args.elastic)


if __name__ == "__main__":
    sys.exit(main())
