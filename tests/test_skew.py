"""Skew-adaptive scheduling contract (the straggler-feedback PR's
tentpole):

- the EWMA estimator converges and its laggard election is hysteretic
  (one noisy round must not flip the adapted schedule);
- the fleet skew digest: built from a ``/straggler`` snapshot, served
  over the tracker's ``skew`` wire command, parsed worker-side;
- every adaptation plan is a pure permutation of the flat schedule
  (property-tested over worlds and laggards — adaptation may only move
  ranks, never add/drop/duplicate them);
- dispatch provenance: ``skew_adapted`` is recorded exactly when the
  knob is on AND a digest names a laggard;
- on the virtual mesh: pre-aggregation and rotation produce the same
  bytes as the flat schedules for association-free payloads;
- the acceptance bar: with ``rabit_skew_adapt`` unset, the bucketed
  MLP train-step jaxpr is byte-identical whether or not a digest is
  present, and zero ``skew_adapted`` elections occur.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from rabit_tpu import telemetry
from rabit_tpu.models import mlp
from rabit_tpu.ops.reducers import SUM, MAX, MIN
from rabit_tpu.parallel import device_allreduce, dispatch, make_mesh
from rabit_tpu.parallel.collectives import shard_over
from rabit_tpu.telemetry import skew
from rabit_tpu.tracker.tracker import Tracker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NDEV = len(jax.devices())

needs_mesh = pytest.mark.skipif(NDEV < 4, reason="needs 4 virtual devices")


@pytest.fixture
def skew_env(monkeypatch):
    """Clean slate: no adaptation knobs, no dispatch table, no host
    grouping leaking in from the environment; monitor state dropped on
    both sides so one test's forced digest can't bleed into another."""
    for var in ("RABIT_SKEW_ADAPT", "RABIT_SKEW_DIGEST",
                "RABIT_SKEW_PREAGG_MS", "RABIT_SKEW_POLL_MS",
                "RABIT_SKEW_SYNC_ROUNDS", "RABIT_SKEW_TRACKER",
                "RABIT_HIER", "RABIT_HIER_GROUP",
                "RABIT_DATAPLANE_WIRE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("RABIT_DISPATCH_TABLE", "none")
    dispatch.clear_cache()
    skew.reset_monitor()
    yield monkeypatch
    skew.reset_monitor()
    dispatch.clear_cache()


def _force_digest(monkeypatch, offsets, laggard, epoch=1):
    monkeypatch.setenv("RABIT_SKEW_DIGEST", json.dumps(
        {"epoch": epoch, "offsets_ms": offsets, "laggard": laggard}))
    skew.reset_monitor()


# ----------------------------------------------------------- estimator


def test_ewma_converges_to_stable_offsets():
    est = skew.SkewEstimator(alpha=0.3)
    for _ in range(40):
        est.update({0: 1.0, 1: 2.0, 2: 50.0})
    offs = est.offsets_ms()
    for rank, want in ((0, 1.0), (1, 2.0), (2, 50.0)):
        assert abs(offs[rank] - want) < 1e-3, offs
    assert est.laggard == 2
    assert abs(est.skew_ms() - 49.0) < 1e-2


def test_ewma_smooths_single_round_noise():
    """One wild observation moves the smoothed offset by only alpha of
    the jump — the reason the estimator exists."""
    est = skew.SkewEstimator(alpha=0.25)
    for _ in range(20):
        est.update({0: 0.0, 1: 10.0})
    est.update({0: 0.0, 1: 110.0})
    assert est.offsets_ms()[1] == pytest.approx(35.0, abs=0.5)


def test_laggard_flip_needs_hysteresis_margin():
    est = skew.SkewEstimator(alpha=1.0, hysteresis_ms=5.0)
    est.update({0: 0.0, 1: 20.0, 2: 0.0})
    assert est.laggard == 1
    # challenger ahead, but within the hysteresis band: no flip
    est.update({0: 0.0, 1: 20.0, 2: 24.0})
    assert est.laggard == 1
    # decisively ahead: the election flips
    est.update({0: 0.0, 1: 20.0, 2: 26.0})
    assert est.laggard == 2


def test_laggard_survives_brief_noise_at_low_alpha():
    """With smoothing on (alpha < 1), a couple of noisy rounds where
    another rank spikes must not steal the election from a persistently
    slow rank."""
    est = skew.SkewEstimator()           # library defaults
    for _ in range(10):
        est.update({0: 0.0, 1: 30.0, 2: 0.0})
    for _ in range(2):
        est.update({0: 0.0, 1: 30.0, 2: 45.0})
    assert est.laggard == 1
    # but a persistent challenger eventually wins
    for _ in range(30):
        est.update({0: 0.0, 1: 30.0, 2: 60.0})
    assert est.laggard == 2


def test_estimator_rejects_bad_alpha():
    with pytest.raises(ValueError, match="alpha"):
        skew.SkewEstimator(alpha=0.0)
    with pytest.raises(ValueError, match="alpha"):
        skew.SkewEstimator(alpha=1.5)


# ------------------------------------------------------ fleet election


def test_fleet_election_epoch_bumps_only_on_change():
    """The tracker-side election: epoch identifies the verdict, so it
    must bump exactly when the served laggard changes — a stable
    election keeps workers' jit cache keys stable."""
    el = skew.FleetElection(alpha=1.0, hysteresis_ms=5.0)
    assert el.fold(None) is None  # nothing ever folded: nothing served
    d1 = el.fold({"epoch": 0, "offsets_ms": {"0": 0.0, "1": 30.0},
                  "laggard": 1})
    assert d1["laggard"] == 1 and d1["epoch"] == 1
    # same verdict, fresher offsets: epoch must NOT move
    d2 = el.fold({"epoch": 0, "offsets_ms": {"0": 0.0, "1": 31.0},
                  "laggard": 1})
    assert d2["laggard"] == 1 and d2["epoch"] == 1
    # election flips (decisively past hysteresis): epoch bumps
    d3 = el.fold({"epoch": 0, "offsets_ms": {"0": 50.0, "1": 0.0},
                  "laggard": 0})
    assert d3["laggard"] == 0 and d3["epoch"] == 2
    # a tie sweep suppresses the accusation and that IS a new verdict
    d4 = el.fold({"epoch": 0, "offsets_ms": {"0": 50.0, "1": 0.0},
                  "laggard": None})
    assert d4["laggard"] is None and d4["epoch"] == 3
    # between sweeps the last served digest keeps being served
    assert el.fold(None) == d4


def test_fleet_election_smooths_and_holds_through_noise():
    """EWMA + hysteresis live fleet-side: a couple of noisy sweeps must
    not flip the served election (worker-side there is no smoothing at
    all — every process must see the same verdict)."""
    el = skew.FleetElection()
    for _ in range(10):
        d = el.fold({"epoch": 0, "offsets_ms": {"0": 0.0, "1": 30.0,
                                                "2": 0.0}, "laggard": 1})
    for _ in range(2):
        d = el.fold({"epoch": 0, "offsets_ms": {"0": 0.0, "1": 30.0,
                                                "2": 45.0}, "laggard": 2})
    assert d["laggard"] == 1 and d["epoch"] == 1


# -------------------------------------------------------------- digest


def _snapshot(rows, signal, lagging_rank=None):
    return {"ranks": rows, "signal": signal, "lagging_rank": lagging_rank,
            "candidate_rank": None, "lag_collectives": 0,
            "busy_skew_s": 0.0}


def test_digest_from_snapshot_offsets_and_laggard():
    # rank 1 waits the least inside collectives -> it is the one the
    # fleet waits FOR; offsets are (max busy - busy) / rounds
    rows = [{"rank": 0, "collectives": 10, "busy_s": 2.0},
            {"rank": 1, "collectives": 10, "busy_s": 0.5},
            {"rank": 2, "collectives": 10, "busy_s": 2.0}]
    d = skew.digest_from_snapshot(_snapshot(rows, True, 1), epoch=7)
    assert d["epoch"] == 7 and d["laggard"] == 1
    assert d["offsets_ms"]["1"] == pytest.approx(150.0)
    assert d["offsets_ms"]["0"] == pytest.approx(0.0)


def test_digest_from_snapshot_tie_never_accuses():
    rows = [{"rank": 0, "collectives": 5, "busy_s": 1.0},
            {"rank": 1, "collectives": 5, "busy_s": 1.0}]
    d = skew.digest_from_snapshot(_snapshot(rows, False), epoch=1)
    assert d is not None and d["laggard"] is None


def test_digest_from_snapshot_empty_is_none():
    assert skew.digest_from_snapshot({"ranks": []}) is None
    assert skew.digest_from_snapshot({}) is None
    assert skew.digest_from_snapshot(None) is None


@pytest.mark.parametrize("bad", [
    None, [], "x", {}, {"offsets_ms": "no"},
    {"offsets_ms": {"0": "NaNope"}},
    # laggard outside the offsets map: refuse rather than adapt blind
    {"offsets_ms": {"0": 1.0}, "laggard": 5},
])
def test_parse_digest_rejects_malformed(bad):
    assert skew.parse_digest(bad) is None


def test_parse_digest_canonicalizes():
    d = skew.parse_digest({"epoch": "3", "laggard": "1",
                           "offsets_ms": {"0": "0.5", "1": 9}})
    assert d == {"epoch": 3, "laggard": 1,
                 "offsets_ms": {0: 0.5, 1: 9.0}}


def test_skew_wire_roundtrip():
    """Tracker `skew` command: the digest set tracker-side comes back
    canonical through fetch_skew; an empty digest (no poll sweep yet)
    comes back as None, not a crash."""
    tr = Tracker(1, ready_timeout=5.0).start()
    try:
        assert skew.fetch_skew(tr.host, tr.port) is None
        digest = {"epoch": 4, "offsets_ms": {"0": 0.0, "1": 12.5},
                  "laggard": 1}
        with tr._lock:
            tr._skew = dict(digest)
        got = skew.fetch_skew(tr.host, tr.port)
        assert got == {"epoch": 4, "offsets_ms": {0: 0.0, 1: 12.5},
                       "laggard": 1}
    finally:
        tr.stop()


def test_fetch_skew_no_tracker_is_none():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    assert skew.fetch_skew("127.0.0.1", port, timeout=0.5) is None


def test_monitor_forced_digest_and_note_applied(skew_env):
    _force_digest(skew_env, {"0": 0.0, "1": 25.0}, 1)
    d = skew.monitor().current()
    assert skew.laggard_of(d) == 1
    assert skew.skew_ms_of(d) == pytest.approx(25.0)
    skew.note_applied("rotate@1")
    assert skew.last_applied() == "rotate@1"
    skew.reset_monitor()
    assert skew.last_applied() is None
    assert skew.monitor().current() is not None  # env still forces one


def test_monitor_tracker_candidate_gated_until_agreement(skew_env):
    """A tracker-fed digest is this process's OPINION, not fleet state:
    applied() must withhold it until a sync boundary adopts it, or each
    process would key static jit args on its own independently-timed
    fetch (the multi-controller deadlock the agreement plane exists to
    prevent)."""
    mon = skew.monitor()
    cand = mon.observe({"epoch": 3, "offsets_ms": {"0": 0.0, "1": 40.0},
                        "laggard": 1})
    assert skew.laggard_of(mon.current()) == 1  # candidate visible...
    assert mon.applied() is None                # ...but not actionable
    mon.set_applied(mon.current())              # the agreement boundary
    assert mon.applied() == cand
    skew.reset_sync()  # world re-forms: agreed state must drop
    assert mon.applied() is None
    assert skew.laggard_of(mon.current()) == 1  # candidate survives


def test_monitor_forced_digest_eligible_before_first_sync(skew_env):
    """RABIT_SKEW_DIGEST is identical on every process by the launch
    contract, so it may apply before any boundary; once a boundary
    runs, its verdict wins outright."""
    _force_digest(skew_env, {"0": 0.0, "1": 25.0}, 1)
    mon = skew.monitor()
    assert skew.laggard_of(mon.applied()) == 1
    mon.set_applied(None)  # a boundary agreed on "no adaptation"
    assert mon.applied() is None


def test_sync_due_fires_on_round_boundaries(skew_env):
    skew_env.setenv("RABIT_SKEW_SYNC_ROUNDS", "4")
    fires = [skew.sync_due() for _ in range(9)]
    assert fires == [True, False, False, False,
                     True, False, False, False, True]
    # a re-formed world restarts the cadence: first dispatch re-agrees
    skew.reset_sync()
    assert skew.sync_due() is True


def test_sync_rounds_knob_floor_and_validation(skew_env):
    assert skew.sync_rounds() == skew.SYNC_ROUNDS_DEFAULT
    skew_env.setenv("RABIT_SKEW_SYNC_ROUNDS", "0")
    assert skew.sync_rounds() == 1
    skew_env.setenv("RABIT_SKEW_SYNC_ROUNDS", "soon")
    with pytest.raises(ValueError, match="RABIT_SKEW_SYNC_ROUNDS"):
        skew.sync_rounds()


@pytest.mark.parametrize("world", [2, 4, 8])
def test_sync_vector_roundtrip_preserves_elections(world):
    """The 5-float agreement vector must reproduce every election the
    schedule keys on — laggard, earliest-arrival root, and the spread
    preagg gates on — through a float32 round-trip."""
    for lag in range(world):
        d = {"epoch": 7, "laggard": lag,
             "offsets_ms": {str(r): (80.0 if r == lag else float(r))
                            for r in range(world)}}
        vec = np.asarray(skew.encode_digest(d, world), np.float32)
        rt = skew.decode_digest(vec)
        parsed = skew.parse_digest(d)
        assert rt["epoch"] == 7
        assert skew.laggard_of(rt) == lag
        assert skew.earliest_of(rt, world) == skew.earliest_of(parsed,
                                                               world)
        assert skew.skew_ms_of(rt) == pytest.approx(
            skew.skew_ms_of(parsed), abs=1e-3)


def test_sync_vector_roundtrip_none_and_tie():
    assert skew.decode_digest(skew.encode_digest(None, 4)) is None
    tie = {"epoch": 2, "offsets_ms": {"0": 1.0, "1": 1.0},
           "laggard": None}
    rt = skew.decode_digest(skew.encode_digest(tie, 4))
    assert rt["epoch"] == 2 and rt["laggard"] is None
    assert skew.decode_digest([1.0, 1.0, 0.0]) is None  # wrong length


def test_monitor_never_blocks_on_dead_tracker(skew_env):
    """REVIEW medium: the dispatch path must not eat a socket timeout
    when the tracker is dead — current() only reads the cache and the
    poller thread absorbs the misses."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    skew_env.setenv("RABIT_SKEW_TRACKER", f"127.0.0.1:{port}")
    skew.reset_monitor()
    t0 = time.monotonic()
    for _ in range(20):
        assert skew.monitor().current() is None
    assert time.monotonic() - t0 < 1.0, "current() blocked on a socket"


def test_monitor_background_poller_picks_up_digest(skew_env):
    tr = Tracker(1, ready_timeout=5.0).start()
    try:
        with tr._lock:
            tr._skew = {"epoch": 2, "offsets_ms": {"0": 0.0, "1": 9.0},
                        "laggard": 1}
        skew_env.setenv("RABIT_SKEW_TRACKER", f"{tr.host}:{tr.port}")
        skew_env.setenv("RABIT_SKEW_POLL_MS", "100")
        skew.reset_monitor()
        deadline = time.monotonic() + 10.0
        got = skew.monitor().current()  # arms the poller, reads cache
        while got is None and time.monotonic() < deadline:
            time.sleep(0.05)
            got = skew.monitor().current()
        assert skew.laggard_of(got) == 1
    finally:
        tr.stop()


# --------------------------------------------- plans: permutation property


def _is_permutation(groups, world):
    flat = [r for g in groups for r in g]
    return sorted(flat) == list(range(world))


@pytest.mark.parametrize("world", range(2, 10))
def test_rotation_is_permutation_with_laggard_last(world):
    for lag in range(world):
        (order,) = skew.rotation_groups(world, lag)
        assert sorted(order) == list(range(world))
        assert order[-1] == lag
    with pytest.raises(ValueError, match="laggard"):
        skew.rotation_order(world, world)


@pytest.mark.parametrize("world", range(2, 10))
def test_preagg_groups_partition(world):
    for lag in range(world):
        early, single = skew.preagg_groups(world, lag)
        assert single == (lag,)
        assert _is_permutation((early, single), world)
        assert list(early) == sorted(early)  # flat order preserved


@pytest.mark.parametrize("world", range(3, 9))
def test_preagg_groups_places_elected_root_first(world):
    """REVIEW low: preagg_allreduce folds at ``early[0]``, so the
    elected root must LEAD the early tuple — encoding it anywhere else
    silently reverts the election to flat order."""
    for lag in range(world):
        for root in range(world):
            if root == lag:
                with pytest.raises(ValueError, match="root"):
                    skew.preagg_groups(world, lag, root=root)
                continue
            early, late = skew.preagg_groups(world, lag, root=root)
            assert early[0] == root
            assert late == (lag,)
            assert _is_permutation((early, late), world)
    with pytest.raises(ValueError, match="root"):
        skew.preagg_groups(world, 0, root=world)


def test_adapt_plan_preagg_elected_root_leads_early_group(skew_env):
    """The earliest-arrival election must reach the fold: the plan's
    root and ``groups[0][0]`` agree even when the earliest rank is not
    the lowest-numbered one."""
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "0.0001")
    digest = skew.parse_digest(
        {"epoch": 1, "laggard": 1,
         "offsets_ms": {"0": 5.0, "1": 30.0, "2": 0.0, "3": 6.0}})
    plan = skew.adapt_plan("ring", 4, 4096, "sum", digest=digest)
    assert plan["kind"] == "preagg"
    assert plan["root"] == 2
    assert plan["groups"][0][0] == plan["root"]
    assert plan["groups"] == ((2, 0, 3), (1,))


def test_demote_delegate_moves_laggard_to_tail_only():
    g = ((0, 1, 2), (3, 4, 5))
    assert skew.demote_delegate(g, 3) == ((0, 1, 2), (4, 5, 3))
    assert skew.demote_delegate(g, 1) == ((0, 2, 1), (3, 4, 5))
    # already at the tail, or not present: untouched
    assert skew.demote_delegate(g, 5) == g
    assert skew.demote_delegate(g, 9) == g


@pytest.mark.parametrize("world", range(2, 10))
@pytest.mark.parametrize("method", ["tree", "ring", "bidir", "swing"])
def test_adapt_plan_always_permutes_flat_schedule(skew_env, world, method):
    """Property: whatever plan adaptation elects, its groups are a
    permutation of the flat rank set — adaptation may only MOVE ranks.
    Checked with pre-aggregation both disabled (topology-only plans)
    and forced (threshold 0-adjacent)."""
    for preagg_ms, kinds in (("0", {"tree_reroot", "rotate"}),
                             ("0.0001", {"tree_reroot", "rotate",
                                         "preagg"})):
        skew_env.setenv("RABIT_SKEW_PREAGG_MS", preagg_ms)
        for lag in range(world):
            offs = {str(r): (30.0 if r == lag else float(r))
                    for r in range(world)}
            digest = {"epoch": 1, "offsets_ms": offs, "laggard": lag}
            plan = skew.adapt_plan(method, world, 4096, "sum",
                                   digest=skew.parse_digest(digest))
            assert plan is not None and plan["kind"] in kinds, plan
            assert plan["laggard"] == lag
            assert plan["root"] != lag
            if plan["groups"] is not None:
                assert _is_permutation(plan["groups"], world), plan


@pytest.mark.parametrize("world", [4, 8])
def test_adapt_plan_hier_demotes_within_partition(skew_env, world):
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "0")
    half = world // 2
    groups = (tuple(range(half)), tuple(range(half, world)))
    for lag in range(world):
        digest = skew.parse_digest(
            {"epoch": 1, "laggard": lag,
             "offsets_ms": {str(r): (30.0 if r == lag else 0.0)
                            for r in range(world)}})
        plan = skew.adapt_plan("hier", world, 4096, "sum",
                               groups=groups, digest=digest)
        assert plan is not None and plan["kind"] == "hier_demote"
        assert _is_permutation(plan["groups"], world)
        # membership per host is preserved, only slot order changes
        for got, want in zip(plan["groups"], groups):
            assert sorted(got) == sorted(want)
            if lag in want:
                assert got[-1] == lag


def test_adapt_plan_none_without_laggard(skew_env):
    assert skew.adapt_plan("ring", 4, 4096, "sum", digest=None) is None
    tie = skew.parse_digest({"epoch": 1, "laggard": None,
                             "offsets_ms": {"0": 0.0, "1": 9.0}})
    assert skew.adapt_plan("ring", 4, 4096, "sum", digest=tie) is None
    # a laggard outside this world (stale digest after a resize)
    stale = {"epoch": 1, "offsets_ms": {0: 0.0, 7: 50.0}, "laggard": 7}
    assert skew.adapt_plan("ring", 4, 4096, "sum", digest=stale) is None


def test_adapt_plan_preagg_gates_on_threshold_and_op(skew_env):
    """Pre-aggregation engages only for SUM payloads whose measured
    skew clears ``rabit_skew_preagg_ms`` x payload-MiB; below the bar
    (or for non-sum ops) the topology-only plan applies."""
    digest = skew.parse_digest(
        {"epoch": 1, "laggard": 3,
         "offsets_ms": {"0": 0.0, "1": 0.0, "2": 0.0, "3": 8.0}})
    mib = 1 << 20
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "2.0")
    # 8 ms skew, 1 MiB payload, 2 ms/MiB bar -> preagg
    plan = skew.adapt_plan("ring", 4, mib, "sum", digest=digest)
    assert plan["kind"] == "preagg"
    assert plan["groups"] == ((0, 1, 2), (3,))
    # 8 MiB payload raises the bar to 16 ms -> rotation instead
    plan = skew.adapt_plan("ring", 4, 8 * mib, "sum", digest=digest)
    assert plan["kind"] == "rotate"
    # max never pre-aggregates through this gate
    plan = skew.adapt_plan("ring", 4, mib, "max", digest=digest)
    assert plan["kind"] == "rotate"
    # threshold <= 0 disables preagg outright
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "0")
    plan = skew.adapt_plan("ring", 4, mib, "sum", digest=digest)
    assert plan["kind"] == "rotate"


def test_knob_validation():
    os.environ["RABIT_SKEW_PREAGG_MS"] = "fast"
    try:
        with pytest.raises(ValueError, match="RABIT_SKEW_PREAGG_MS"):
            skew.preagg_ms_per_mib()
    finally:
        del os.environ["RABIT_SKEW_PREAGG_MS"]
    os.environ["RABIT_SKEW_POLL_MS"] = "soon"
    try:
        with pytest.raises(ValueError, match="RABIT_SKEW_POLL_MS"):
            skew.poll_interval_s()
    finally:
        del os.environ["RABIT_SKEW_POLL_MS"]
    os.environ["RABIT_SKEW_POLL_MS"] = "1"
    try:
        assert skew.poll_interval_s() == skew.POLL_MS_FLOOR / 1000.0
    finally:
        del os.environ["RABIT_SKEW_POLL_MS"]


# ------------------------------------------------- dispatch provenance


def test_resolve_skew_adapted_provenance(skew_env):
    skew_env.setenv("RABIT_SKEW_ADAPT", "1")
    _force_digest(skew_env, {"0": 0.0, "1": 40.0}, 1)
    telemetry.reset(capacity=64, enabled=True)
    try:
        f32 = np.dtype(np.float32)
        assert dispatch.resolve(10**6, f32, SUM, 4)[0] == "ring"
        # the fixed-topology involution degrades to a rotatable shape
        assert dispatch.resolve(100, f32, SUM, 4, method="auto")[0] \
            == "tree"
        snap = telemetry.snapshot()
        provs = {c.get("provenance") for c in snap["counters"]
                 if c["name"] == "dispatch"}
        assert provs == {"skew_adapted"}, snap["counters"]
        assert any(c["name"] == "dispatch.skew_adapted"
                   and c["count"] >= 2 for c in snap["counters"])
    finally:
        telemetry.reset(enabled=False)


def test_resolve_no_provenance_when_knob_off(skew_env):
    """Digest present but the knob unset: dispatch must not consult it
    and no skew_adapted election may appear."""
    _force_digest(skew_env, {"0": 0.0, "1": 40.0}, 1)
    telemetry.reset(capacity=64, enabled=True)
    try:
        f32 = np.dtype(np.float32)
        dispatch.resolve(10**6, f32, SUM, 4)
        snap = telemetry.snapshot()
        assert all(c.get("provenance") != "skew_adapted"
                   for c in snap["counters"]), snap["counters"]
        assert not any(c["name"] == "dispatch.skew_adapted"
                       for c in snap["counters"])
    finally:
        telemetry.reset(enabled=False)


def test_resolve_no_provenance_for_out_of_world_laggard(skew_env):
    """REVIEW low: a digest naming a laggard outside this world (stale
    after a resize, or another mesh's verdict) adapts nothing —
    resolve must not stamp skew_adapted for a plan that cannot
    apply."""
    skew_env.setenv("RABIT_SKEW_ADAPT", "1")
    _force_digest(skew_env, {"0": 0.0, "7": 90.0}, 7)
    telemetry.reset(capacity=64, enabled=True)
    try:
        f32 = np.dtype(np.float32)
        dispatch.resolve(10**6, f32, SUM, 4)
        dispatch.resolve(100, f32, SUM, 4, method="auto")
        snap = telemetry.snapshot()
        assert all(c.get("provenance") != "skew_adapted"
                   for c in snap["counters"]), snap["counters"]
        assert not any(c["name"] == "dispatch.skew_adapted"
                       for c in snap["counters"])
    finally:
        telemetry.reset(enabled=False)


def test_resolve_enabled_without_digest_is_unadapted(skew_env):
    skew_env.setenv("RABIT_SKEW_ADAPT", "1")
    telemetry.reset(capacity=64, enabled=True)
    try:
        f32 = np.dtype(np.float32)
        assert dispatch.resolve(10**6, f32, SUM, 8)[0] == "ring"
        assert not any(c["name"] == "dispatch.skew_adapted"
                       for c in telemetry.snapshot()["counters"])
    finally:
        telemetry.reset(enabled=False)


def test_resolve_explicit_preagg_passthrough(skew_env):
    f32 = np.dtype(np.float32)
    method, wire = dispatch.resolve(10**6, f32, SUM, 4, method="preagg")
    assert method == "preagg" and wire is None
    # preagg ships raw ppermute payloads: a requested env wire is
    # ignored on this path like on the tree path
    skew_env.setenv("RABIT_DATAPLANE_WIRE", "int8")
    assert dispatch.resolve(10**6, f32, SUM, 4,
                            method="preagg")[1] is None


# ------------------------------------------------------- mesh behavior


@needs_mesh
@pytest.mark.parametrize("op,fold", [(SUM, np.sum), (MAX, np.max),
                                     (MIN, np.min)])
@pytest.mark.parametrize("dt", [np.int32, np.float32])
def test_preagg_allreduce_matches_flat(skew_env, op, fold, dt):
    """Explicit preagg for every laggard position: identical bytes to
    the flat tree on association-free payloads (the per-rank
    contributions differ by rank so a dropped/duplicated contribution
    cannot cancel)."""
    mesh = make_mesh(4)
    rng = np.random.default_rng(17)
    per_rank = rng.integers(-40, 40, (4, 257)).astype(dt)
    flat = np.asarray(device_allreduce(
        shard_over(mesh, per_rank), mesh, op, method="tree"))
    want = fold(per_rank, axis=0)
    np.testing.assert_array_equal(flat, want)
    for lag in range(4):
        got = np.asarray(device_allreduce(
            shard_over(mesh, per_rank), mesh, op, method="preagg",
            groups=skew.preagg_groups(4, lag)))
        assert got.dtype == flat.dtype, (op, dt, lag)
        np.testing.assert_array_equal(got, flat)


@needs_mesh
@pytest.mark.parametrize("method", ["ring", "bidir", "swing"])
def test_rotation_bitexact_vs_flat(skew_env, method):
    """The adapted (rotated) schedule applied through the live digest
    path returns the same bytes as the flat schedule for integer-valued
    payloads, for every laggard."""
    mesh = make_mesh(4)
    rng = np.random.default_rng(23)
    per_rank = rng.integers(-50, 50, (4, 1031)).astype(np.float32)
    flat = np.asarray(device_allreduce(
        shard_over(mesh, per_rank), mesh, SUM, method=method))
    np.testing.assert_array_equal(flat, per_rank.sum(0))
    skew_env.setenv("RABIT_SKEW_ADAPT", "1")
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "0")  # isolate rotation
    for lag in range(4):
        _force_digest(skew_env,
                      {str(r): (50.0 if r == lag else 0.0)
                       for r in range(4)}, lag)
        got = np.asarray(device_allreduce(
            shard_over(mesh, per_rank), mesh, SUM, method=method))
        np.testing.assert_array_equal(got, flat)
        assert skew.last_applied() == f"rotate@{lag}", (method, lag)


@needs_mesh
def test_auto_adapted_span_attribute(skew_env):
    """method=auto + live digest: the dispatch provenance, the applied
    plan, and the span's ``adapted`` attribute all agree."""
    skew_env.setenv("RABIT_SKEW_ADAPT", "1")
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "0")  # elect the re-root
    _force_digest(skew_env, {"0": 0.0, "1": 0.0, "2": 45.0, "3": 0.0}, 2)
    mesh = make_mesh(4)
    per_rank = np.tile(np.arange(64, dtype=np.int32), (4, 1))
    telemetry.reset(capacity=64, enabled=True)
    try:
        out = np.asarray(device_allreduce(
            shard_over(mesh, per_rank), mesh, SUM))
        np.testing.assert_array_equal(out, np.arange(64) * 4)
        snap = telemetry.snapshot()
        spans = [s for s in snap["spans"] if s["name"] == "allreduce"]
        assert spans and spans[0]["attrs"].get("adapted") \
            == "tree_reroot@2", spans
        assert any(c["name"] == "dispatch.skew_adapted"
                   for c in snap["counters"])
    finally:
        telemetry.reset(enabled=False)


@needs_mesh
def test_device_path_adopts_candidate_only_at_boundary(skew_env):
    """Agreement discipline on the device path: dispatch acts on the
    digest ADOPTED at the last sync boundary, not the live candidate —
    a fresher tracker fetch mid-window must not flip the schedule until
    the next boundary (static jit args may only change in fleet
    lockstep)."""
    skew_env.setenv("RABIT_SKEW_ADAPT", "1")
    skew_env.setenv("RABIT_SKEW_PREAGG_MS", "0")  # isolate rotation
    skew_env.setenv("RABIT_SKEW_SYNC_ROUNDS", "1000")
    mesh = make_mesh(4)
    per_rank = np.tile(np.arange(32, dtype=np.int32), (4, 1))
    want = np.arange(32) * 4

    def digest_naming(lag, epoch):
        return {"epoch": epoch, "laggard": lag,
                "offsets_ms": {str(r): (45.0 if r == lag else 0.0)
                               for r in range(4)}}

    mon = skew.monitor()
    mon.observe(digest_naming(2, 1))
    assert mon.applied() is None  # candidate awaits the first boundary
    out = np.asarray(device_allreduce(shard_over(mesh, per_rank),
                                      mesh, SUM, method="ring"))
    np.testing.assert_array_equal(out, want)
    assert skew.last_applied() == "rotate@2"  # dispatch 0 IS a boundary
    # a fresher candidate inside the window: the schedule must hold
    mon.observe(digest_naming(3, 2))
    out = np.asarray(device_allreduce(shard_over(mesh, per_rank),
                                      mesh, SUM, method="ring"))
    np.testing.assert_array_equal(out, want)
    assert skew.last_applied() == "rotate@2"
    # world re-forms -> next dispatch re-agrees -> new election lands
    skew.reset_sync()
    out = np.asarray(device_allreduce(shard_over(mesh, per_rank),
                                      mesh, SUM, method="ring"))
    np.testing.assert_array_equal(out, want)
    assert skew.last_applied() == "rotate@3"


@needs_mesh
def test_adapt_off_is_inert_on_device_path(skew_env):
    """Digest in the environment but knob unset: no adaptation state is
    written at all."""
    _force_digest(skew_env, {"0": 0.0, "1": 45.0}, 1)
    mesh = make_mesh(4)
    per_rank = np.tile(np.arange(32, dtype=np.int32), (4, 1))
    out = np.asarray(device_allreduce(shard_over(mesh, per_rank),
                                      mesh, SUM, method="ring"))
    np.testing.assert_array_equal(out, np.arange(32) * 4)
    assert skew.last_applied() is None


# ------------------------------------------------ jaxpr purity (gate)


needs_8dev = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")


def _prims(jaxpr):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, ClosedJaxpr):
                    out.extend(_prims(sub.jaxpr))
                elif isinstance(sub, Jaxpr):
                    out.extend(_prims(sub))
    return out


@needs_8dev
def test_train_step_jaxpr_identical_with_knob_unset(skew_env):
    """Acceptance bar: rabit_skew_adapt unset -> the bucketed MLP train
    step traces to a byte-identical jaxpr whether or not a skew digest
    is present, and zero skew_adapted elections are recorded."""
    mesh = make_mesh(8, ("dp", "tp"), (4, 2))
    params, x, y = mlp.make_sharded_inputs(
        mesh, batch=16, in_dim=12, hidden=8, out_dim=4, seed=7)
    step = mlp.make_train_step(mesh, lr=0.5, grad_sync="bucket")

    def trace():
        jax.clear_caches()
        return _prims(jax.make_jaxpr(step)(params, x, y).jaxpr)

    telemetry.reset(capacity=256, enabled=True)
    try:
        without = trace()
        _force_digest(skew_env, {"0": 0.0, "1": 60.0}, 1)
        with_digest = trace()
        snap = telemetry.snapshot()
    finally:
        telemetry.reset(enabled=False)
    assert without == with_digest
    assert without.count("ppermute") == 6  # test_bucketing's count
    assert not any(c["name"] == "dispatch.skew_adapted"
                   for c in snap["counters"])
    assert all(c.get("provenance") != "skew_adapted"
               for c in snap["counters"])


# --------------------------------------------------- real gloo cluster


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_skew_adaptation_on_gloo_cluster():
    """4 real processes, rank 2 sleeping before every collective: the
    adapted schedule must (a) stay bit-exact against the flat ring
    across dtypes and (b) lower the fleet-mean round time."""
    nproc = 4
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    worker = os.path.join(ROOT, "tests", "workers", "skew_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), str(nproc), str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out}"
        assert f"rank {i}/{nproc} OK" in out, out
