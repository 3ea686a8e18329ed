#!/usr/bin/env python
"""Report which hardware-evidence captures are still missing.

Each item is a committed artifact from a capture tool; an item counts
only when its artifact is fresh, was taken on a ``tpu`` backend and is
correct, so a CPU run or a stale capture never satisfies it.

Prints one line per outstanding item and exits nonzero while any
remain; exits 0 (silent) when the evidence set is complete.
`--have X` queries a single item (0 = already captured); unknown item
names exit 2 loudly — a fail-open typo here would silently skip a
capture step forever.

`--json` emits one machine-readable status line carrying the repo's
shared schema header (``rabit_tpu.capture_status/v1`` — the same
header family as BENCH_*/COLLECTIVE_SWEEP_*/telemetry artifacts), so
a caller parses a versioned document instead of grepping ad-hoc
``MISSING`` lines. Exit codes are unchanged.

`--live HOST:PORT` scrapes a running rank's (or the tracker's) live
metrics endpoint (``rabit_metrics_port``, telemetry/live.py) instead
of the on-disk evidence set: it GETs ``/healthz`` and ``/metrics``,
validates the Prometheus exposition, and emits one
``rabit_tpu.live_status/v1`` JSON line (identity, sample count,
collective counter total). Against the tracker it additionally GETs
``/straggler`` (best-effort; rank endpoints 404) and renders the
detector's verdict EXPLICITLY: ``signal=true`` names the laggard,
while a tie (``signal=false`` with a ``candidate_rank``) is reported
as ``verdict: tie`` — the candidate is the tie-break's would-be pick,
never an accusation the detector itself declined to make. A multi-job
tracker additionally answers ``/jobs``, and the line grows per-job
health (status / world / epoch / quarantine count) plus the admission
plane's queue depth and queued/shed totals. Exit 0 when the endpoint
is healthy, 1 when unreachable or unhealthy.
"""

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rabit_tpu.telemetry.schema import make_header  # noqa: E402

# Captures from before this cutoff predate the current kernel (the
# v5e VMEM fix + narrow-side fusion, commit 3d0d4b7) — comparisons
# like flagship default-vs-flash must not mix kernel versions. Date
# granularity is right even though 3d0d4b7 was committed 02:29Z on the
# cutoff day: the one earlier same-day artifact (BENCH_LOCAL 01:14Z)
# was captured with that fix already in the working tree and landed IN
# that commit — capture time precedes commit time, not the fix.
FRESH = "20260731"

KNOWN = ("kernel_hw", "flagship_flash", "flagship_default", "wire_tpu",
         "bench_local")


def _arts(prefix):
    # evidence lives under benchmarks/artifacts/; the repo root is
    # still scanned so pre-move checkouts (and tests that drop files
    # straight into a tmp REPO) keep working
    out = []
    paths = sorted(
        glob.glob(os.path.join(REPO, "benchmarks", "artifacts",
                               f"{prefix}_*.json"))
        + glob.glob(os.path.join(REPO, f"{prefix}_*.json")),
        key=os.path.basename)
    for p in paths:
        try:
            with open(p) as f:
                out.append(json.load(f))
        except (OSError, ValueError):
            continue
    return out


def _fresh(art):
    return str(art.get("timestamp_utc", ""))[:8] >= FRESH


def missing():
    """Every gate requires: current-kernel freshness (timestamp_utc >=
    FRESH — artifacts without the stamp count as stale) AND a tpu
    backend where the artifact records one, so a CPU run can never
    satisfy a device-evidence gate."""
    gaps = {}

    def good(prefix, pred=lambda a: True):
        return [a for a in _arts(prefix) if _fresh(a) and pred(a)]

    if not good("KERNEL_HW", lambda a: a.get("backend") == "tpu"
                and a.get("complete") and "flash_bwd_fused_vs_xla" in a):
        gaps["kernel_hw"] = ("no complete current-kernel KERNEL_HW artifact "
                             "with the fused flash backward measured")

    # both flagship legs must run on the CURRENT kernel: a legacy
    # default-attention artifact would make the default-vs-flash
    # comparison cross-version
    flag = good("FLAGSHIP_HW", lambda a: a.get("backend") == "tpu")
    if not [a for a in flag if a.get("flash_attn")]:
        gaps["flagship_flash"] = "no current-kernel flash FLAGSHIP_HW run"
    if not [a for a in flag if not a.get("flash_attn")]:
        gaps["flagship_default"] = ("no current-kernel default-attention "
                                    "FLAGSHIP_HW run")

    def tpu_rows(a):
        rows = a.get("tpu")
        return rows and all(r.get("backend") == "tpu" for r in rows)
    if not good("WIRE_BENCH", tpu_rows):
        gaps["wire_tpu"] = ("no current-kernel WIRE_BENCH artifact with a "
                            "tpu-backend device phase")

    if not good("BENCH_LOCAL", lambda a: a.get("backend") == "tpu"
                and a.get("correct") is True):
        gaps["bench_local"] = ("no correct tpu-backend BENCH_LOCAL capture "
                               "of the current kernel")

    return gaps


def live_status(target):
    """Scrape HOST:PORT's /healthz + /metrics; return (doc, ok)."""
    import urllib.error
    import urllib.request
    host, _, port = target.rpartition(":")
    doc = make_header("live_status")
    doc["target"] = target
    doc["ok"] = False
    try:
        base = f"http://{host}:{int(port)}"
    except ValueError:
        doc["error"] = f"bad target {target!r} (want HOST:PORT)"
        return doc, False
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=5.0) as r:
            health = json.load(r)
        with urllib.request.urlopen(base + "/metrics", timeout=5.0) as r:
            ctype = r.headers.get("Content-Type", "")
            text = r.read().decode()
    except (OSError, ValueError, urllib.error.URLError) as e:
        doc["error"] = f"{type(e).__name__}: {e}"
        return doc, False
    doc["health"] = health
    doc["exposition_ok"] = ("version=0.0.4" in ctype
                            and "# TYPE" in text)
    samples = [ln for ln in text.splitlines()
               if ln and not ln.startswith("#")]
    doc["samples"] = len(samples)
    collectives = 0
    for ln in samples:
        if ln.startswith("rabit_collective_total"):
            try:
                collectives += int(float(ln.rsplit(None, 1)[1]))
            except (ValueError, IndexError):
                pass
    doc["collectives_total"] = collectives
    # C10k control-plane gauges (ISSUE 19): surfaced as first-class
    # fields when the target is a tracker endpoint (rank endpoints
    # simply lack the families and the keys stay absent)
    for fam, key in (("rabit_tracker_open_conns", "open_conns"),
                     ("rabit_tracker_loop_lag_ms", "loop_lag_ms"),
                     ("rabit_wal_snapshot_seq", "wal_snapshot_seq"),
                     ("rabit_sched_preemptions_total",
                      "sched_preemptions_total")):
        total = None
        for ln in samples:
            if ln.startswith(fam + " ") or ln.startswith(fam + "{"):
                try:
                    total = (total or 0.0) + float(ln.rsplit(None, 1)[1])
                except (ValueError, IndexError):
                    pass
        if total is not None:
            doc[key] = total
    # /straggler is a tracker-only route; rank endpoints 404 and the
    # field is simply absent (scrape health is judged without it)
    try:
        with urllib.request.urlopen(base + "/straggler", timeout=5.0) as r:
            strag = json.load(r)
    except (OSError, ValueError, urllib.error.URLError):
        strag = None
    if isinstance(strag, dict) and "signal" in strag:
        if strag.get("signal") and strag.get("lagging_rank") is not None:
            doc["straggler"] = {
                "verdict": "lagging",
                "rank": strag["lagging_rank"],
                "lag_collectives": strag.get("lag_collectives", 0),
                "busy_skew_s": strag.get("busy_skew_s", 0.0)}
        elif strag.get("candidate_rank") is not None:
            # signal=false + candidate: the detector measured a
            # tie-break winner but declined to name a laggard — report
            # the tie as such instead of printing the candidate as if
            # accused
            doc["straggler"] = {
                "verdict": "tie",
                "candidate_rank": strag["candidate_rank"],
                "busy_skew_s": strag.get("busy_skew_s", 0.0)}
        else:
            doc["straggler"] = {"verdict": "none"}
    # /jobs is the multi-job tracker's admission/fault-domain route;
    # single-job trackers and rank endpoints simply lack it (or report
    # multi_job false), and the field stays absent
    try:
        with urllib.request.urlopen(base + "/jobs", timeout=5.0) as r:
            jobsdoc = json.load(r)
    except (OSError, ValueError, urllib.error.URLError):
        jobsdoc = None
    if isinstance(jobsdoc, dict) and jobsdoc.get("multi_job"):
        doc["jobs"] = {
            j["job"]: {"status": j.get("status"),
                       "world": j.get("world", 0),
                       "epoch": j.get("epoch", 0),
                       "quarantined": j.get("quarantined", 0)}
            for j in jobsdoc.get("jobs", [])
            if isinstance(j, dict) and j.get("job")}
        doc["admission"] = {
            "queue_depth": len(jobsdoc.get("queue", [])),
            "queued_total": jobsdoc.get("queued_total", 0),
            "shed_total": jobsdoc.get("shed_total", 0)}
    # /slo is the SLO-plane burn route (multi-job or lease-guarded
    # trackers); plain trackers and rank endpoints lack it and the
    # field stays absent — scrape health never depends on it
    try:
        with urllib.request.urlopen(base + "/slo", timeout=5.0) as r:
            slodoc = json.load(r)
    except (OSError, ValueError, urllib.error.URLError):
        slodoc = None
    if isinstance(slodoc, dict) and isinstance(slodoc.get("slos"), list):
        doc["slo"] = {
            "worst": slodoc.get("worst", "no_data"),
            "objectives": {
                v["slo"]: {"state": v.get("state"),
                           "value": v.get("value"),
                           "burn": v.get("burn")}
                for v in slodoc["slos"]
                if isinstance(v, dict) and v.get("slo")}}
    # /incidents is the causal incident plane's route (ISSUE 20,
    # trackers with rabit_events set); everything else lacks it and
    # the field stays absent
    try:
        with urllib.request.urlopen(base + "/incidents", timeout=5.0) as r:
            incdoc = json.load(r)
    except (OSError, ValueError, urllib.error.URLError):
        incdoc = None
    if isinstance(incdoc, dict) and "open" in incdoc:
        row = {"open": incdoc.get("open_count", 0),
               "worst": incdoc.get("worst", "none")}
        newest = None
        for inc in incdoc.get("recent", []):
            if isinstance(inc, dict) and inc.get("summary"):
                newest = inc
        if newest is not None:
            row["newest"] = (f"{newest.get('id')} "
                             f"[{newest.get('severity')}] "
                             f"{newest['summary']}")
        doc["incidents"] = row
    doc["ok"] = bool(health.get("ok")) and doc["exposition_ok"]
    return doc, doc["ok"]


def main():
    if len(sys.argv) == 3 and sys.argv[1] == "--live":
        doc, ok = live_status(sys.argv[2])
        print(json.dumps(doc, sort_keys=True))
        sys.exit(0 if ok else 1)
    gaps = missing()
    if len(sys.argv) == 3 and sys.argv[1] == "--have":
        item = sys.argv[2]
        if item not in KNOWN:
            print(f"capture_status: unknown item {item!r} "
                  f"(known: {', '.join(KNOWN)})", file=sys.stderr)
            sys.exit(2)
        sys.exit(1 if item in gaps else 0)
    if len(sys.argv) == 2 and sys.argv[1] == "--json":
        doc = make_header("capture_status")
        doc["complete"] = not gaps
        doc["missing"] = dict(sorted(gaps.items()))
        print(json.dumps(doc, sort_keys=True))
        sys.exit(1 if gaps else 0)
    for k, why in sorted(gaps.items()):
        print(f"MISSING {k}: {why}")
    sys.exit(1 if gaps else 0)


if __name__ == "__main__":
    main()
