#!/usr/bin/env python
"""Telemetry end-to-end smoke test (ci.sh stage 6).

Starts a real 2-worker local rendezvous with the tracker's /metrics +
/healthz HTTP surface enabled, has each worker (a separate process, so
telemetry registries are genuinely per-rank) push heartbeats over the
rendezvous protocol while driving the step ledger — with rank 1
fault-injected (``DMLC_FAULT_SPEC`` delay) to be a straggler — then:

  1. scrapes /metrics and validates every line parses as Prometheus
     text exposition (strict: family grouping, one TYPE per family),
     with samples from BOTH ranks plus the merged view, the build-info
     / heartbeat-age gauges, and the per-rank step-ledger families;
  2. checks /healthz reports >= 2 ranks;
  3. asserts the anomaly watchdog flagged EXACTLY rank 1 as a
     straggler on /anomalies (and no flags on the healthy rank 0),
     with the matching dmlc_anomaly_* surface on /metrics;
  4. renders one ``dmlc top`` refresh in plain mode against the live
     tracker and checks both ranks and the straggler flag appear;
  5. scrapes /trace and validates the cluster-merged Chrome trace:
     spans from BOTH ranks under DISTINCT pids, labeled rank process
     rows, monotone non-negative clock-corrected timestamps, and the
     watchdog's anomaly marker row;
  6. exports the smoke process's own spans as Chrome trace JSON and
     validates it is well-formed with >= 1 complete ("X") event;
  7. (PR 16) rank 1 churns six fresh shapes through a profiled jit
     site: the compile ledger's ``compute.compile`` spans reach
     the cluster /trace, the heartbeat-shipped compute doc trips a
     ``recompile_storm`` flag on rank 1 ONLY (/anomalies + the
     dmlc_anomaly_recompile_storm_flags family + tracker /compute
     ``storming_ranks``), and dmlc-top renders the compute pane.

Both workers run under ``DMLC_LOCKCHECK=1`` (the runtime lock-order
watchdog instruments every ``concurrency.make_lock`` lock) AND
``DMLC_RACECHECK=1`` (every acquire site records its attribute→lock
pairing, cross-checked against the static guarded-by analysis of
``analysis.race_pass``), and assert clean reports for both before
exiting — a lock-order regression or a static/dynamic guarded-by
drift in the telemetry path fails this smoke, not production.

Exit 0 on success, 1 with a diagnostic on any failure.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dmlc_tpu import telemetry  # noqa: E402
from dmlc_tpu.tracker.rendezvous import RabitTracker  # noqa: E402

N_STEPS = 24
BASE_STEP_S = 0.02
STRAGGLE_DELAY_S = 0.15

WORKER_CODE = """
import sys, time
sys.path.insert(0, {repo!r})
from dmlc_tpu import telemetry
from dmlc_tpu.resilience import fault_point
from dmlc_tpu.telemetry import HeartbeatSender
from dmlc_tpu.tracker.client import TrackerClient

c = TrackerClient(jobid="smoke%d" % {idx}).start(world_size=2)
# distinct per-rank distributions so the scrape provably carries data
# from each worker, not one rank twice
for i in range(20):
    telemetry.observe_duration("feed", "producer_stall",
                               0.001 * (c.rank + 1) * (i % 5 + 1))
    telemetry.inc("smoke", "beats")
# per-rank spans: these ship with the heartbeats (incremental trace
# push + NTP clock sample) and must appear on the tracker's /trace
with telemetry.span("smoke.work.r%d" % c.rank, stage="smoke"):
    time.sleep(0.05)
# rank 1 churns shapes through a profiled jit site: each novel shape
# is a fresh XLA signature, so the compile ledger records the traces
# (with compute.compile spans for /trace), the heartbeat ships
# the compute doc, and the tracker watchdog must flag a
# recompile_storm on THIS rank only — rank 0 never touches jax and
# so never even grows a compute doc
if c.rank == 1:
    import jax.numpy as jnp
    from dmlc_tpu.telemetry import compute as _compute
    churn = _compute.profiled_jit(lambda x: x * 2.0, site="smoke.churn")
    for n in range(1, 7):
        churn(jnp.zeros((n,), jnp.float32))
hb = HeartbeatSender(c, interval=0.2)
# drive the step ledger: DMLC_FAULT_SPEC delays rank 1's every step,
# so the tracker watchdog must flag it (and only it) as a straggler
for i in range({n_steps}):
    telemetry.step_begin()
    fault_point("smoke.step", rank=c.rank)
    time.sleep({base_step})
    telemetry.step_end(tokens=256)
time.sleep(1.0)
hb.close()
c.shutdown()
# this worker ran with DMLC_LOCKCHECK=1 + DMLC_RACECHECK=1: every
# make_lock() lock in the telemetry/heartbeat/step-ledger path was
# instrumented — a recorded order inversion, a held-while-blocked
# wait, or an observed attribute→lock pairing contradicting the
# static guarded-by analysis fails the worker (and so the smoke)
from dmlc_tpu.concurrency import lockcheck_assert_clean, \
    racecheck_assert_clean, racecheck_observed
lockcheck_assert_clean()
if not racecheck_observed():
    raise SystemExit("racecheck recorded no acquire sites — the "
                     "DMLC_RACECHECK instrumentation went dark")
racecheck_assert_clean()
"""

def fail(msg: str) -> None:
    print(f"telemetry smoke FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def validate_prometheus(body: str) -> int:
    """Strict exposition check (grouping, one HELP/TYPE per family,
    escaped label values) — the SAME oracle the unit tests use, so the
    smoke and tests can never drift apart in strictness."""
    from dmlc_tpu.telemetry.exporters import validate_exposition_text

    try:
        return validate_exposition_text(body)
    except ValueError as e:
        fail(f"exposition violation: {e}")


def validate_merged_trace(url: str) -> None:
    """Scrape /trace: a valid Chrome trace with spans from BOTH worker
    ranks under distinct pids, labeled rank rows, monotone non-negative
    corrected timestamps, and the watchdog's anomaly markers."""
    doc = json.loads(urllib.request.urlopen(f"{url}/trace").read())
    evs = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    for ev in evs:
        for k in ("name", "ts", "dur", "pid", "tid"):
            if k not in ev:
                fail(f"/trace event missing {k!r}: {ev}")
    # workers are pid rank+1; the tracker's own row is pid 0
    worker_pids = sorted({e["pid"] for e in evs if e["pid"] >= 1})
    if len(worker_pids) < 2:
        fail(f"/trace has spans from pids {worker_pids} (< 2 worker "
             f"ranks); events:\n{json.dumps(evs)[:2000]}")
    names = {e["name"] for e in evs}
    for want in ("smoke.work.r0", "smoke.work.r1", "step",
                 # rank 1's churned compiles draw real spans: compile
                 # wall time is attributable on the cluster trace
                 "compute.compile"):
        if want not in names:
            fail(f"/trace missing worker span {want!r}; got {sorted(names)}")
    if any(e["ts"] < 0 for e in evs):
        fail("/trace has negative corrected timestamps")
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    for r in (0, 1):
        if not any(p.startswith(f"rank {r}") for p in procs):
            fail(f"/trace has no labeled process row for rank {r}: {procs}")
    markers = [e for e in doc["traceEvents"]
               if e.get("ph") == "i" and e.get("cat") == "anomaly"]
    if not any("straggler rank 1" in m.get("name", "") for m in markers):
        fail(f"/trace lacks the straggler anomaly marker; markers="
             f"{[m.get('name') for m in markers]}")
    if any(m["ts"] < 0 for m in markers):
        fail("/trace anomaly markers have negative timestamps")
    print(f"telemetry smoke: /trace OK ({len(evs)} spans from "
          f"pids {worker_pids}, {len(markers)} anomaly markers)")


def validate_anomalies(url: str) -> None:
    """Poll /anomalies until the watchdog flags rank 1 as a straggler;
    assert the healthy rank is never flagged."""
    deadline = time.time() + 60
    doc = {}
    while time.time() < deadline:
        doc = json.loads(urllib.request.urlopen(f"{url}/anomalies").read())
        flags1 = (doc.get("ranks", {}).get("1", {}) or {}).get("flags", [])
        if "straggler" in flags1:
            break
        time.sleep(0.2)
    else:
        fail(f"watchdog never flagged rank 1 as straggler; /anomalies:\n"
             f"{json.dumps(doc)[:3000]}")
    flags0 = (doc.get("ranks", {}).get("0", {}) or {}).get("flags", [])
    if "straggler" in flags0:
        fail(f"healthy rank 0 falsely flagged: {flags0}")
    active = {(a.get("rank"), a.get("kind"))
              for a in doc.get("active", [])}
    if (1, "straggler") not in active:
        fail(f"/anomalies active list lacks rank 1 straggler: {active}")
    r1 = doc["ranks"]["1"]
    for key in ("step_time_s", "step_time_ewma_s",
                "goodput_tokens_per_s"):
        if not isinstance(r1.get(key), (int, float)):
            fail(f"/anomalies rank 1 missing {key}: {r1}")
    if not doc.get("recent_verdicts"):
        fail("/anomalies has no recent verdicts after a flag fired")
    print(f"telemetry smoke: /anomalies OK (rank 1 straggler at "
          f"step_time={r1['step_time_s']:.3f}s vs cluster median "
          f"{doc['cluster']['median_step_s']:.3f}s; rank 0 clean)")

    # PR 16: rank 1's shape churn crossed the storm threshold — the
    # compute doc rode the heartbeats and the watchdog must flag a
    # recompile_storm on rank 1 (and never on rank 0, which runs no
    # profiled jit sites at all)
    while time.time() < deadline:
        doc = json.loads(urllib.request.urlopen(f"{url}/anomalies").read())
        flags1 = (doc.get("ranks", {}).get("1", {}) or {}).get("flags", [])
        if "recompile_storm" in flags1:
            break
        time.sleep(0.2)
    else:
        fail(f"watchdog never flagged rank 1's recompile storm; "
             f"/anomalies:\n{json.dumps(doc)[:3000]}")
    flags0 = (doc.get("ranks", {}).get("0", {}) or {}).get("flags", [])
    if "recompile_storm" in flags0:
        fail(f"rank 0 falsely flagged as storming: {flags0}")
    comp1 = (doc["ranks"]["1"] or {}).get("compute") or {}
    if not isinstance(comp1.get("traces"), (int, float)) \
            or comp1["traces"] < 4:
        fail(f"/anomalies rank 1 compute doc missing traces: {comp1}")
    cdoc = json.loads(urllib.request.urlopen(f"{url}/compute").read())
    if cdoc.get("storming_ranks") != [1]:
        fail(f"tracker /compute storming_ranks != [1]: {cdoc}")
    if "1" not in (cdoc.get("ranks") or {}):
        fail(f"tracker /compute lacks rank 1's doc: {cdoc}")
    print(f"telemetry smoke: /compute OK (rank 1 storm after "
          f"{comp1['traces']} traces; rank 0 clean)")


def validate_dmlc_top(url: str) -> None:
    """One plain-mode ``dmlc top`` refresh against the live tracker."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "dmlc_top.py"),
         url, "--plain", "--once"],
        capture_output=True, text=True, timeout=60)
    if r.returncode != 0:
        fail(f"dmlc-top exited {r.returncode}: {r.stderr[:2000]}")
    out = r.stdout
    if "RANK" not in out or "FLAGS" not in out:
        fail(f"dmlc-top table header missing:\n{out[:2000]}")
    rows = {line.split()[0] for line in out.splitlines()
            if line.strip() and line.split()[0].isdigit()}
    if not {"0", "1"} <= rows:
        fail(f"dmlc-top lacks per-rank rows (got {rows}):\n{out[:2000]}")
    straggler_rows = [line for line in out.splitlines()
                     if line.strip().startswith("1 ")
                     and "straggler" in line]
    if not straggler_rows:
        fail(f"dmlc-top does not show rank 1's straggler flag:\n"
             f"{out[:2000]}")
    if "compute " not in out or "STORM ranks=[1]" not in out:
        fail(f"dmlc-top compute pane missing rank 1's storm:\n"
             f"{out[:2000]}")
    print("telemetry smoke: dmlc-top OK (one plain refresh, straggler "
          "flag + compute storm visible)")
    print("\n".join("    " + line for line in out.splitlines()[:6]))


def main() -> None:
    tracker = RabitTracker("127.0.0.1", 2, metrics_port=0)
    tracker.start(2)
    url = f"http://127.0.0.1:{tracker.metrics_port}"
    env = dict(os.environ)
    env.update(tracker.worker_envs())
    # rank 1 pays a delay fault on EVERY step: the deterministic
    # straggler the watchdog must catch (and rank 0 must not trip on)
    env["DMLC_FAULT_SPEC"] = \
        f"smoke.step@rank:1=delay:{STRAGGLE_DELAY_S}:*"
    # run the workers under the runtime lock-order watchdog: the whole
    # heartbeat/ledger/telemetry lock surface is exercised end-to-end
    # and each worker asserts a clean lockcheck report before exiting
    env["DMLC_LOCKCHECK"] = "1"
    # ... and a clean racecheck (attribute→lock pairing) report too
    env["DMLC_RACECHECK"] = "1"
    # rank 1's shape churn needs a jax backend; CPU keeps it hermetic.
    # 6 churned shapes against a threshold of 4 traces/window makes the
    # storm verdict deterministic even if the ambient env raised it
    env["JAX_PLATFORMS"] = "cpu"
    env["DMLC_COMPUTE_STORM_TRACES"] = "4"
    workers = [
        subprocess.Popen(
            [sys.executable, "-c",
             WORKER_CODE.format(repo=REPO, idx=i, n_steps=N_STEPS,
                                base_step=BASE_STEP_S)],
            env=env)
        for i in range(2)
    ]

    with telemetry.span("smoke.scrape", stage="smoke"):
        deadline = time.time() + 30
        body = ""
        # wait for real snapshot samples from both ranks (the heartbeat
        # AGE gauges appear at brokering time, before any data arrives —
        # matching bare rank="N" would race the first beat)
        while time.time() < deadline:
            body = urllib.request.urlopen(f"{url}/metrics").read().decode()
            if ('dmlc_smoke_beats{rank="0"}' in body
                    and 'dmlc_smoke_beats{rank="1"}' in body):
                break
            time.sleep(0.1)
        else:
            fail(f"both ranks never appeared in /metrics; got:\n{body[:2000]}")

    validate_anomalies(url)
    validate_dmlc_top(url)

    # re-scrape so the step-ledger + anomaly families are present
    body = urllib.request.urlopen(f"{url}/metrics").read().decode()
    n = validate_prometheus(body)
    for want in ('rank="0"', 'rank="1"', 'rank="all"',
                 "dmlc_feed_producer_stall_secs_bucket",
                 "dmlc_tracker_ranks_reporting 2",
                 "dmlc_build_info{",
                 'dmlc_heartbeat_age_seconds{rank="0"}',
                 'dmlc_heartbeat_age_seconds{rank="1"}',
                 'dmlc_step_time_secs_bucket{rank="0"',
                 'dmlc_step_goodput_tokens_per_s{rank="1"}',
                 'dmlc_anomaly_active{rank="1",kind="straggler"} 1',
                 'dmlc_anomaly_active{rank="0",kind="straggler"} 0',
                 'dmlc_anomaly_straggler_flags{rank="tracker"}',
                 'dmlc_anomaly_active{rank="1",kind="recompile_storm"} 1',
                 'dmlc_anomaly_recompile_storm_flags{rank="tracker"}'):
        if want not in body:
            fail(f"missing {want!r} in /metrics payload")
    print(f"telemetry smoke: /metrics OK ({n} samples, strict exposition)")

    hz = json.loads(urllib.request.urlopen(f"{url}/healthz").read())
    if hz.get("ranks_reporting", 0) < 2:
        fail(f"/healthz reports {hz} (< 2 ranks)")
    print(f"telemetry smoke: /healthz OK ({hz['ranks_reporting']} ranks)")

    for w in workers:
        if w.wait(timeout=120) != 0:
            fail(f"worker exited {w.returncode}")
    tracker.join(timeout=30)
    validate_merged_trace(url)
    tracker.close()

    trace = json.loads(telemetry.to_chrome_trace_json())
    complete = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    if not complete:
        fail("Chrome trace has no complete events")
    for ev in complete:
        for k in ("name", "ts", "dur", "pid", "tid"):
            if k not in ev:
                fail(f"Chrome trace event missing {k!r}: {ev}")
    print(f"telemetry smoke: Chrome trace OK "
          f"({len(complete)} complete events)")
    print("telemetry smoke OK")


if __name__ == "__main__":
    main()
