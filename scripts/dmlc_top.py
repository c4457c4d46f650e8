#!/usr/bin/env python
"""``dmlc top`` — live cluster step-health view over ssh.

Polls a running tracker's ``/anomalies`` + ``/healthz`` endpoints
(telemetry.heartbeat.TelemetryHTTPServer; enable with
``DMLC_TRACKER_METRICS_PORT``) and renders one line per rank:

    RANK  STEP ms  EWMA ms  GOODPUT tok/s  MFU%%  FEED%%  HB AGE  FLAGS  REMED

``STEP``/``EWMA`` come from each rank's shipped step-ledger records,
``FEED%%`` is the watchdog's feed-wait-fraction EWMA, ``FLAGS`` are the
watchdog's active anomaly verdicts (straggler / regression /
feed_stall / goodput_collapse), ``REMED`` is the rank's latest
self-heal remediation (``skip@<step>``, ``rollback@<step>`` — what the
worker DID about a poisoned step), and ``HB AGE`` is heartbeat
staleness from /healthz (dead ranks render as ``DEAD``).

Pointed at a serving replica (``dmlc-serve``'s port) instead of a
tracker, the same poll picks up ``/requests`` + ``/slo`` and renders a
**serving pane** under the rank table: request throughput and failure
mix, server-side TTFT decomposition (queue/prefill) and TBT p99,
preemption rate, KV occupancy, and per-objective SLO burn rates with
active violations highlighted.  Against a tracker, serving replicas'
SLO flags (``slo_ttft``/``slo_tbt``/``slo_error_rate``) appear in the
per-rank FLAGS column via the heartbeat-shipped status.

Either target also feeds a **compute pane** from ``/compute``: compile
ledger totals (traces/hits/recompiles), the recompile-storm verdict,
the step roofline (``mfu``/``membw_util``/``bound``), HBM peak and
headroom; against a tracker the same
pane shows per-rank recompile totals and storm-flagged ranks.

A **goodput pane** (``/goodput`` + ``/incidents``) shows the job-level
wall-clock decomposition against a tracker — goodput fraction,
effective tokens/s, the largest badput buckets by name — and the
newest incident forensics reports; against a serving replica the same
endpoint feeds the availability ledger (state fractions, tokens vs.
capacity).

Pointed at a **router** with an autoscaler wired, ``/fleet`` feeds a
fleet pane: replica count, aggregate utilization, the controller's
hysteresis streaks / cooldown / last decision (with ``SATURATED``
highlighted), and a per-tenant admission line (weight, admitted,
rejected) from the router ``/healthz`` tenants block.  With
``DMLC_TRACE_FLEET=1`` a **traces pane** (``/traces`` +
``/decisions``) adds the slowest recent fleet traces — trace id, TTFT
decomposition, dispatch-attempt count, replicas touched — the tail of
the cluster-brain decision audit log, and SLO exemplar trace ids.

Runs full-screen (curses) when stdout is a TTY; ``--plain`` prints one
table per refresh instead (pipe-friendly, and what the CI smoke
drives).  ``--once`` renders a single refresh and exits.

Usage:
    dmlc-top <host:port | http://host:port> [--interval 2]
             [--plain] [--once] [-n N]
"""

import argparse
import json
import sys
import time
import urllib.request

__all__ = ["fetch", "render_table", "render_serving_pane",
           "render_compute_pane", "render_fleet_pane",
           "render_traces_pane", "render_goodput_pane", "main"]

COLUMNS = ("RANK", "STEP ms", "EWMA ms", "GOODPUT", "MFU%", "FEED%",
           "HB AGE", "FLAGS", "REMED")
_FMT = "{:>5} {:>9} {:>9} {:>11} {:>6} {:>6} {:>7}  {:<12} {}"


def _remed(st: dict) -> str:
    """One-token remediation summary: skip@<step> / rollback@<step>
    (+xN when repeated)."""
    r = st.get("remediation")
    if not isinstance(r, dict) or not r.get("last_action"):
        return "-"
    out = str(r["last_action"])
    step = r.get("step")
    if isinstance(step, (int, float)):
        out += f"@{int(step)}"
    n = r.get("rollbacks") if r.get("last_action") == "rollback" \
        else r.get("skips")
    if isinstance(n, (int, float)) and n > 1:
        out += f" x{int(n)}"
    return out


def fetch(base_url: str, timeout: float = 5.0) -> dict:
    """One poll: anomalies/healthz (tracker) + requests/slo (serving
    replica) — a missing endpoint yields an empty dict, so the view
    degrades to whatever the target actually serves instead of dying
    mid-watch."""
    out = {}
    for key, path in (("anomalies", "/anomalies"), ("healthz", "/healthz"),
                      ("requests", "/requests"), ("slo", "/slo"),
                      ("compute", "/compute"), ("fleet", "/fleet"),
                      ("traces", "/traces"), ("decisions", "/decisions"),
                      ("goodput", "/goodput"), ("incidents", "/incidents")):
        try:
            with urllib.request.urlopen(base_url + path,
                                        timeout=timeout) as r:
                out[key] = json.load(r)
        except Exception:  # noqa: BLE001 - endpoint may be older/absent
            out[key] = {}
    return out


def _ms(v) -> str:
    return f"{v * 1e3:.1f}" if isinstance(v, (int, float)) else "-"


def _num(v, fmt="{:.0f}") -> str:
    return fmt.format(v) if isinstance(v, (int, float)) else "-"


def render_serving_pane(doc: dict) -> list:
    """The serving pane lines (empty when the target serves no
    /requests — i.e. it is a tracker, not a replica)."""
    summ = (doc.get("requests") or {}).get("summary") or {}
    if not summ:
        return []

    def ms(key):
        v = summ.get(key)
        return f"{v * 1e3:.1f}ms" if isinstance(v, (int, float)) else "-"

    fails = summ.get("fail_reasons") or {}
    fail_txt = (" (" + ",".join(f"{k}:{v}" for k, v in sorted(fails.items()))
                + ")") if fails else ""
    occ = summ.get("kv_occupancy")
    lines = [
        "serving  ok={} failed={}{} live={} queue={} "
        "ttft_p99={} (q_p99={} prefill_p99={}) tbt_p99={} "
        "preempt_rate={:.2f} kv_occ={}".format(
            summ.get("requests_done", 0), summ.get("requests_failed", 0),
            fail_txt, summ.get("live_requests", 0),
            summ.get("decode_queue_depth", 0),
            ms("ttft_p99_s"), ms("queue_wait_p99_s"), ms("prefill_p99_s"),
            ms("tbt_p99_s"), summ.get("preemption_rate") or 0.0,
            f"{occ * 100:.0f}%" if isinstance(occ, (int, float)) else "-")]
    slo = doc.get("slo") or {}
    objs = slo.get("objectives") or {}
    if objs:
        parts = []
        for name, o in sorted(objs.items()):
            mark = " VIOLATION" if o.get("violating") else ""
            parts.append(f"{name} {o.get('burn_fast', 0):.1f}x/"
                         f"{o.get('burn_slow', 0):.1f}x{mark}")
        lines.append("slo      burn fast/slow: " + "  ".join(parts))
    return lines


def render_compute_pane(doc: dict) -> list:
    """The compute pane lines: compile-ledger totals, the recompile-
    storm verdict, the roofline verdict and HBM headroom.  Handles both
    a replica's local ``/compute`` document (``sites``/``roofline``)
    and the tracker's cluster shape (``ranks``); empty when the target
    serves neither."""
    comp = doc.get("compute") or {}
    if not comp:
        return []

    def gb(v):
        return (f"{v / (1 << 30):.2f}GiB"
                if isinstance(v, (int, float)) else "-")

    lines = []
    if "sites" in comp:  # replica-local document
        storm = comp.get("storm") or {}
        storm_txt = ("STORM " + ",".join(
            s.get("site", "?") for s in storm.get("sites") or [])
            if storm.get("active") else "ok")
        hbm = comp.get("hbm") or {}
        lines.append(
            "compute  traces={} hits={} recompiles={} storm={} "
            "hbm_peak={} headroom={}".format(
                comp.get("traces_total", 0),
                comp.get("cache_hits_total", 0),
                comp.get("recompiles_total", 0), storm_txt,
                gb(hbm.get("peak_bytes")), gb(hbm.get("headroom_bytes"))))
        roof = comp.get("roofline") or {}
        if roof.get("bound"):
            mfu = roof.get("mfu")
            bw = roof.get("membw_util")
            lines.append(
                "roofline {} bound  mfu={} membw_util={} "
                "intensity={}".format(
                    roof["bound"],
                    _num(mfu * 100 if isinstance(mfu, (int, float))
                         else None, "{:.1f}%"),
                    _num(bw * 100 if isinstance(bw, (int, float))
                         else None, "{:.1f}%"),
                    _num(roof.get("intensity"), "{:.1f}")))
    elif comp.get("ranks"):  # tracker cluster document
        storming = comp.get("storming_ranks") or []
        parts = []
        for r, st in sorted(comp["ranks"].items(), key=lambda kv: kv[0]):
            st = st or {}
            parts.append(f"r{r}:{st.get('recompiles', 0)}")
        lines.append(
            "compute  recompiles " + " ".join(parts)
            + (f"  STORM ranks={storming}" if storming else "  storm=ok"))
    return lines


def render_fleet_pane(doc: dict) -> list:
    """The fleet pane lines (empty unless the target is a router with
    an autoscaler wired — i.e. it serves ``/fleet``): the control
    loop's live verdict plus per-tenant admission shares from the
    router /healthz tenants block."""
    fl = doc.get("fleet") or {}
    lines = []
    if fl.get("config"):
        util = fl.get("utilization")
        sat = " SATURATED" if fl.get("saturated") else ""
        hot = " slo_hot" if fl.get("slo_hot") else ""
        counters = fl.get("counters") or {}
        lines.append(
            "fleet    replicas={} owned={} util={} streaks={}↑/{}↓ "
            "cooldown={}s last={}{}{}  (ups={} downs={})".format(
                fl.get("replicas", 0), len(fl.get("owned") or []),
                _num(util, "{:.2f}"), fl.get("high_streak", 0),
                fl.get("low_streak", 0),
                _num(fl.get("cooldown_remaining_s"), "{:.0f}"),
                fl.get("last_decision", "-"), sat, hot,
                counters.get("scale_ups", 0),
                counters.get("scale_downs", 0)))
    tenants = ((doc.get("healthz") or {}).get("tenants") or {}).get(
        "tenants") or []
    if tenants:
        parts = []
        for t in tenants:
            parts.append("{}:w{:g} ok={} rej={}".format(
                t.get("tenant"), t.get("weight", 1),
                t.get("admitted", 0), t.get("rejected", 0)))
        lines.append("tenants  " + "  ".join(parts))
    return lines


def render_traces_pane(doc: dict, n: int = 5) -> list:
    """The distributed-tracing pane (empty unless the target serves
    ``/traces``/``/decisions`` — i.e. a router): the slowest recent
    fleet traces with their TTFT decomposition / attempt fan-out /
    replicas touched, the tail of the cluster-brain decision audit
    log, and any SLO exemplar trace ids (the jump from a burning
    histogram to a concrete journey to open)."""
    lines = []
    traces = (doc.get("traces") or {}).get("traces") or []
    for tr in traces[:n]:
        reps = tr.get("replicas") or []
        lat = tr.get("latency_s")
        ttft = tr.get("ttft_s")
        q = tr.get("queue_s")
        pf = tr.get("prefill_s")
        lines.append(
            "trace    {} lat={} ttft={} (q={} prefill={}) attempts={}{} "
            "replicas={}".format(
                str(tr.get("trace_id", "?"))[:16],
                _num(lat, "{:.3f}s"), _num(ttft, "{:.3f}s"),
                _num(q, "{:.3f}s"), _num(pf, "{:.3f}s"),
                tr.get("attempts", 0),
                " HEDGED" if tr.get("hedged") else "",
                ",".join(str(r) for r in reps) or "-"))
    decisions = (doc.get("decisions") or {}).get("decisions") or []
    if decisions:
        parts = []
        for d in decisions[-n:]:
            tag = d.get("kind", "?")
            who = (d.get("replica") or d.get("victim_rank")
                   or d.get("verdict") or d.get("tenant"))
            parts.append(f"{tag}({who})" if who is not None else tag)
        lines.append("decide   " + " -> ".join(parts))
    objs = (doc.get("slo") or {}).get("objectives") or {}
    ex_parts = []
    for name, o in sorted(objs.items()):
        ids = [str(e.get("trace_id", ""))[:12]
               for e in (o.get("exemplars") or [])[-3:]]
        if ids:
            ex_parts.append(f"{name}:{','.join(ids)}")
    if ex_parts:
        lines.append("exemplar " + "  ".join(ex_parts))
    return lines


def render_goodput_pane(doc: dict) -> list:
    """The goodput pane: against a tracker, the cluster wall-clock
    decomposition from ``/goodput`` — goodput fraction, effective
    tokens/s, and the largest badput buckets by name — plus the newest
    incident reports from ``/incidents``.  Against a serving replica
    the same endpoint serves the availability ledger: state fractions
    (summing to 1) and tokens served vs. capacity."""
    gp = doc.get("goodput") or {}
    lines = []
    cluster = gp.get("cluster") or {}
    if cluster.get("wall_s"):
        bad = sorted(
            ((b, s) for b, s in (cluster.get("buckets") or {}).items()
             if b != "productive" and s >= 0.05),
            key=lambda kv: -kv[1])
        lines.append(
            "goodput  {:.0f}% productive over {:.0f}s wall  eff={} tok/s"
            "  badput: {}".format(
                (cluster.get("goodput_fraction") or 0.0) * 100,
                cluster["wall_s"],
                _num(cluster.get("effective_tokens_per_s"), "{:,.0f}"),
                "  ".join(f"{b}={s:.1f}s" for b, s in bad[:5]) or "none"))
    elif gp.get("states"):  # serving replica: availability ledger
        fr = gp.get("fractions") or {}
        lines.append(
            "avail    {:.0f}% serving (drain={:.0f}% crash={:.0f}% "
            "idle={:.0f}%)  tokens={} capacity_util={}".format(
                (gp.get("availability") or 0.0) * 100,
                (fr.get("draining") or 0.0) * 100,
                (fr.get("crashed_recovering") or 0.0) * 100,
                (fr.get("starved_idle") or 0.0) * 100,
                _num(gp.get("tokens_served"), "{:,.0f}"),
                _num(gp.get("capacity_utilization"), "{:.2f}")))
    for inc in ((doc.get("incidents") or {}).get("incidents") or [])[:2]:
        lines.append("incident {} {:.0f}s: {}".format(
            inc.get("id", "?"), inc.get("duration_s") or 0.0,
            inc.get("summary", "")))
    return lines


def render_table(doc: dict, base_url: str = "") -> str:
    """The poll document as fixed-width text (one refresh)."""
    an = doc.get("anomalies") or {}
    hz = doc.get("healthz") or {}
    ranks = an.get("ranks") or {}
    ages = hz.get("ranks") or {}
    dead = {str(r) for r in hz.get("dead_ranks") or []}
    cluster = an.get("cluster") or {}
    lines = []
    med = cluster.get("median_step_s")
    lines.append(
        f"dmlc top — {base_url}  {time.strftime('%H:%M:%S')}  "
        f"ranks={hz.get('ranks_reporting', len(ranks))} "
        f"dead={sorted(dead) if dead else '[]'} "
        f"median_step={_ms(med)}ms "
        f"active_anomalies={len(an.get('active') or [])}")
    lines.append(_FMT.format(*COLUMNS))
    for r in sorted(set(ranks) | set(ages), key=lambda x: int(x)):
        st = ranks.get(r) or {}
        age = ages.get(r)
        mfu = st.get("mfu")
        feed = st.get("feed_stall_frac")
        flags = ",".join(st.get("flags") or [])
        if r in dead:
            flags = ("DEAD," + flags).rstrip(",")
        lines.append(_FMT.format(
            r,
            _ms(st.get("step_time_s")),
            _ms(st.get("step_time_ewma_s")),
            _num(st.get("goodput_tokens_per_s"), "{:,.0f}"),
            _num(mfu * 100 if isinstance(mfu, (int, float)) else None,
                 "{:.1f}"),
            _num(feed * 100 if isinstance(feed, (int, float)) else None,
                 "{:.0f}"),
            _num(age, "{:.1f}s"),
            flags or "-",
            _remed(st)))
    verdicts = (an.get("recent_verdicts") or [])[-3:]
    for v in verdicts:
        lines.append(f"  ! rank {v.get('rank')} {v.get('kind')}: "
                     f"{v.get('detail', '')}")
    lines.extend(render_serving_pane(doc))
    lines.extend(render_compute_pane(doc))
    lines.extend(render_fleet_pane(doc))
    lines.extend(render_goodput_pane(doc))
    lines.extend(render_traces_pane(doc))
    return "\n".join(lines)


def _plain_loop(url: str, interval: float, iterations: int) -> int:
    n = 0
    while True:
        print(render_table(fetch(url), url), flush=True)
        n += 1
        if iterations and n >= iterations:
            return 0
        print()
        time.sleep(interval)


def _curses_loop(url: str, interval: float, iterations: int) -> int:
    import curses

    def run(scr):
        curses.curs_set(0)
        scr.nodelay(True)
        n = 0
        while True:
            text = render_table(fetch(url), url)
            scr.erase()
            maxy, maxx = scr.getmaxyx()
            for i, line in enumerate(text.splitlines()):
                if i >= maxy - 1:
                    break
                scr.addnstr(i, 0, line, maxx - 1)
            scr.addnstr(min(maxy - 1, i + 2), 0,
                        "q to quit", maxx - 1)
            scr.refresh()
            n += 1
            if iterations and n >= iterations:
                return
            deadline = time.time() + interval
            while time.time() < deadline:
                ch = scr.getch()
                if ch in (ord("q"), ord("Q")):
                    return
                time.sleep(0.05)

    curses.wrapper(run)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dmlc-top", description=__doc__.splitlines()[0])
    ap.add_argument("tracker", help="tracker metrics endpoint: host:port "
                    "or http://host:port (DMLC_TRACKER_METRICS_PORT)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period seconds (default 2)")
    ap.add_argument("--plain", action="store_true",
                    help="print tables instead of the curses screen")
    ap.add_argument("--once", action="store_true",
                    help="render one refresh and exit")
    ap.add_argument("-n", "--iterations", type=int, default=0,
                    help="stop after N refreshes (0 = forever)")
    args = ap.parse_args(argv)
    url = args.tracker
    if not url.startswith("http"):
        url = "http://" + url
    url = url.rstrip("/")
    iterations = 1 if args.once else args.iterations
    use_curses = not args.plain and sys.stdout.isatty()
    if use_curses:
        try:
            return _curses_loop(url, args.interval, iterations)
        except Exception:  # noqa: BLE001 - no curses/terminal: degrade
            pass
    try:
        return _plain_loop(url, args.interval, iterations)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
