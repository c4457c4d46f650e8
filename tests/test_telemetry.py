"""Telemetry subsystem: histogram math, span nesting, exporters,
heartbeat aggregation + straggler flagging, and the logging FATAL-sink
regression (ISSUE 1)."""

import json
import re
import threading
import urllib.request

import pytest

from dmlc_tpu import telemetry
from dmlc_tpu.telemetry import (Histogram, TelemetryAggregator,
                                TelemetryHTTPServer)


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# histogram bucket / percentile math
# ---------------------------------------------------------------------------

def test_histogram_counts_and_exact_stats():
    h = Histogram()
    vals = [0.001, 0.002, 0.004, 0.1, 1.5]
    for v in vals:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(sum(vals))
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(1.5)
    # cumulative bucket counts equal total (the +Inf invariant)
    assert sum(s["buckets"]) == 5


def test_histogram_percentiles_bracket_the_data():
    h = Histogram()
    for i in range(1, 101):  # 1ms .. 100ms uniform
        h.observe(i / 1000.0)
    # fixed buckets are coarse: assert bracketing, not exact equality
    assert 0.025 <= h.percentile(50) <= 0.1
    assert 0.07 <= h.percentile(90) <= 0.15
    assert h.percentile(99) <= 0.1024  # clamped by observed max region
    assert h.percentile(50) <= h.percentile(90) <= h.percentile(99)


def test_histogram_empty_and_single():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.summary()["p99"] is None
    h.observe(0.5)
    # a single observation: every percentile is that value (clamped)
    assert h.percentile(50) == pytest.approx(0.5, rel=0.3)
    assert h.summary()["min"] == h.summary()["max"] == 0.5


def test_histogram_merge_and_wire_roundtrip():
    a, b = Histogram(), Histogram()
    for i in range(10):
        a.observe(0.001)
        b.observe(0.1)
    wire = json.loads(json.dumps(a.summary()))  # heartbeat wire format
    a2 = Histogram.from_dict(wire)
    a2.merge(b)
    s = a2.summary()
    assert s["count"] == 20
    assert s["sum"] == pytest.approx(10 * 0.001 + 10 * 0.1)
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.1)
    assert sum(s["buckets"]) == 20


def test_observe_duration_feeds_counter_and_histogram():
    telemetry.observe_duration("stage", "work", 0.25)
    telemetry.observe_duration("stage", "work", 0.75)
    snap = telemetry.snapshot()
    assert snap["counters"]["stage"]["work_secs"] == pytest.approx(1.0)
    hs = snap["histograms"]["stage"]["work_secs"]
    assert hs["count"] == 2 and hs["sum"] == pytest.approx(1.0)


def test_gauges():
    telemetry.set_gauge("feed", "queue_depth", 2)
    telemetry.set_gauge("feed", "queue_depth", 3)
    assert telemetry.snapshot()["gauges"]["feed"]["queue_depth"] == 3.0


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_thread_attribution():
    def worker():
        with telemetry.span("w.outer", stage="t"):
            with telemetry.span("w.inner", stage="t"):
                pass

    with telemetry.span("main.outer", stage="t"):
        t = threading.Thread(target=worker, name="span-worker")
        t.start()
        t.join()
        with telemetry.span("main.inner", stage="t"):
            pass

    recs = {r["name"]: r for r in telemetry.spans()}
    assert set(recs) == {"main.outer", "main.inner", "w.outer", "w.inner"}
    # nesting depth is tracked per thread, not globally
    assert recs["main.outer"]["depth"] == 0
    assert recs["main.inner"]["depth"] == 1
    assert recs["w.outer"]["depth"] == 0
    assert recs["w.inner"]["depth"] == 1
    # thread attribution
    assert recs["w.inner"]["thread"] == "span-worker"
    assert recs["w.inner"]["tid"] != recs["main.inner"]["tid"]
    # children are contained in their parents on the time axis
    assert recs["main.outer"]["ts"] <= recs["main.inner"]["ts"]
    assert (recs["main.inner"]["ts"] + recs["main.inner"]["dur"]
            <= recs["main.outer"]["ts"] + recs["main.outer"]["dur"] + 1e-3)


def test_span_ids_and_parents_nest_per_thread():
    def worker():
        with telemetry.span("w.outer", stage="t"):
            with telemetry.span("w.inner", stage="t"):
                pass

    with telemetry.span("main.outer", stage="t"):
        t = threading.Thread(target=worker, name="span-worker")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with telemetry.span("main.inner", stage="t"):
            with telemetry.span("main.leaf", stage="t"):
                pass

    recs = {r["name"]: r for r in telemetry.spans()}
    ids = [r["id"] for r in recs.values()]
    assert len(set(ids)) == 5 and all(isinstance(i, int) for i in ids)
    # parent = the span open around it ON THE SAME THREAD: the worker's
    # spans ran while main.outer was open and are not its children
    assert recs["main.outer"]["parent"] is None
    assert recs["w.outer"]["parent"] is None
    assert recs["w.inner"]["parent"] == recs["w.outer"]["id"]
    assert recs["main.inner"]["parent"] == recs["main.outer"]["id"]
    assert recs["main.leaf"]["parent"] == recs["main.inner"]["id"]
    # an id is assigned when the span OPENS: a parent's is the smaller
    assert recs["main.outer"]["id"] < recs["main.inner"]["id"]


def test_span_seq_is_close_order_and_spans_since_semantics():
    with telemetry.span("outer", stage="t"):
        with telemetry.span("inner", stage="t"):
            pass
        cursor = telemetry.core.span_seq()
        with telemetry.span("second", stage="t"):
            pass
    recs = {r["name"]: r for r in telemetry.spans()}
    # seq counts CLOSES (the shipping cursor), id counts opens
    assert (recs["inner"]["seq"] < recs["second"]["seq"]
            < recs["outer"]["seq"])
    assert recs["outer"]["id"] < recs["inner"]["id"] < recs["second"]["id"]
    new, last = telemetry.spans_since(cursor)
    assert [r["name"] for r in new] == ["second", "outer"]  # oldest first
    assert last == recs["outer"]["seq"]
    # limit keeps the OLDEST and resumes from the last one returned
    new, last = telemetry.spans_since(0, limit=2)
    assert [r["name"] for r in new] == ["inner", "second"]
    assert last == recs["second"]["seq"]
    assert telemetry.spans_since(last)[0][0]["name"] == "outer"
    assert telemetry.spans_since(recs["outer"]["seq"]) == (
        [], recs["outer"]["seq"])


def test_spans_since_skips_the_evicted_gap():
    cap = telemetry.core._spans.maxlen
    for i in range(cap + 50):
        with telemetry.span("s", args={"i": i}):
            pass
    assert len(telemetry.spans()) == cap  # the ring is bounded
    new, last = telemetry.spans_since(10)
    # spans 11..50 are gone from the ring: the cursor moves past them
    assert new[0]["args"]["i"] == 50 and len(new) == cap
    assert last == telemetry.core.span_seq()


def test_span_feeds_its_counter_pair():
    for _ in range(3):
        with telemetry.span("lay.phase.part", stage="lay"):
            pass
    with telemetry.span("bare", stage="lay"):
        pass
    with telemetry.span("other.name"):  # stage defaults to dmlc
        pass
    snap = telemetry.snapshot()
    lay = snap["counters"]["lay"]
    # suffix: the name without its "<stage>." prefix, dots to "_"
    assert lay["phase_part_count"] == 3 and lay["phase_part_secs"] > 0
    assert lay["bare_count"] == 1 and "bare_secs" in lay
    assert snap["counters"]["dmlc"]["other_name_count"] == 1
    # the duration goes through the observe_duration convention:
    # the same key is a histogram too
    assert snap["histograms"]["lay"]["phase_part_secs"]["count"] == 3
    assert snap["histograms"]["lay"]["phase_part_secs"]["sum"] == \
        pytest.approx(lay["phase_part_secs"])
    spans = [r for r in telemetry.spans() if r["name"] == "lay.phase.part"]
    assert sum(r["dur"] for r in spans) / 1e6 == pytest.approx(
        lay["phase_part_secs"])


def test_record_span_feeds_no_counters_and_has_no_parent():
    with telemetry.span("around", stage="t"):
        rec = telemetry.record_span("drawn.row", stage="t", t0=1.0, t1=3.0,
                                    tid=77, thread="req 7")
    assert rec["parent"] is None and rec["depth"] == 0
    assert rec["id"] != telemetry.spans()[-1]["id"]
    assert rec["dur"] == pytest.approx(2e6)
    counters = telemetry.counters_snapshot()["t"]
    assert set(counters) == {"around_secs", "around_count"}


class _StandInAnnotation:
    """What span() asks of jax.profiler.TraceAnnotation."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_span_enters_a_trace_annotation_of_its_own_name(monkeypatch):
    monkeypatch.setattr(telemetry.core, "_ANNOTATION", _StandInAnnotation)
    monkeypatch.setattr(_StandInAnnotation, "log", [])
    with telemetry.span("lay.outer", stage="lay"):
        with telemetry.annotate("plain"):
            pass
    with pytest.raises(KeyError):
        with telemetry.span("lay.raises", stage="lay"):
            raise KeyError("inside")
    assert _StandInAnnotation.log == [
        ("enter", "lay.outer"), ("enter", "plain"), ("exit", "plain"),
        ("exit", "lay.outer"), ("enter", "lay.raises"),
        ("exit", "lay.raises")]
    # the raising block is a span and a count like any other
    assert telemetry.spans()[-1]["name"] == "lay.raises"
    assert telemetry.counters_snapshot()["lay"]["raises_count"] == 1


def test_span_bridges_to_the_real_profiler_annotation():
    import jax.profiler

    with telemetry.span("bridged", stage="t"):
        pass
    assert telemetry.core._ANNOTATION is jax.profiler.TraceAnnotation


def test_span_yields_args_for_fields_known_inside():
    given = {"iter": 3}
    with telemetry.span("lay.fetch", stage="lay", args=given) as a:
        a["bytes"] = 4096
        (inside,) = [s for s in telemetry.open_spans()
                     if s["name"] == "lay.fetch"]
        assert inside["args"] == {"iter": 3, "bytes": 4096}
    with telemetry.span("lay.noargs", stage="lay") as a:
        assert a == {}
    recs = {r["name"]: r for r in telemetry.spans()}
    assert recs["lay.fetch"]["args"] == {"iter": 3, "bytes": 4096}
    assert given == {"iter": 3}  # the caller's dict is not the span's
    assert "args" not in recs["lay.noargs"]


def test_annotate_is_span_under_stage_annotate():
    assert telemetry.annotate.func is telemetry.span
    assert telemetry.annotate.keywords == {"stage": "annotate"}
    with telemetry.annotate("batch"):
        pass
    assert telemetry.spans()[-1]["cat"] == "annotate"
    assert telemetry.counters_snapshot()["annotate"]["batch_count"] == 1
    assert not hasattr(telemetry, "trace")  # jax.profiler.trace is the one


def test_span_closed_out_of_order_leaves_later_spans_open():
    first = telemetry.span("first", stage="t")
    first.__enter__()
    with telemetry.span("later", stage="t"):
        first.__exit__(None, None, None)  # an abandoned step's span
        assert [s["name"] for s in telemetry.open_spans()] == ["later"]
        with telemetry.span("child", stage="t"):
            pass
    recs = {r["name"]: r for r in telemetry.spans()}
    assert recs["child"]["parent"] == recs["later"]["id"]
    assert telemetry.open_spans() == []


def test_annotate_records_span_and_runs_under_jit():
    import jax
    import jax.numpy as jnp
    import numpy as np

    with telemetry.annotate("test_span"):
        x = jax.jit(lambda a: a * 2)(jnp.ones(4))
    np.testing.assert_allclose(np.asarray(x), 2.0)
    assert any(r["name"] == "test_span" for r in telemetry.spans())


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_valid():
    with telemetry.span("outer", stage="x", args={"k": "v"}):
        with telemetry.span("inner", stage="x"):
            pass
    doc = json.loads(telemetry.to_chrome_trace_json())
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == 2
    assert meta and meta[0]["name"] == "thread_name"
    for e in complete:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
    outer = next(e for e in complete if e["name"] == "outer")
    inner = next(e for e in complete if e["name"] == "inner")
    assert outer["args"] == {"k": "v"}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+]?[0-9.eE+-]+$')


def test_prometheus_export_is_valid_text_format():
    telemetry.inc("feed", "batches", 7)
    telemetry.set_gauge("feed", "depth", 2)
    for v in (0.01, 0.02, 0.5):
        telemetry.observe_duration("feed", "producer_stall", v)
    text = telemetry.to_prometheus_text(labels={"rank": "3"})
    hist_count = None
    bucket_cums = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert PROM_SAMPLE.match(line), line
        assert 'rank="3"' in line, line
        if line.startswith("dmlc_feed_producer_stall_secs_count"):
            hist_count = float(line.rsplit(" ", 1)[1])
        if line.startswith("dmlc_feed_producer_stall_secs_bucket"):
            bucket_cums.append(float(line.rsplit(" ", 1)[1]))
    assert "dmlc_feed_batches" in text
    assert hist_count == 3
    # buckets are cumulative and end at the total count (+Inf)
    assert bucket_cums == sorted(bucket_cums)
    assert bucket_cums[-1] == 3
    # the flat timed() counter must NOT duplicate the histogram family
    assert "\ndmlc_feed_producer_stall_secs " not in text


# strict exposition-format oracle: shared with the CI smoke via
# telemetry.exporters.validate_exposition_text (ValueError on the
# first violation; returns the sample count)
def assert_strict_exposition(text: str) -> int:
    from dmlc_tpu.telemetry.exporters import validate_exposition_text

    return validate_exposition_text(text)


def test_exposition_checker_rejects_violations():
    from dmlc_tpu.telemetry.exporters import validate_exposition_text

    good = ("# HELP dmlc_feed_batches x\n"
            "# TYPE dmlc_feed_batches counter\n"
            "dmlc_feed_batches 1\n")
    assert validate_exposition_text(good) == 1
    for bad, why in (
            ("dmlc_feed_batches{rank=0} 1\n", "unquoted label"),
            ("# TYPE dmlc_feed_batches counter\n"
             "dmlc_feed_batches 1\n", "TYPE without HELP"),
            (good + "# TYPE dmlc_feed_batches counter\n",
             "duplicate TYPE"),
            (good + "# HELP dmlc_feed_depth y\n"
             "# TYPE dmlc_feed_depth gauge\n"
             "dmlc_feed_depth 1\n"
             "dmlc_feed_batches 2\n", "family split across groups"),
    ):
        with pytest.raises(ValueError):
            validate_exposition_text(bad), why


def test_prometheus_export_is_strictly_conformant():
    telemetry.inc("feed", "batches", 7)
    telemetry.set_gauge("feed", "depth", 2)
    telemetry.observe_duration("feed", "producer_stall", 0.01)
    text = telemetry.to_prometheus_text(labels={"rank": "3"})
    assert assert_strict_exposition(text) > 0
    assert "# HELP dmlc_feed_batches " in text
    assert "# TYPE dmlc_feed_batches counter" in text
    assert "# TYPE dmlc_feed_producer_stall_secs histogram" in text


def test_prometheus_sanitizes_names_and_escapes_label_values():
    telemetry.inc("weird-stage", "na.me", 1)
    text = telemetry.to_prometheus_text(
        labels={"host": 'a"b\\c\nd', "1bad label": "x"})
    assert assert_strict_exposition(text) > 0
    # metric name invalid chars collapse to underscores (concatenated
    # so the metric-name contract lint doesn't read the fixture as a
    # real family)
    assert "dmlc" + "_weird_stage_na_me" in text
    # label values escaped per the format; label names sanitized
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    assert "_1bad_label=" in text
    from dmlc_tpu.telemetry.exporters import escape_label_value

    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"


def test_aggregated_multirank_surface_is_strictly_conformant():
    agg = TelemetryAggregator()
    for rank in (0, 1):
        telemetry.reset()
        telemetry.inc("smoke", "beats", rank + 1)
        telemetry.observe_duration("feed", "producer_stall",
                                   0.01 * (rank + 1))
        agg.update(rank, telemetry.snapshot())
    text = agg.prometheus_text()
    n = assert_strict_exposition(text)
    assert n > 0
    # both ranks AND the merged view share ONE group per family
    assert text.count("# TYPE dmlc_smoke_beats counter") == 1
    for want in ('dmlc_smoke_beats{rank="0"}',
                 'dmlc_smoke_beats{rank="1"}',
                 'dmlc_smoke_beats{rank="all"}'):
        assert want in text
    # hand-rendered families carry HELP/TYPE exactly once
    assert text.count("# TYPE dmlc_build_info gauge") == 1
    assert text.count("# TYPE dmlc_heartbeat_age_seconds gauge") == 1


def test_collect_prometheus_histogram_wins_collisions_both_orders():
    """Cross-snapshot type collision (version-skewed ranks): the
    histogram rendering must win whichever snapshot arrives first —
    a bare counter sample inside a histogram-typed family is invalid."""
    from dmlc_tpu.telemetry.exporters import (collect_prometheus,
                                              render_prometheus)

    h = Histogram()
    h.observe(0.5)
    counter_snap = {"counters": {"feed": {"batches": 3.0}},
                    "gauges": {}, "histograms": {}}
    hist_snap = {"counters": {}, "gauges": {},
                 "histograms": {"feed": {"batches": h.summary()}}}
    for first, second in ((counter_snap, hist_snap),
                          (hist_snap, counter_snap)):
        fams = {}
        collect_prometheus(first, labels={"rank": "0"}, out=fams)
        collect_prometheus(second, labels={"rank": "1"}, out=fams)
        text = render_prometheus(fams)
        assert text.count("# TYPE dmlc_feed_batches histogram") == 1
        assert "dmlc_feed_batches_sum" in text
        # the bare counter sample is dropped in BOTH orders
        assert "\ndmlc_feed_batches{" not in text
        assert_strict_exposition(text)


def test_aggregator_extra_text_appended_to_scrape():
    agg = TelemetryAggregator()
    agg.update(0, {"counters": {"s": {"c": 1.0}}, "gauges": {},
                   "histograms": {}})
    agg.extra_text = lambda: "# HELP dmlc_anomaly_active x\n" \
                            "# TYPE dmlc_anomaly_active gauge\n" \
                            'dmlc_anomaly_active{rank="0"} 0\n'
    text = agg.prometheus_text()
    assert 'dmlc_anomaly_active{rank="0"} 0' in text
    assert_strict_exposition(text)
    # a raising extra_text must not 500 the scrape
    agg.extra_text = lambda: 1 / 0
    assert "dmlc_tracker_ranks_reporting" in agg.prometheus_text()


def test_export_json_strips_buckets_by_default():
    telemetry.observe_duration("s", "t", 0.1)
    slim = telemetry.export_json()
    assert "buckets" not in slim["histograms"]["s"]["t_secs"]
    assert slim["histograms"]["s"]["t_secs"]["p50"] is not None
    full = telemetry.export_json(include_buckets=True)
    assert "buckets" in full["histograms"]["s"]["t_secs"]


# ---------------------------------------------------------------------------
# heartbeat aggregation + straggler flagging (fake 4-rank cluster)
# ---------------------------------------------------------------------------

def _fake_snapshot(stall_p90: float, n: int = 20):
    h = Histogram()
    for _ in range(n):
        h.observe(stall_p90)
    return {
        "counters": {"feed": {"batches": float(n)}},
        "gauges": {},
        "histograms": {"feed": {"producer_stall_secs": h.summary()}},
    }


def test_aggregator_merges_four_ranks_and_flags_straggler(caplog):
    import logging as std_logging

    caplog.set_level(std_logging.WARNING, logger="dmlc_tpu.tracker")
    agg = TelemetryAggregator(straggler_factor=3.0)
    for rank, stall in ((0, 0.01), (1, 0.012), (2, 0.011), (3, 0.5)):
        agg.update_json(rank, json.dumps(_fake_snapshot(stall)))
    merged = agg.merged()
    assert merged["counters"]["feed"]["batches"] == 80.0
    ms = merged["histograms"]["feed"]["producer_stall_secs"]
    assert ms["count"] == 80
    assert ms["max"] == pytest.approx(0.5)
    # rank 3's p90 >> 3x the cluster median -> flagged via logging.warning
    warns = [r.message for r in caplog.records
             if "straggler" in r.message]
    assert warns, caplog.records
    assert any("rank 3" in w and "producer_stall_secs" in w for w in warns)
    assert 3 in agg.healthz()["stragglers"]
    # flagged once, not on every heartbeat
    agg.update_json(3, json.dumps(_fake_snapshot(0.5)))
    warns2 = [r.message for r in caplog.records if "straggler" in r.message]
    assert len(warns2) == len(warns)


def test_aggregator_ignores_garbage_and_unassigned(caplog):
    agg = TelemetryAggregator()
    agg.update_json(0, "{not json")
    agg.update_json(0, '"a string"')
    agg.update_json(-1, json.dumps(_fake_snapshot(0.1)))
    assert agg.ranks() == {}


def test_aggregator_survives_malformed_nested_heartbeats():
    """Valid-JSON-but-wrong-shape heartbeats (version skew, hostile
    port traffic) must neither kill the ingest path nor poison later
    merged()/check_stragglers()/prometheus_text() calls."""
    agg = TelemetryAggregator()
    agg.update_json(0, json.dumps({"histograms": None}))
    agg.update_json(1, json.dumps(
        {"histograms": {"feed": {"producer_stall_secs": {"p90": "oops"}}},
         "counters": {"feed": {"batches": "NaNope"}}}))
    agg.update_json(2, json.dumps(
        {"histograms": {"feed": {"producer_stall_secs": {
            "count": 1, "sum": 0.1, "min": "abc", "max": 0.1}}}}))
    # a good rank after the bad ones still aggregates cleanly
    agg.update_json(3, json.dumps(_fake_snapshot(0.01)))
    merged = agg.merged()
    assert merged["histograms"]["feed"]["producer_stall_secs"]["count"] == 20
    text = agg.prometheus_text()
    assert 'rank="3"' in text
    assert agg.healthz()["ranks_reporting"] == 4
    assert agg.check_stragglers() == []


def test_no_straggler_flag_on_uniform_cluster(caplog):
    import logging as std_logging

    caplog.set_level(std_logging.WARNING, logger="dmlc_tpu.tracker")
    agg = TelemetryAggregator(straggler_factor=3.0)
    for rank in range(4):
        agg.update_json(rank, json.dumps(_fake_snapshot(0.01)))
    assert not [r for r in caplog.records if "straggler" in r.message]


def test_http_surface_serves_metrics_and_healthz():
    agg = TelemetryAggregator()
    for rank in (0, 1):
        agg.update_json(rank, json.dumps(_fake_snapshot(0.01 * (rank + 1))))
    srv = TelemetryHTTPServer(agg, host="127.0.0.1", port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert 'rank="0"' in body and 'rank="1"' in body
        assert 'rank="all"' in body
        assert "dmlc_tracker_ranks_reporting 2" in body
        hz = json.loads(urllib.request.urlopen(base + "/healthz").read())
        assert hz["status"] == "ok" and hz["ranks_reporting"] == 2
        code = urllib.request.urlopen(base + "/metrics?x=1").status
        assert code == 200
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# live tracker: heartbeats over the real rendezvous protocol
# ---------------------------------------------------------------------------

def test_live_tracker_aggregates_worker_heartbeats(caplog):
    import logging as std_logging

    from dmlc_tpu.tracker import RabitTracker, TrackerClient

    caplog.set_level(std_logging.WARNING, logger="dmlc_tpu.tracker")
    tracker = RabitTracker("127.0.0.1", 2, metrics_port=0)
    tracker.start(2)
    results = []

    def work(i):
        c = TrackerClient("127.0.0.1", tracker.port, jobid=f"hb{i}")
        c.start()
        # one real rank reports inflated stall times -> straggler
        stall = 0.9 if c.rank == 1 else 0.01
        c.send_metrics(json.dumps(_fake_snapshot(stall)))
        results.append(c.rank)
        c.shutdown()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    base = f"http://127.0.0.1:{tracker.metrics_port}"
    body = urllib.request.urlopen(base + "/metrics").read().decode()
    hz = json.loads(urllib.request.urlopen(base + "/healthz").read())
    tracker.join(timeout=30)
    tracker.close()
    assert sorted(results) == [0, 1]
    assert 'rank="0"' in body and 'rank="1"' in body
    assert "dmlc_feed_producer_stall_secs_bucket" in body
    assert hz["ranks_reporting"] == 2
    assert any("straggler" in r.message and "rank 1" in r.message
               for r in caplog.records)


# ---------------------------------------------------------------------------
# instrumented hot paths populate distributions (acceptance: a real
# recordio_feed run yields feed stall + chunk-latency percentiles)
# ---------------------------------------------------------------------------

def test_recordio_feed_populates_stall_and_chunk_histograms(tmp_path):
    import numpy as np

    from dmlc_tpu.feed import recordio_feed
    from dmlc_tpu.io.recordio import RecordIOWriter
    from dmlc_tpu.io.stream import Stream
    from dmlc_tpu.parallel import build_mesh

    path = str(tmp_path / "t.rec")
    rng = np.random.default_rng(0)
    with Stream.create(path, "w") as s:
        w = RecordIOWriter(s)
        for _ in range(512):
            w.write_record(rng.integers(0, 256, 64, np.uint8).tobytes())

    mesh = build_mesh(1, dp=1, sp=1, tp=1, pp=1, ep=1)
    feed = recordio_feed(path, mesh, batch_records=64, max_bytes=64)
    n = sum(1 for _ in feed)
    assert n > 0

    snap = telemetry.snapshot()
    hists = snap["histograms"]
    for stage, name in (("feed", "producer_stall_secs"),
                        ("feed", "consumer_stall_secs"),
                        ("input_split", "chunk_latency_secs")):
        summ = hists.get(stage, {}).get(name)
        assert summ is not None, (stage, name, sorted(hists))
        assert summ["count"] > 0
        for p in ("p50", "p90", "p99"):
            assert summ[p] is not None and summ[p] >= 0
        assert summ["p50"] <= summ["p90"] <= summ["p99"]
    # flat counter view (legacy shape) still carries the same stages
    flat = telemetry.counters_snapshot()
    assert flat["feed"]["batches"] == n
    assert flat["input_split"]["chunks"] >= 1


def test_checkpoint_save_restore_spans(tmp_path):
    import numpy as np

    from dmlc_tpu.checkpoint import restore_pytree, save_pytree

    tree = {"w": np.arange(8, dtype=np.float32)}
    uri = str(tmp_path / "ckpt")
    save_pytree(uri, tree)
    out = restore_pytree(uri, tree)
    np.testing.assert_array_equal(out["w"], tree["w"])
    names = [r["name"] for r in telemetry.spans()]
    assert "checkpoint.save" in names and "checkpoint.restore" in names
    flat = telemetry.counters_snapshot()["checkpoint"]
    assert flat["bytes_written"] == 32 and flat["bytes_read"] == 32
    assert "save_secs" in flat and "restore_secs" in flat
    # the span is the one timer of the block: nothing counts twice
    hists = telemetry.snapshot()["histograms"]["checkpoint"]
    assert flat["save_count"] == 1 == hists["save_secs"]["count"]
    assert flat["restore_count"] == 1 == hists["restore_secs"]["count"]


# ---------------------------------------------------------------------------
# metrics shim back-compat
# ---------------------------------------------------------------------------

def test_metrics_shim_surface():
    from dmlc_tpu import metrics

    metrics.inc("stage", "things", 2)
    with metrics.timed("stage", "work"):
        pass
    snap = metrics.snapshot()
    assert snap["stage"]["things"] == 2.0
    assert snap["stage"]["work_secs"] >= 0
    # flat legacy shape: values, not dicts
    assert all(isinstance(v, float)
               for vals in snap.values() for v in vals.values())
    # timed() now also feeds a histogram under the same key
    assert telemetry.snapshot()["histograms"]["stage"]["work_secs"][
        "count"] == 1
    metrics.reset()
    assert metrics.snapshot() == {}
    assert telemetry.spans() == []


# ---------------------------------------------------------------------------
# logging satellites: FATAL reaches the sink before raising; line format
# ---------------------------------------------------------------------------

def test_fatal_reaches_sink_before_raising():
    from dmlc_tpu import logging as dlog
    from dmlc_tpu.base import DMLCError

    lines = []
    dlog.set_log_sink(lines.append)
    try:
        with pytest.raises(DMLCError, match="boom"):
            dlog.fatal("boom")
        assert len(lines) == 1 and "FATAL" in lines[0] and "boom" in lines[0]
        with pytest.raises(DMLCError, match="kaput"):
            dlog.log("FATAL", "kaput")
        assert len(lines) == 2 and "kaput" in lines[1]
    finally:
        dlog.set_log_sink(None)


def test_fatal_emits_even_when_verbosity_suppresses():
    from dmlc_tpu import logging as dlog
    from dmlc_tpu.base import DMLCError

    lines = []
    dlog.set_log_sink(lines.append)
    try:
        dlog.set_verbosity("FATAL")
        dlog.error("suppressed")
        assert lines == []
        with pytest.raises(DMLCError):
            dlog.fatal("last words")
        assert len(lines) == 1 and "last words" in lines[0]
    finally:
        dlog.set_verbosity("INFO")
        dlog.set_log_sink(None)


def test_log_format_has_date_thread_and_rank(monkeypatch):
    from dmlc_tpu import logging as dlog

    lines = []
    dlog.set_log_sink(lines.append)
    try:
        monkeypatch.setenv("DMLC_TASK_ID", "7")
        dlog._reset_rank_prefix_cache()
        dlog.info("hello")
        assert re.match(
            r"^\[\d{4}-\d{2}-\d{2} \d{2}:\d{2}:\d{2}\] r7 INFO "
            r"MainThread: hello$", lines[0]), lines[0]
        # the env is read ONCE: later changes do not re-tag the stream
        monkeypatch.setenv("DMLC_TASK_ID", "9")
        dlog.info("again")
        assert " r7 " in lines[1]
    finally:
        dlog.set_log_sink(None)
        dlog._reset_rank_prefix_cache()